"""A serving cell: the routed tier under a traffic mix, timed, then checked.

The window drives the public serving API: ``RoutedFrontend.submit`` and
``run`` over ``PlanRouter.from_manifest(examples/plans)`` and a
``BucketedEnginePool`` with the mix's buckets. Open loop: requests due
while ``run()`` is going are submitted after each engine step (from the
benchmark's ``StepProbe`` around it), the rest between calls to ``run()``.
Closed loop: each client sends its next request from the hook that
delivers the last token of its previous one. After the window closes
nothing more is sent and the requests in flight are drained. A traced run
reads its per-layer metrics from the window's first ``TRACE_S`` seconds,
and stops the profiler at the first engine step or call boundary after
the window closes, so the stop (a minute or more) falls in the drain.
"""

from __future__ import annotations

import gc
import math
import shutil
import time
from pathlib import Path

import jax
import numpy as np

import check
import flops
import trace_reduce as tracing
import traffic
import weights

COMPILE_EVENTS = "/jax/core/compile/"
# The traced slice: a v5e's trace of a whole 40 s chat window came back
# cut (the same 2,287 steps and 28.212 s busy on every seed).
TRACE_S = 20.0


def model_config(doc: dict):
    """The program's ModelConfig for the registry arch, with every size
    the configuration file states."""
    import dataclasses
    from repro.configs import get_config
    if doc.get("tie_word_embeddings"):
        raise ValueError("the program keeps an untied lm_head")
    return dataclasses.replace(
        get_config(doc["arch"]),
        n_layers=doc["num_hidden_layers"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"],
        d_ff=doc["intermediate_size"], vocab_size=doc["vocab_size"],
        head_dim=doc["head_dim"], qkv_bias=doc["attention_bias"],
        qk_norm=doc["qk_norm"], rope_theta=doc["rope_theta"],
        norm_eps=doc["rms_norm_eps"])


def pct(values, q: float) -> float:
    """Nearest-rank percentile; a missing value is ``inf``."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)] if v else math.inf


class Session:
    """Requests, their Completions and the host times of their tokens."""

    def __init__(self, front, mix: dict, reqs: list, t0: float,
                 seconds: float, trace: bool = False):
        self.front, self.mix, self.reqs = front, mix, reqs
        self.t0, self.seconds = t0, seconds
        self.slice_s = min(seconds, TRACE_S)
        self.in_slice = self.profiling = trace
        self.stop_s = 0.0
        if trace:
            self.span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            self.span.__enter__()
        self.comp, self.times, self.sent = {}, {}, {}
        self.next = 0                              # open loop cursor
        self.queues = {}                           # closed loop: client ->
        if mix["loop"] == "closed":
            for r in reqs:
                self.queues.setdefault(r.client, []).append(r)
            for q in self.queues.values():
                q.reverse()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, r) -> None:
        from repro.serving import ServeRequest
        self.times[r.uid] = []
        self.sent[r.uid] = self.now()

        def on_token(tok, uid=r.uid, r=r):
            ts = self.times[uid]
            ts.append(time.perf_counter())
            if self.mix["loop"] == "closed" and len(ts) == r.max_new:
                self.send_next(r.client)

        self.comp[r.uid] = self.front.submit(ServeRequest(
            uid=r.uid, prompt=r.prompt, max_new=r.max_new,
            workload=self.mix["workload"], method=self.mix["method"],
            on_token=on_token))

    def tick(self) -> None:
        """After each engine step: end the traced slice and stop the
        profiler when each is due, and send the arrivals that are due."""
        self.end_trace()
        self.submit_due()

    def end_trace(self) -> None:
        """Close the traced slice once it has lasted ``slice_s``; stop the
        profiler once the window has closed."""
        now = self.now()
        if self.in_slice and now >= self.slice_s:
            self.in_slice = False
            self.span.__exit__(None, None, None)
        if not self.profiling or now < self.seconds:
            return
        self.profiling = False
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t

    def submit_due(self) -> None:
        if self.mix["loop"] != "open":
            return
        now = self.now()
        while (self.next < len(self.reqs)
               and self.reqs[self.next].due <= now
               and self.reqs[self.next].due < self.seconds):
            self.submit(self.reqs[self.next])
            self.next += 1

    def send_next(self, client) -> None:
        q = self.queues[client]
        if q and self.now() < self.seconds:
            self.submit(q.pop())

    def next_due(self) -> float:
        if self.mix["loop"] != "open" or self.next >= len(self.reqs):
            return math.inf
        return self.reqs[self.next].due

    def drive(self) -> None:
        """Send the window's requests, then drain what is in flight."""
        if self.mix["loop"] == "closed":
            for c in sorted(self.queues):
                self.send_next(c)
        while True:
            self.tick()
            m = self.front.metrics()
            if m["parked"] or m["inflight"]:
                with jax.profiler.TraceAnnotation("bench.frontend_run"):
                    self.front.run()
                continue
            nxt = min(self.next_due(),
                      self.slice_s if self.in_slice else math.inf)
            if nxt >= self.seconds:
                break
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, nxt - self.now()))
        if self.profiling:                       # nothing ran at the close
            time.sleep(max(0.0, self.seconds - self.now()))
            self.end_trace()


class StepProbe:
    """Counts, from the benchmark's side of the call, the steps the
    frontend makes an engine take: for each step its host time, the slots
    that fed a token and the model FLOPs of those tokens, with a
    ``bench.engine_step`` span around the call. After each step it calls
    ``tick`` (the session's)."""

    def __init__(self, engine, doc: dict):
        self.steps = []
        self.tick = lambda: None
        inner, batcher = engine.step, engine.batcher

        def step():
            before = [r for r in batcher.active if r is not None]
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                moved = inner()
            if moved:
                after = [r for r in batcher.active if r is not None]
                fed = {id(r): r for r in before + after}.values()
                self.steps.append((time.perf_counter(), len(fed), sum(
                    flops.token_flops(doc, r.steps) for r in fed)))
            self.tick()
            return moved

        engine.step = step

    def between(self, lo: float, hi: float) -> list:
        return [s for s in self.steps if lo <= s[0] < hi]


def _warm(front, pool, mix: dict, n_slots: int, max_len: int) -> None:
    """Serve the warm-up requests through the same API, reset the engines'
    caches (the reset path runs in the window too), and serve them again
    from the reset state."""
    from repro.serving import ServeRequest
    for _ in range(2):
        for r in traffic.warmup(mix, n_slots):
            front.submit(ServeRequest(
                uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                workload=mix["workload"], method=mix["method"],
                on_token=lambda tok: None))
        front.run()
        for eng in pool.live().values():
            eng.recycle_if_exhausted(max_len)


def run(root: Path, doc: dict, mix: dict, limits: dict, reference,
        seed: int, seconds: float, trace: bool, control: bool = False,
        t_start: float = None) -> tuple:
    """One run of a serving cell -> (result line, per-layer context,
    numbers compared with their limits)."""
    from repro.core.schedules import preload_schedules
    from repro.models import init
    from repro.serving import (BucketedEnginePool, PlanRouter,
                               RoutedFrontend, parse_buckets)

    plans = root / "examples" / "plans"
    preload_schedules(str(plans / "schedules"))
    cfg = model_config(doc)
    abstract = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    params = weights.make(abstract, seed)
    buckets = parse_buckets(mix["buckets"])
    if len(buckets) != 1:
        raise ValueError("a serving mix names one engine bucket")
    bucket = buckets[0]
    pool = BucketedEnginePool(cfg, params, buckets)
    front = RoutedFrontend(pool, PlanRouter.from_manifest(plans,
                                                          arch=cfg.name))
    _warm(front, pool, mix, bucket.n_slots, bucket.max_len)
    probes = [StepProbe(e, doc) for e in pool.live().values()]
    compiles_before = pool.stats()["compiles"]
    reqs = traffic.stream(mix, seed, cfg.vocab_size,
                          traffic.count_for(mix, seconds))
    in_window = [False]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if in_window[0] and ev.startswith(COMPILE_EVENTS) else None)

    log_dir = root / ".bench_trace"
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(log_dir), profiler_options=_trace_opts())
    setup_s = time.time() - t_start
    session = Session(front, mix, reqs, time.perf_counter(), seconds,
                      trace=trace)
    for p in probes:
        p.tick = session.tick
    in_window[0] = True
    session.drive()
    in_window[0] = False
    t_end = time.perf_counter()

    # -- what the window did -------------------------------------------------
    t0, w_end = session.t0, session.t0 + seconds
    sent = [r for r in reqs if r.uid in session.comp]
    comp = session.comp
    ok = [r for r in sent if comp[r.uid].ok
          and len(comp[r.uid].tokens) == r.max_new]
    n_tok = sum(1 for r in sent for t in session.times[r.uid]
                if t0 <= t < w_end)
    metrics = {"tokens_per_s": n_tok / seconds, "setup_s": setup_s}
    if mix["loop"] == "open":
        ttft, itl, late = [], [], []
        for r in sent:
            ts = session.times[r.uid]
            ttft.append((ts[0] - t0 - r.due) if ts and comp[r.uid].ok
                        else math.inf)
            itl.extend(np.diff(ts).tolist())
            late.append(session.sent[r.uid] - r.due)
        metrics["itl_p95_ms"] = 1e3 * pct(itl, 0.95)
        load = {"loop": "open", "rate_per_s": mix["rate_per_s"],
                "sent": len(sent), "ttft_p50_ms": 1e3 * pct(ttft, 0.50),
                "ttft_p90_ms": 1e3 * pct(ttft, 0.90),
                "generator_late_p50_ms": 1e3 * pct(late, 0.5),
                "generator_late_max_ms": 1e3 * max(late, default=0.0)}
    else:
        load = {"loop": "closed", "clients": mix["clients"],
                "sent": len(sent)}
    load["drain_s"] = t_end - w_end
    traces = [e.trace_count for e in pool.live().values()]

    dev = jax.devices()
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in dev)
    steps = [s for p in probes
             for s in p.between(t0, t0 + session.slice_s)] if trace else []
    layer_ctx = {"serve": {"n_slots": bucket.n_slots, "steps": len(steps),
                           "slot_steps": sum(s[1] for s in steps),
                           "model_flops": sum(s[2] for s in steps)}}
    done = [(r.prompt, list(comp[r.uid].tokens)) for r in ok]
    compiles_in_pool = pool.stats()["compiles"] - compiles_before

    # -- free the program's state, then the reference ----------------------
    stop_s = session.stop_s
    del front, pool, session, comp, probes
    gc.collect()
    length = mix["prompt"]["max"] + mix["output"]["max"]
    readings = check.gaps(reference.forward, params, doc, done, length,
                          control=control)
    del params
    gc.collect()

    # The control's picks stand in the program's place: its run is judged
    # by the same limits, and the program's own numbers are only reported.
    judged = readings["control" if control else "program"]
    compared = {k: [check.NUMBERS[k](judged), lim]
                for k, lim in limits.items()}
    compared.update({
        "failed_requests": [len(sent) - len(ok), 0],
        "compiles_in_window": [len(compiles) + compiles_in_pool, 0],
        "engine_traces_over_1": [sum(t - 1 for t in traces), 0]})
    correct = bool(done) and all(v <= lim for v, lim in compared.values())
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(peak_mem)}
    result = {"correct": correct, "attempted": len(sent),
              "failed": len(sent) - len(ok), "metrics": metrics,
              "device": device, "load": load,
              "checked_tokens": len(readings["program"])}
    if control:
        result["program"] = {k: check.NUMBERS[k](readings["program"])
                             for k in limits}
        result["moments"] = {side: check.moments(g)
                             for side, g in readings.items()}
    if trace:
        path = tracing.find_xplane(str(log_dir))
        t1 = time.perf_counter()
        loaded = tracing.load(path)
        t2 = time.perf_counter()
        layer_ctx["trace"] = tracing.summarize(*loaded)
        import os, sys
        print(f"trace: stop {stop_s:.1f} s, load {t2 - t1:.1f} s, summarize "
              f"{time.perf_counter() - t2:.1f} s, {os.path.getsize(path)} "
              f"bytes", file=sys.stderr)
        shutil.rmtree(log_dir, ignore_errors=True)
    return result, layer_ctx, compared


def _trace_opts():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
