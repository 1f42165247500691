#!/usr/bin/env python3
"""One traced run of a serving cell on the chip, read by the program's own
names: the traced slice's idle time by the phase span open in it, the
decode step's device time by GEMM site, the queue wait, the prefill share
of the slot-steps, and the delay from each launch to the step it launched.

    python3 bench/tests/phases_on_chip.py --workload qwen3-0.6b.chat \\
        --seed 7 --seconds 40

It runs ``serve.run`` as ``bench/run.py --trace 1`` does and takes a few
readings beside it: the batchers' slot-step counts at the slice's edges,
each request's submit and admit times, the compiled step's HLO text, and
the program's spans from the trace before it is removed. Tables go to
stderr; the last stdout line is one JSON object with this traced run's
end-to-end metrics, the cell's per-layer metrics and the new readings.
Against a program without the phases, counters or site scopes, those
readings come out null.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
import weakref
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import run  # noqa: E402  (first: its import time starts setup_s)
import phases  # noqa: E402
import serve  # noqa: E402
import trace_reduce as tr  # noqa: E402


class Readings:
    """What the run's own objects say, taken as the run goes."""

    def __init__(self):
        self.batchers, self.hlo, self.comps, self.counts = [], [], [], {}
        self.t0 = self.slice_s = self.path = None
        self.trace = {}

    def slot_steps(self) -> dict:
        out = {}
        for ref in self.batchers:
            for k, v in (getattr(ref(), "slot_steps", None) or {}).items():
                out[k] = out.get(k, 0) + v
        return out

    def install(self) -> None:
        """Wrap the benchmark's probe, session and trace reader."""
        me = self
        probe_init, sess_init = serve.StepProbe.__init__, serve.Session.__init__
        end_trace, submit = serve.Session.end_trace, serve.Session.submit
        find, summarize = tr.find_xplane, tr.summarize

        def probe(self, engine, doc):
            probe_init(self, engine, doc)
            me.batchers.append(weakref.ref(engine.batcher))
            text = getattr(engine.batcher, "step_hlo_text", lambda: None)()
            if text:
                me.hlo.append(text)

        def session(self, *a, **kw):
            sess_init(self, *a, **kw)
            me.t0, me.slice_s = self.t0, self.slice_s
            me.counts["start"] = me.slot_steps()

        def end(self):
            if self.in_slice and self.now() >= self.slice_s:
                me.counts["end"] = me.slot_steps()
            end_trace(self)

        def sent(self, r):
            submit(self, r)
            me.comps.append(self.comp[r.uid])

        def found(log_dir):
            me.path = find(log_dir)
            return me.path

        def summary(devices, host):
            try:
                me.read_trace(devices, host)
            except Exception:                 # keep the run's own numbers
                traceback.print_exc()
            return summarize(devices, host)

        serve.StepProbe.__init__, serve.Session.__init__ = probe, session
        serve.Session.end_trace, serve.Session.submit = end, sent
        tr.find_xplane, tr.summarize = found, summary

    def read_trace(self, devices: dict, host: list) -> None:
        (lo, hi), = [(s, e) for n, s, e in host if n == tr.WINDOW_SPAN]
        program = phases.load(self.path)
        labels = {}
        for text in self.hlo:
            labels.update(phases.hlo_labels(text))
        runs = sum(1 for lines in devices.values()
                   for n, s, _ in lines.get("XLA Modules", [])
                   if phases.STEP_EXECUTABLE in n and lo <= s < hi)
        pairs = phases.launch_pairs(
            devices, [p for p in program if lo <= p[1] < hi])
        lag = phases.device_lag_ns(pairs)
        table = phases.phase_table(devices, host, program)
        sites = phases.site_table(devices, labels, lo, hi)
        delays = sorted(phases.launch_delays_ms(pairs))
        all_leaf = sum(e - s for lines in devices.values() for _, s, e in
                       tr.leaves([o for o in lines.get("XLA Ops", [])
                                  if lo <= o[1] and o[2] <= hi])) * 1e-9
        self.trace = {
            "step_runs": runs, "phase_idle_s": table, "site_s": sites,
            "device_lag_ms": lag * 1e-6, "phase_idle_s_lagged":
            phases.phase_table(devices, host, program, lag_ns=lag),
            "longest_gaps": longest_gaps(devices, host, program, lo, hi),
            "program_idle_share": phases.program_idle_share(table),
            "host_gap_ms": phases.host_gap_ms(table, program, lo, hi),
            "gemm_share": phases.gemm_share(sites),
            "step_leaf_s": sum(sites.values()), "all_leaf_s": all_leaf,
            "launch_delay_ms_quartiles": [
                delays[int(q * (len(delays) - 1))] for q in (0.25, 0.5, 0.75)]
            if delays else None}

    def numbers(self) -> dict:
        lo = self.t0
        hi = None if lo is None else lo + self.slice_s
        stamps = [(getattr(c, "submitted_at", None),
                   getattr(c, "admitted_at", None)) for c in self.comps]
        out = {"queue_wait_p90_ms": phases.queue_wait_p90_ms(stamps, lo, hi)
               if lo is not None else None,
               "prefill_share": phases.prefill_share(
                   self.counts.get("start", {}), self.counts.get("end", {})),
               "slot_steps": self.counts}
        out.update({k: v for k, v in self.trace.items()
                    if k not in ("phase_idle_s", "phase_idle_s_lagged",
                                 "site_s")})
        return out


def longest_gaps(devices: dict, host: list, program: list, lo: int, hi: int,
                 n: int = 5) -> list:
    """The ``n`` longest idle gaps of the first device: [seconds, {span:
    seconds}] by the innermost span open in each part."""
    lines = next(iter(devices.values()))
    busy = tr.union([(s, e) for _, s, e in lines.get("XLA Ops", [])], lo, hi)
    spans = [sp for sp in host + program if sp[0] != tr.WINDOW_SPAN]
    top = sorted(tr.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[(e - s) * 1e-9, {k: v * 1e-9 for k, v in
                              phases.idle_by_span([(s, e)], spans).items()}]
            for s, e in top]


def tables(r: Readings) -> None:
    t = r.trace
    if not t:
        return
    for key, how in (("phase_idle_s", "as traced"),
                     ("phase_idle_s_lagged", f"device events "
                      f"{t['device_lag_ms']:.3f} ms later")):
        idle = sum(t[key].values())
        print(f"phases ({how}): idle {idle:.3f} s of the slice, by the span "
              "open in it", file=sys.stderr)
        for k, v in sorted(t[key].items(), key=lambda kv: -kv[1]):
            print(f"  {k:28s} {v:9.4f} s {100 * v / idle:6.2f} %",
                  file=sys.stderr)
    runs = max(1, t["step_runs"])
    print(f"sites: {t['step_leaf_s']:.3f} s of leaf ops in {runs} runs of "
          f"the step ({t['all_leaf_s']:.3f} s of all leaf ops in the slice)",
          file=sys.stderr)
    for k, v in sorted(t["site_s"].items(), key=lambda kv: -kv[1]):
        print(f"  {k:48s} {1e3 * v / runs:9.4f} ms/step "
              f"{100 * v / t['step_leaf_s']:6.2f} %", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.Cell.from_manifest(run.read_json(run.ROOT / "BENCHMARK.json"),
                                  args.workload)
    devs = run.check_devices(cell.entry["chips"])
    run.enable_compile_cache()
    r = Readings()
    r.install()
    result, ctx, compared = serve.run(
        run.ROOT, cell.doc, cell.mix, cell.limits, cell.reference, args.seed,
        args.seconds, True, t_start=run.T_START)
    ctx["peak"] = run.peak_for(devs[0].device_kind)
    tables(r)
    print(json.dumps(run.finite({
        "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "traced_metrics": result["metrics"],
        "per_layer": {k: v["value"]
                      for k, v in run.read_layers(cell, ctx).items()},
        "readings": r.numbers()})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
