"""Batched serving driver: prefill + greedy incremental decode with a KV/SSM
cache, request batching, and per-request length masks.

Local (CPU) example:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --batch 4 --prompt-len 12 --gen 16

``--precision-plan plan.json`` serves under a numerics plan produced by the
``repro.numerics`` tailoring search instead of the default uniform policy.
``--engine continuous`` routes the same requests through the fixed-slot
``ContinuousBatcher`` with plan-aware AOT warmup (the decode step compiles
under the plan's formats before the first request arrives, so plan-served
decode hits the compile cache instead of retracing mid-request).
``--engine routed`` goes through the full serving tier (``repro.serving``):
the plan zoo's MANIFEST picks each request's numerics by workload class
(``--workload``), a bucketed AOT engine pool serves it, and per-class
routing/latency stats print at the end.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.dispatch import policy_from_plan, use_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decode_step, forward, init, init_cache, LOCAL
from repro.models.transformer import prefill


def serve(cfg, params, prompts, gen_len: int, dist=LOCAL):
    """prompts: (B, S) int32. Greedy decode gen_len tokens. Returns (B, gen)."""
    B, S = prompts.shape
    cache = init_cache(cfg, B, max_len=S + gen_len, dtype=jnp.float32)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model))
    last_logits, cache = prefill(params, cfg, batch, cache, dist)

    # weights as an argument, so the executable carries no copy of them;
    # the cache is donated, so each step updates it in place
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t, dist),
                   donate_argnums=(1,))

    out = []
    tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
    for _ in range(gen_len):
        out.append(tok)
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--precision-plan", default=None,
                    help="serve under a repro.numerics PrecisionPlan JSON")
    ap.add_argument("--mesh", default=None,
                    help="RxC (data x model) device mesh, e.g. 2x4")
    ap.add_argument("--profile", default="decode_tp",
                    choices=["fsdp", "ddp", "decode_tp"],
                    help="sharding profile when --mesh is set")
    ap.add_argument("--engine", default="simple",
                    choices=["simple", "continuous", "routed"],
                    help="simple whole-batch decode, the fixed-slot "
                         "ContinuousBatcher with plan-aware warmup, or the "
                         "workload-routed bucketed serving tier")
    ap.add_argument("--workload", default="chat",
                    help="workload class (chat/solve/repro) or explicit plan "
                         "name for --engine routed")
    ap.add_argument("--plans", default="examples/plans",
                    help="plan zoo directory for --engine routed")
    ap.add_argument("--buckets", default=None,
                    help="slots x len bucket table for --engine routed, "
                         "e.g. 2x32,4x64 (default: one bucket sized to fit)")
    ap.add_argument("--monitor", action="store_true",
                    help="serve under live calibration-envelope monitors "
                         "(envelope from --precision-plan or the zoo plan)")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="write the unified metrics registry (+ monitor "
                         "snapshot) as JSON when serving finishes")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose /metrics (Prometheus text) and "
                         "/metrics.json on this local port while serving")
    ap.add_argument("--metrics-hold", type=float, default=0.0,
                    help="keep the --metrics-port server up this many "
                         "seconds after serving completes")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the span timeline as Chrome-trace JSON")
    args = ap.parse_args(argv)

    from repro.core.schedules import preload_schedules
    enable_compile_cache()
    n_sched = preload_schedules(os.path.join(args.plans, "schedules"))
    if n_sched:
        print(f"[serve] schedule zoo: {n_sched} GEMM schedules preloaded "
              f"(warm plan cache, zero autotune misses)")

    cfg = get_config(args.arch)
    base_arch = cfg.name
    if args.reduced:
        cfg = cfg.reduced()
    params = init(cfg, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    policy = (policy_from_plan(args.precision_plan)
              if args.precision_plan else None)
    dist = LOCAL
    if args.mesh:
        if args.engine != "simple":
            raise SystemExit("--mesh is supported with --engine simple only")
        from repro.launch import sharding as shd
        mesh = shd.make_mesh(args.mesh)
        dist = shd.distribution_for(mesh, args.profile,
                                    numerics_policy=policy)
        params = jax.device_put(
            params, shd.param_shardings(cfg, params, mesh,
                                        profile=args.profile))
    srv = None
    if args.metrics_port is not None:
        from repro.obs import start_metrics_server
        srv = start_metrics_server(args.metrics_port)
        print(f"[serve] metrics at http://127.0.0.1:{srv.server_port}"
              f"/metrics (+ /metrics.json)")

    mon_ctx = contextlib.nullcontext(None)
    if args.monitor or args.metrics_dump:
        from repro.obs import monitoring
        envelope = None
        if args.precision_plan:
            from repro.numerics import load_plan
            envelope = (load_plan(args.precision_plan).meta
                        or {}).get("envelope")
        elif args.engine == "routed":
            import json as _json
            with open(os.path.join(args.plans, "MANIFEST.json")) as f:
                manifest = _json.load(f)
            for key, entry in sorted(manifest.get("plans", {}).items()):
                if base_arch in (key, entry.get("arch")):
                    from repro.numerics import load_plan
                    envelope = (load_plan(os.path.join(
                        args.plans, entry.get("file", f"{key}.json"))).meta
                        or {}).get("envelope")
                    break
        mon_ctx = monitoring(envelope=envelope)

    t0 = time.time()
    stack = contextlib.ExitStack()
    mon = stack.enter_context(mon_ctx)
    if args.engine == "routed":
        from repro.serving import (BucketedEnginePool, PlanRouter,
                                   RoutedFrontend, ServeRequest)
        if cfg.family not in ("dense", "moe", "vlm"):
            raise SystemExit(
                f"--engine routed supports KV-cache families "
                f"(dense/moe/vlm); {args.arch} is family={cfg.family!r} — "
                f"use the default --engine simple")
        if args.precision_plan:
            raise SystemExit("--engine routed picks plans from the zoo "
                             "MANIFEST; use --workload, not --precision-plan")
        router = PlanRouter.from_manifest(args.plans, arch=base_arch)
        buckets = args.buckets or (
            f"{args.batch}x{args.prompt_len + args.gen + 2}")
        pool = BucketedEnginePool(cfg, params, buckets)
        front = RoutedFrontend(pool, router)
        comps = [front.submit(ServeRequest(uid=i, prompt=row.tolist(),
                                           max_new=args.gen,
                                           workload=args.workload))
                 for i, row in enumerate(jnp.asarray(prompts))]
        front.run()
        toks = jnp.asarray([c.result() for c in comps])
        dt = time.time() - t0
        st = front.stats()
        for wl, cs in st["classes"].items():
            plans = ", ".join(sorted(cs["plans"]))
            print(f"[serve:routed] {wl}: {cs['completed']}/{cs['submitted']} "
                  f"ok via {plans}  mean_steps={cs['mean_steps']:.1f} "
                  f"tok/s={cs['tokens_per_s']:.1f}")
        print(f"[serve:routed] pool: {st['pool']['compiles']} compiles, "
              f"buckets={st['pool']['bucket_hits']}")
    elif args.engine == "continuous":
        from repro.launch.batching import ContinuousBatcher, Request
        if cfg.family not in ("dense", "moe", "vlm"):
            raise SystemExit(
                f"--engine continuous supports KV-cache families "
                f"(dense/moe/vlm); {args.arch} is family={cfg.family!r} — "
                f"use the default --engine simple")
        eng = ContinuousBatcher(
            cfg, params, n_slots=args.batch,
            max_len=args.prompt_len + 2 * args.gen + 2,
            warmup=policy if policy is not None else True)
        reqs = [Request(uid=i, prompt=row.tolist(), max_new=args.gen)
                for i, row in enumerate(jnp.asarray(prompts))]
        for r in reqs:
            eng.submit(r)
        eng.run()
        toks = jnp.asarray([r.out for r in reqs])
    else:
        ctx = use_policy(policy) if policy is not None \
            else contextlib.nullcontext()
        with ctx:
            toks = serve(cfg, params, prompts, args.gen, dist=dist)
    stack.close()                      # uninstall monitors, land callbacks
    dt = time.time() - t0
    plan_note = f" plan={args.precision_plan}" if args.precision_plan else ""
    print(f"[serve] {args.arch}: engine={args.engine} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s){plan_note}")
    print("sample:", toks[0].tolist())
    if mon is not None:
        print(f"[serve] monitor: worst={mon.worst_status()} over "
              f"{len(mon.statuses())} sites, "
              f"overflow_events={mon.overflow_events()}")
    if args.metrics_dump:
        import json as _json

        from repro.obs import default_registry
        dump = {"kind": "repro.obs.ServingMetricsDump", "version": 1,
                "arch": args.arch, "engine": args.engine,
                "metrics": default_registry().snapshot(),
                "monitor": mon.snapshot() if mon is not None else None}
        with open(args.metrics_dump, "w") as f:
            _json.dump(dump, f, indent=1, sort_keys=True, default=str)
        print(f"[serve] metrics dump -> {args.metrics_dump}")
    if args.trace_out:
        from repro.obs import save_chrome_trace
        n_ev = save_chrome_trace(args.trace_out)
        print(f"[serve] chrome trace ({n_ev} events) -> {args.trace_out}")
    if srv is not None:
        if args.metrics_hold > 0:
            time.sleep(args.metrics_hold)
        srv.shutdown()


if __name__ == "__main__":
    main()
