#!/usr/bin/env python3
"""Smoke test on a TPU: the Pallas FDP kernels and the routed serving path.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh path, on four chips

One chip runs three phases in one process and stops non-zero at the first
failure:

1. ``device``  - JAX must see a TPU (no silent fall back to the CPU).
2. ``kernels`` - the 2-D, batched and sorted-segment Pallas kernels at
   qwen3-0.6b site shapes compile as ``tpu_custom_call`` and match the
   ``simulate`` oracle bit for bit; one small GEMM also matches the host
   ``Fraction`` oracle.
3. ``serving`` - qwen3-0.6b at its published widths (weights from
   ``--seed``) serves chat, solve and repro requests through
   ``PlanRouter`` -> ``BucketedEnginePool`` -> ``RoutedFrontend``; then one
   solve prompt served through a ``ContinuousBatcher`` under the Pallas FDP
   policy must give the same tokens as under ``/fdp91``.

``--chips 4`` runs only the mesh path: one ``make_mesh_train_step`` step of
qwen3-0.6b on the 1x4, 2x2 and 4x1 meshes (updated params bit-identical
across them) and a K-sharded FDP ``dispatch.gemm`` that matches one chip.

The last line of stdout is the JSON verdict, printed only when every phase
passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
PLANS = Path(__file__).resolve().parent / "examples" / "plans"
BUCKET = "4x64"              # slots x max_len of every serving engine
MAX_NEW = 4
PER_CLASS = 2                # requests of each workload class


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------
def phase_device(want: int) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"JAX sees {dev.platform!r}, not a TPU (did libtpu initialize?)")
    check(len(devs) == want, f"{len(devs)} devices, --chips {want} wanted")
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# 2. kernels
# ---------------------------------------------------------------------------
def _equal(x, y) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(x), np.asarray(y))


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fdp
    from repro.core.accumulator import AccumulatorSpec
    from repro.core.formats import BF16, FP32
    from repro.core.metrics import fdp_oracle
    from repro.kernels import ops as kops

    spec = AccumulatorSpec.paper_91bit()
    key = iter(jax.random.split(jax.random.key(seed), 64))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(key), shape, jnp.float32).astype(dtype)

    def compiled(fn, *args):
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        check("tpu_custom_call" in exe.as_text(),
              "kernel did not lower to a tpu_custom_call")
        out = jax.block_until_ready(exe(*args))
        return out, dt

    sim = lambda fmt: jax.jit(lambda a, b: fdp.fdp_gemm(a, b, spec, fmt))

    # 2-D kernel at the decode-step sites (8 slots), prefill and lm_head
    for name, m, k, n, fmt in (("mlp_in", 8, 1024, 3072, FP32),
                               ("mlp_out_bf16", 8, 3072, 1024, BF16),
                               ("attn_q_prefill", 256, 1024, 2048, FP32),
                               ("lm_head", 8, 1024, 151936, FP32)):
        a = fmt.quantize(normal((m, k)))
        b = fmt.quantize(normal((k, n)))
        got, dt = compiled(lambda x, y: kops.fdp_gemm(x, y, spec=spec,
                                                      fmt=fmt), a, b)
        ok = _equal(got, sim(fmt)(a, b))
        log(f"[kernels] 2d {name} ({m}x{k})@({k}x{n}): compile {dt:.2f}s "
            f"tpu_custom_call=yes bit-equal-simulate={ok}")
        check(ok, f"2-D kernel {name} differs from simulate")

    # batched kernel at attention's (slots x kv heads) contractions
    for name, bsz, m, k, n in (("attn_qk", 32, 2, 128, 64),
                               ("attn_av", 32, 2, 64, 128)):
        a, b = normal((bsz, m, k)), normal((bsz, k, n))
        got, dt = compiled(lambda x, y: kops.fdp_gemm_batched(x, y,
                                                              spec=spec),
                           a, b)
        ref = jax.vmap(sim(FP32))(a, b)
        ok = _equal(got, ref)
        log(f"[kernels] batched {name} {bsz}x({m}x{k})@({k}x{n}): compile "
            f"{dt:.2f}s tpu_custom_call=yes bit-equal-simulate={ok}")
        check(ok, f"batched kernel {name} differs from simulate")

    # the sorted-segment pair at uneven groups (one empty)
    sizes = np.array([40, 0, 100, 60, 16, 24, 8, 8], np.int32)
    T, d, f = int(sizes.sum()), 1024, 768
    x, g, w = normal((T, d)), normal((T, f)), normal((len(sizes), d, f))
    gs = jnp.asarray(sizes)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    rows = [slice(int(bounds[e]), int(bounds[e + 1]))
            for e in range(len(sizes))]
    got, dt = compiled(lambda x_, w_, s_: kops.fdp_ragged_gemm(
        x_, w_, s_, spec=spec), x, w, gs)
    ref = jnp.concatenate([sim(FP32)(x[r], w[e]) for e, r in enumerate(rows)
                           if r.stop > r.start])
    ok = _equal(got, ref)
    log(f"[kernels] ragged fwd T={T} d={d} f={f} E={len(sizes)}: compile "
        f"{dt:.2f}s tpu_custom_call=yes bit-equal-simulate={ok}")
    check(ok, "ragged forward kernel differs from simulate")
    got, dt = compiled(lambda x_, g_, s_: kops.fdp_ragged_dw(
        x_, g_, s_, num_groups=len(sizes), spec=spec), x, g, gs)
    ref = jnp.stack([sim(FP32)(x[r].T, g[r]) if r.stop > r.start
                     else jnp.zeros((d, f), jnp.float32) for r in rows])
    ok = _equal(got, ref)
    log(f"[kernels] ragged wgrad: compile {dt:.2f}s tpu_custom_call=yes "
        f"bit-equal-simulate={ok}")
    check(ok, "ragged wgrad kernel differs from simulate")

    # one small GEMM against the host Fraction oracle, both paths
    a = normal((8, 96)) * jnp.exp2(jnp.round(3 * normal((8, 96))))
    b = normal((96, 8)) * jnp.exp2(jnp.round(3 * normal((96, 8))))
    pal = np.asarray(kops.fdp_gemm(a, b, spec=spec))
    simo = np.asarray(sim(FP32)(a, b))
    an, bn = np.asarray(a), np.asarray(b)
    orc = np.array([[fdp_oracle(an[i], bn[:, j], spec) for j in range(8)]
                    for i in range(8)], np.float32)
    ok = _equal(pal, orc) and _equal(simo, orc)
    log(f"[kernels] (8x96)@(96x8) wide-range: pallas == simulate == "
        f"Fraction oracle: {ok}")
    check(ok, "kernel/simulate disagree with the Fraction oracle")


# ---------------------------------------------------------------------------
# 3. serving
# ---------------------------------------------------------------------------
def phase_serving(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.core.accumulator import AccumulatorSpec
    from repro.core.dispatch import GemmConfig, NumericsPolicy
    from repro.core.formats import FP32
    from repro.core.schedules import preload_schedules
    from repro.launch.batching import ContinuousBatcher, Request
    from repro.models import init
    from repro.serving import (BucketedEnginePool, PlanRouter,
                               RoutedFrontend, ServeRequest, parse_buckets)

    preload_schedules(str(PLANS / "schedules"))
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: init(cfg, k))(jax.random.key(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serving] {ARCH}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size} params={n_params} "
        f"({time.perf_counter() - t0:.1f}s to init)")

    router = PlanRouter.from_manifest(PLANS, arch=cfg.name)
    pool = BucketedEnginePool(cfg, params, parse_buckets(BUCKET))
    front = RoutedFrontend(pool, router)
    prompts = jax.random.randint(jax.random.key(seed + 1),
                                 (3 * PER_CLASS, 12), 0, cfg.vocab_size)
    comps = []
    for i in range(3 * PER_CLASS):
        wl = ("chat", "solve", "repro")[i // PER_CLASS]
        plen = 6 + 3 * (i % PER_CLASS)
        comps.append(front.submit(ServeRequest(
            uid=i, prompt=[int(t) for t in prompts[i, :plen]],
            max_new=MAX_NEW, workload=wl)))
    t0 = time.perf_counter()
    front.run()
    wall = time.perf_counter() - t0
    pool_st = pool.stats()
    log(f"[serving] routed: {len(comps)} requests in {wall:.1f}s including "
        f"{pool_st['compiles']} engine compiles, bucket {BUCKET}")
    for c in comps:
        log(f"[serving]   uid={c.request.uid} {c.request.workload:5s} -> "
            f"{c.plan}: ok={c.ok} tokens={c.tokens if c.ok else c.error}")
    check(all(c.ok for c in comps), "a routed request did not complete")
    check(all(len(c.tokens) == MAX_NEW for c in comps),
          "a routed request returned the wrong number of tokens")
    fdp91 = next(c for c in comps if c.plan.endswith("/fdp91"))

    # the same solve prompt through the Pallas kernel: every GEMM is the
    # same exact FDP, so greedy decoding must land on the same tokens
    pallas = NumericsPolicy(
        GemmConfig(FP32, AccumulatorSpec.paper_91bit(), "pallas"),
        name="fdp91_pallas")
    bucket = parse_buckets(BUCKET)[0]
    t0 = time.perf_counter()
    eng = ContinuousBatcher(cfg, params, n_slots=bucket.n_slots,
                            max_len=bucket.max_len, warmup=pallas)
    dt_compile = time.perf_counter() - t0
    req = Request(uid=0, prompt=list(fdp91.request.prompt), max_new=MAX_NEW)
    eng.submit(req)
    t0 = time.perf_counter()
    eng.run()
    ok = req.out == fdp91.tokens
    log(f"[serving] pallas-policy batcher: compile {dt_compile:.1f}s, "
        f"{req.steps} steps in {time.perf_counter() - t0:.1f}s, "
        f"tokens={req.out} vs /fdp91 {fdp91.tokens}: equal={ok}")
    check(ok, "Pallas-policy tokens differ from the /fdp91 tokens")


# ---------------------------------------------------------------------------
# four chips: the mesh path
# ---------------------------------------------------------------------------
def phase_mesh(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.accumulator import AccumulatorSpec
    from repro.core.dispatch import FDP91, gemm, policy_from_plan
    from repro.launch.mesh import auto_mesh
    from repro.launch.sharding import distribution_for
    from repro.models import init
    from repro.parallel.compat import shard_map_unchecked
    from repro.train.loop import make_mesh_train_step
    from repro.train.optimizer import adamw, state_quant_from_policy
    from repro.workloads import make_probe_batch

    # K-sharded FDP GEMM: the limb psum across four chips == one chip
    a = jax.random.normal(jax.random.key(seed), (8, 3072))
    b = jax.random.normal(jax.random.key(seed + 1), (3072, 1024))
    one = jax.jit(lambda x, y: gemm(x, y, site="mlp_out", policy=FDP91))(a, b)
    mesh = auto_mesh((4,), ("x",))
    sharded = jax.jit(shard_map_unchecked(
        lambda x, y: gemm(x, y, site="mlp_out", policy=FDP91,
                          reduce_axis="x"),
        mesh=mesh, in_specs=(P(None, "x"), P("x", None)), out_specs=P()))
    ok = _equal(sharded(a, b), one)
    log(f"[mesh] K-sharded FDP gemm (8x3072)@(3072x1024) over 4 chips == "
        f"one chip: {ok}")
    check(ok, "K-sharded FDP gemm differs from one chip")

    # one full-width train step per mesh factorization
    cfg = get_config(ARCH)
    policy = policy_from_plan(str(PLANS / "qwen3_0p6b.json"))
    opt = adamw(lr=1e-3, state_quant=state_quant_from_policy(policy))
    grad_spec = AccumulatorSpec(ovf=10, msb=10, lsb=-20)
    batch = make_probe_batch(cfg, batch_size=4, seq=32, seed=seed + 2,
                             with_targets=True)
    first = None
    for shape in ((1, 4), (2, 2), (4, 1)):
        mesh = auto_mesh(shape, ("data", "model"))
        dist = distribution_for(mesh, "ddp", numerics_policy=policy)
        step = make_mesh_train_step(cfg, opt, dist, fdp_grad_spec=grad_spec)
        # the same seeded weights, made replicated on this mesh's devices
        params = jax.jit(lambda k: init(cfg, k),
                         out_shardings=NamedSharding(mesh, P()))(
                             jax.random.key(seed))
        t0 = time.perf_counter()
        carry, metrics = step((params, opt.init(params)), batch)
        del params
        leaves = [np.asarray(x) for x in jax.tree.leaves(carry[0])]
        del carry
        dt = time.perf_counter() - t0
        same = first is None or all(
            np.array_equal(x, y) for x, y in zip(first, leaves))
        first = first or leaves
        log(f"[mesh] train step {shape[0]}x{shape[1]}: loss="
            f"{float(metrics['loss']):.6f} {dt:.1f}s (compile included) "
            f"params bit-identical to 1x4: {same}")
        check(same, f"params after one step on {shape} differ from 1x4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t_start = time.perf_counter()
    try:
        device = phase_device(args.chips)
        phases = ([("mesh", phase_mesh)] if args.chips == 4 else
                  [("kernels", phase_kernels), ("serving", phase_serving)])
        for name, fn in phases:
            t0 = time.perf_counter()
            fn(args.seed)
            log(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"[chip_smoke] all phases passed in "
        f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
