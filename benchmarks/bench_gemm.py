"""FCCM'22 throughput-table analogue: generated-kernel GEMM benchmark.

Wall-times on this CPU container are *not* TPU numbers; alongside them we
report the generator's datapath model (limbs, int-ops/MAC, modeled pJ/MAC,
modeled FPGA watts) which is the basis of the Fig. 2/3 energy axes, and the
MXU-native baseline for the same shapes.

Three sections:
  * the classic per-shape table (native / simulate / pallas targets),
  * **grad rows**: ``value_and_grad`` over a dispatched GEMM per mode (one
    forward + the two phase-dispatched backward GEMMs through the custom_vjp
    layer) so the regression gate covers gradient-dispatch overhead, and
  * **ragged rows**: the sorted-segment MoE kernel against the grouped FDP
    reference, bit-exactness asserted.

``--json out.json`` additionally writes every row machine-readably
(per-impl/per-shape wall time + modeled energy) so benchmark trajectories
can be tracked across commits (CI uploads it as an artifact); ``--quick``
trims the shapes for bounded CI lanes.
"""

import argparse
import json
import platform
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import AccumulatorSpec, FP32, generate_gemm, plan_gemm
from repro.core.energy import FREQ_HZ, gemm_power
from repro.kernels import ops as kops

# Grad rows: value_and_grad over one dispatched GEMM per execution mode —
# one forward plus the two phase-dispatched backward GEMMs (dA = G·Bᵀ,
# dB = Aᵀ·G), i.e. the training hot path through the custom_vjp dispatch.
GRAD_SHAPES = [(64, 256, 64)]
QUICK_GRAD_SHAPES = [(32, 128, 32)]

SHAPES = [(64, 256, 64), (128, 512, 128)]
QUICK_SHAPES = [(32, 128, 32)]
# quick mode adds native-only rows at these shapes: the bench-regression
# gate anchors its cross-machine speed calibration on the native (pure-XLA)
# rows, and sub-millisecond samples are too noisy to anchor on — these run
# several ms per call, comfortably above the gate's noise floor, at
# negligible bench cost (no FDP kernels run for them).
QUICK_NATIVE_ANCHORS = [(256, 1024, 256), (384, 1536, 384), (512, 2048, 512)]
SPECS = [AccumulatorSpec.paper_91bit(), AccumulatorSpec(9, 6, -20)]

ROWS: list = []                 # machine-readable mirror of every CSV line


def emit(name, seconds_per_call, derived, *, shape=None, spec=None,
         impl=None, unit="us"):
    """Print the classic CSV line and mirror it into ROWS for --json."""
    val = seconds_per_call * 1e6 if unit == "us" else seconds_per_call
    fmtv = f"{val:.0f}" if unit == "us" else f"{val:.2f}"
    print(f"{name},{fmtv},{derived}")
    row = {"name": name, "seconds_per_call": seconds_per_call,
           "derived": derived}
    if impl:
        row["impl"] = impl
    if shape is not None:
        M, K, N = shape
        macs = M * K * N
        row["shape"] = {"M": M, "K": K, "N": N}
        if seconds_per_call > 0:
            row["gflops"] = 2 * macs / seconds_per_call / 1e9
        if spec is not None or impl == "native":
            p = gemm_power(FP32, spec)
            row["modeled"] = {
                "watts_fpga": p.watts,
                "energy_j_per_call": p.energy_joules(macs),
                "freq_hz": FREQ_HZ,
            }
    ROWS.append(row)


def timeit(fn, *args, reps=3):
    """Best-of-``reps`` after a compile+warm call: on this container's
    shared CPU a mean absorbs throttling bursts and swings 2-4x between
    runs; the minimum is the stable machine-capability number the
    regression gate can anchor on."""
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run_table(shapes=SHAPES, specs=SPECS):
    rng = np.random.default_rng(0)
    print("name,us_per_call,derived")
    for (M, K, N) in shapes:
        a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        flops = 2 * M * K * N

        g_native = generate_gemm(None, FP32, "native")
        s = timeit(g_native.fn, a, b)
        emit(f"gemm_native_f32_{M}x{K}x{N}", s,
             f"GFLOPs={flops/s/1e9:.2f}|{g_native.report.describe()!r}",
             shape=(M, K, N), impl="native")

        for spec in specs:
            for target in ("simulate", "pallas"):
                g = generate_gemm(spec, FP32, target)       # tile: auto-plan
                s = timeit(g.fn, a, b, reps=3)
                r = g.report
                emit(f"gemm_{target}_w{spec.width}_{M}x{K}x{N}", s,
                     f"GFLOPs={flops/s/1e9:.3f}"
                     f"|limbs={r.num_limbs}|intops/mac={r.int_ops_per_mac}"
                     f"|pJ/MAC={r.pj_per_mac_tpu_model:.1f}"
                     f"|P_fpga={r.watts_fpga_model:.3f}W",
                     shape=(M, K, N), spec=spec, impl=target)
    # bit-exactness cross-check at bench shapes
    spec = AccumulatorSpec.paper_91bit()
    gs = generate_gemm(spec, FP32, "simulate")
    gp = generate_gemm(spec, FP32, "pallas", tile=(32, 32, 128))
    a = jnp.asarray(rng.standard_normal((48, 160)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((160, 24)), jnp.float32)
    same = bool(jnp.array_equal(gs.fn(a, b), gp.fn(a, b)))
    emit("gemm_parity_check", 0, f"bitexact={same}")
    assert same


def run_grad_rows(shapes=GRAD_SHAPES):
    """Backward-pass dispatch rows: ``value_and_grad`` over one dispatched
    GEMM per mode, so the regression gate covers the custom_vjp gradient
    dispatch overhead (policy lookup + two bwd-site GEMMs), not just the
    forward kernels. The ``gflops`` figure counts all three GEMMs."""
    from repro.core.dispatch import (FDP91, MXU_FP32, GemmConfig,
                                     NumericsPolicy, gemm, use_policy)

    spec = AccumulatorSpec.paper_91bit()
    policies = [
        ("native_f32", MXU_FP32, None),
        ("simulate_w91", FDP91, spec),
        ("pallas_w91",
         NumericsPolicy(GemmConfig(FP32, spec, "pallas"), name="pallas91"),
         spec),
    ]
    rng = np.random.default_rng(3)
    for (M, K, N) in shapes:
        a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        flops = 3 * 2 * M * K * N              # fwd + dA + dB
        for tag, policy, acc in policies:
            def loss(x, y):
                return gemm(x, y, site="bench_grad").sum()

            with use_policy(policy):
                vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
                s = timeit(lambda: vg(a, b)[1][0])
            emit(f"gemm_grad_{tag}_{M}x{K}x{N}", s,
                 f"GFLOPs={flops/s/1e9:.3f}|fwd+dA+dB",
                 shape=(M, K, N), spec=acc, impl=f"grad_{tag.split('_')[0]}")
            # emit() assumes one GEMM per call; a grad call runs three
            # (fwd + dA + dB), so both derived figures scale by 3
            ROWS[-1]["gflops"] = flops / s / 1e9
            if "modeled" in ROWS[-1]:
                ROWS[-1]["modeled"]["energy_j_per_call"] *= 3


def run_native_anchors(shapes=QUICK_NATIVE_ANCHORS):
    """Native-only rows for the regression gate's machine-speed anchor."""
    rng = np.random.default_rng(2)
    for (M, K, N) in shapes:
        a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        flops = 2 * M * K * N
        g = generate_gemm(None, FP32, "native")
        s = timeit(g.fn, a, b, reps=5)
        emit(f"gemm_native_f32_{M}x{K}x{N}", s, f"GFLOPs={flops/s/1e9:.2f}",
             shape=(M, K, N), impl="native")


def _best_of(fn, reps=2):
    """Compile+warm once, then best wall-clock of ``reps`` (the container's
    cpu-share throttling makes single samples noisy)."""
    out = jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best, out


# Ragged (MoE expert) GEMM: tokens sorted by expert. (T, d, f, E).
RAGGED_CASES = [(256, 128, 128, 8)]
QUICK_RAGGED_CASES = [(128, 64, 64, 4)]


def _uneven_groups(T, E):
    """Deterministic uneven segment sizes summing to T, with one
    intentionally empty expert (the routing edge case the sorted-segment
    kernel must not mis-walk)."""
    w = np.arange(1, E + 1, dtype=np.int64)
    gs = (w * T) // w.sum()
    gs[0] += T - gs.sum()
    if E > 2:
        gs[0] += gs[1]
        gs[1] = 0
    return np.asarray(gs, np.int64)


def run_ragged_rows(cases=RAGGED_CASES):
    """MoE ragged-GEMM rows: XLA's native ragged_dot anchor, the grouped FDP
    reference (every expert over every token, O(T*E*d*f) MACs, then select),
    and the sorted-segment FDP kernel (contiguous segment walk, O(T*d*f)).
    All three gflops figures count the *useful* work 2*T*d*f, so the
    reference row's deficit vs the segment row is exactly the E-fold
    wasted-MAC factor this kernel removes. Reference and segment outputs are
    asserted bit-identical."""
    spec = SPECS[0]
    rng = np.random.default_rng(7)
    for (T, d, f, E) in cases:
        gs_np = _uneven_groups(T, E)
        gs = jnp.asarray(gs_np, jnp.int32)
        x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32)
        flops = 2 * T * d * f
        tag = f"{T}x{d}x{f}_E{E}"

        if hasattr(jax.lax, "ragged_dot"):
            native = jax.jit(lambda: jax.lax.ragged_dot(x, w, gs))
        else:  # dense one-hot contraction: still pure-XLA, still an anchor
            seg_oh = jnp.asarray(np.repeat(np.arange(E), gs_np))
            oh = jax.nn.one_hot(seg_oh, E, dtype=jnp.float32)
            native = jax.jit(lambda: jnp.einsum("td,te,edf->tf", x, oh, w))
        t_nat, _ = _best_of(native)
        emit(f"ragged_native_{tag}", t_nat, f"GFLOPs={flops/t_nat/1e9:.3f}",
             shape=(T, d, f), impl="native", unit="s")

        # token-axis block at the mean segment size (what the dispatch
        # ragged path deploys): boundary-tile overhead stays O(E*bm) << T
        from repro.core.dispatch import _fit_ragged
        plan = _fit_ragged(plan_gemm(T, f, d, fmt=FP32, spec=spec),
                           "bm", T, E)
        seg = np.repeat(np.arange(E), gs_np)

        def reference():
            outs = jnp.stack([kops.fdp_gemm(x, w[e], spec=spec, plan=plan)
                              for e in range(E)])
            return outs[seg, np.arange(T)]

        t_ref, out_ref = _best_of(reference)
        emit(f"ragged_fdp_reference_w{spec.width}_{tag}", t_ref,
             f"GFLOPs={flops/t_ref/1e9:.3f}|grouped O(T*E) MACs",
             shape=(T, d, f), spec=spec, impl="ragged_reference", unit="s")

        t_seg, out_seg = _best_of(
            lambda: kops.fdp_ragged_gemm(x, w, gs, spec=spec, plan=plan))
        same = bool(jnp.array_equal(out_ref, out_seg))
        emit(f"ragged_fdp_segment_w{spec.width}_{tag}", t_seg,
             f"GFLOPs={flops/t_seg/1e9:.3f}|speedup={t_ref/t_seg:.1f}x"
             f"|bitexact={same}",
             shape=(T, d, f), spec=spec, impl="ragged_segment", unit="s")
        assert same, "sorted-segment kernel diverged from grouped reference"


def run(quick: bool = False, json_path: str | None = None):
    ROWS.clear()
    t0 = time.time()
    if quick:
        run_table(shapes=QUICK_SHAPES, specs=[SPECS[0]])
        run_grad_rows(shapes=QUICK_GRAD_SHAPES)
        run_native_anchors()
        run_ragged_rows(cases=QUICK_RAGGED_CASES)
    else:
        run_table()
        run_grad_rows()
        run_ragged_rows()
    if json_path:
        doc = {
            "bench": "bench_gemm",
            "quick": quick,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "wall_seconds": time.time() - t0,
            "rows": ROWS,
        }
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {len(ROWS)} rows to {json_path}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable rows (BENCH_gemm.json)")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI lane)")
    args = ap.parse_args(argv)
    run(quick=args.quick, json_path=args.json)


if __name__ == "__main__":
    main()
