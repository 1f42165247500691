"""Multi-device checks, run in a subprocess with 8 placeholder devices
(tests/test_distributed.py drives this). Each check prints 'CHECK <name> OK'
or raises."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import auto_mesh
from repro.parallel.compat import shard_map_unchecked

from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import use_policy, MXU_FP32
from repro.models.config import ModelConfig
from repro.models.layers import Distribution, LOCAL
from repro.models import moe as MOE
from repro.parallel.collectives import reproducible_psum
from repro.parallel.pipeline import pipeline_apply


def check_reproducible_psum():
    """Integer psum is bitwise order-invariant; check quantize/psum/dequant
    matches a float reference within grid resolution and is deterministic."""
    mesh = auto_mesh((8,), ("dp",))
    spec = AccumulatorSpec(ovf=8, msb=8, lsb=-16)
    x = jax.random.normal(jax.random.key(0), (8, 64))

    def f(xl):
        return reproducible_psum(xl[0], "dp", spec)

    out = shard_map_unchecked(f, mesh=mesh, in_specs=P("dp"),
                              out_specs=P())(x)
    ref = np.asarray(x).sum(0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=8 * 2.0 ** -16)
    # determinism across two calls
    out2 = shard_map_unchecked(f, mesh=mesh, in_specs=P("dp"),
                               out_specs=P())(x)
    assert jnp.array_equal(out, out2)
    print("CHECK reproducible_psum OK")


def _moe_cfg(E=4, k=2):
    return ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                       n_experts=E, top_k=k)


def check_moe_tp_parity():
    """shard_map TP-MoE == local MoE (fp32)."""
    mesh = auto_mesh((2, 4), ("data", "model"))
    dist = Distribution(mesh=mesh, dp_axes=("data",), tp_axis="model")
    cfg = _moe_cfg()
    p = MOE.init_moe(jax.random.key(0), cfg.d_model, cfg.d_ff, cfg.n_experts)
    x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
    with use_policy(MXU_FP32):
        local = MOE.moe_block(x, p, cfg, LOCAL)
        dist_out = jax.jit(lambda x: MOE.moe_block(x, p, cfg, dist))(x)
    np.testing.assert_allclose(np.asarray(local), np.asarray(dist_out),
                               rtol=2e-4, atol=2e-5)
    print("CHECK moe_tp_parity OK")


def check_moe_ep_parity():
    """EP all-to-all MoE == local MoE when capacity is ample (fp32)."""
    mesh = auto_mesh((2, 4), ("data", "model"))
    dist = Distribution(mesh=mesh, dp_axes=("data",), tp_axis="model")
    cfg = _moe_cfg(E=8, k=2)
    p = MOE.init_moe(jax.random.key(0), cfg.d_model, cfg.d_ff, cfg.n_experts)
    x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
    with use_policy(MXU_FP32):
        local = MOE.moe_block(x, p, cfg, LOCAL)
        ep = jax.jit(lambda x: MOE.moe_block_ep(x, p, cfg, dist,
                                                capacity_factor=8.0))(x)
    np.testing.assert_allclose(np.asarray(local), np.asarray(ep),
                               rtol=2e-4, atol=2e-5)
    print("CHECK moe_ep_parity OK")


def check_pipeline_parity():
    """4-stage GPipe == sequential layer stack."""
    mesh = auto_mesh((4,), ("stage",))
    S, n_micro, mb, d = 4, 8, 2, 16
    keys = jax.random.split(jax.random.key(0), S)
    params = {"w": jnp.stack([jax.random.normal(k, (d, d)) / d ** 0.5
                              for k in keys])}

    def body(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    out = pipeline_apply(body, params, x, mesh, "stage")
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ params["w"][s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("CHECK pipeline_parity OK")


def check_sp_forward_parity():
    """Sequence-parallel sharded forward == single-device forward (fp32)."""
    from repro.configs import get_config
    from repro.models import forward, init
    mesh = auto_mesh((2, 4), ("data", "model"))
    dist = Distribution(mesh=mesh, dp_axes=("data",), tp_axis="model")
    cfg = get_config("llama3.2-3b").reduced()
    params = init(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    with use_policy(MXU_FP32):
        local = forward(params, cfg, {"tokens": toks}, LOCAL, remat="none")
        sharded = jax.jit(lambda p, t: forward(
            p, cfg, {"tokens": t}, dist, remat="none"))(params, toks)
    np.testing.assert_allclose(np.asarray(local), np.asarray(sharded),
                               rtol=3e-4, atol=3e-4)
    print("CHECK sp_forward_parity OK")


def check_fdp_limb_psum():
    """K-sharded FDP: limb psum == single-device GEMM, bit-for-bit, for
    every assignment of K-shards to devices (ring order permutations)."""
    from repro.core import accumulator as acc
    from repro.core import fdp
    from repro.parallel.collectives import fdp_psum

    spec = AccumulatorSpec(ovf=30, msb=30, lsb=-30)
    mesh = auto_mesh((8,), ("x",))
    a = jax.random.normal(jax.random.key(0), (8, 256))
    b = jax.random.normal(jax.random.key(1), (256, 16))
    ref = np.asarray(fdp.fdp_gemm(a, b, spec))

    def f(al, bl):
        limbs = fdp.fdp_gemm_limbs(al, bl, spec)
        return acc.to_float(spec, fdp_psum(limbs, "x", spec))

    sharded = shard_map_unchecked(f, mesh=mesh,
                                  in_specs=(P(None, "x"), P("x", None)),
                                  out_specs=P())
    rng = np.random.default_rng(0)
    S = a.shape[1] // 8
    for trial in range(3):
        # permute which device owns which K-block: the integer limb psum
        # must land on identical bits for every shard assignment
        perm = np.arange(8) if trial == 0 else rng.permutation(8)
        idx = np.concatenate([np.arange(p * S, (p + 1) * S) for p in perm])
        out = sharded(a[:, idx], b[idx, :])
        assert np.array_equal(np.asarray(out), ref), f"order {trial} drifted"
    print("CHECK fdp_limb_psum OK")


def check_mesh_reshape_logits():
    """Paper-MLP training under the deployed plan: bit-identical logits and
    loss-gradients on 1x8, 2x4 and 8x1 meshes (the mesh workload), plus one
    full make_mesh_train_step step landing on identical params."""
    from repro.configs import get_config
    from repro.core.dispatch import policy_from_plan
    from repro.launch.sharding import distribution_for
    from repro.train.loop import make_mesh_train_step
    from repro.train.optimizer import adamw
    from repro.workloads import (MeshReshapeStability, WorkloadContext,
                                 make_probe_batch)
    from repro.workloads.mesh import MESH_CAP_BITS

    cfg = get_config("paper-mlp").reduced()
    plan_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "..", "examples", "plans", "paper_mlp.json")
    policy = policy_from_plan(plan_path)
    ctx = WorkloadContext.for_model(cfg)
    rep = MeshReshapeStability.from_context(ctx).run(policy)
    assert rep.details["logits_bits"] == MESH_CAP_BITS, rep.details
    assert rep.details["grad_bits"] == MESH_CAP_BITS, rep.details
    assert rep.mesh == "1x8,2x4,4x2,8x1", rep.mesh
    # every FDP-mode site must be bit-identical across mesh factorizations
    # (its cross-device reduction goes through the limb-summed fdp_psum)
    for pat, gcfg in policy.overrides:
        if gcfg.mode != "native" and pat in rep.site_attribution:
            assert rep.site_attribution[pat] == MESH_CAP_BITS, (
                pat, rep.site_attribution[pat])

    opt = adamw(lr=1e-3)
    batch = make_probe_batch(cfg, batch_size=8, seq=8, seed=3,
                             with_targets=True)
    grad_spec = AccumulatorSpec(ovf=10, msb=10, lsb=-20)
    stepped = []
    for shape in ((1, 8), (2, 4), (8, 1)):
        mesh = auto_mesh(shape, ("data", "model"))
        dist = distribution_for(mesh, "ddp", numerics_policy=policy)
        step = make_mesh_train_step(cfg, opt, dist, fdp_grad_spec=grad_spec)
        (params, _), _metrics = step((ctx.params, opt.init(ctx.params)),
                                     batch)
        stepped.append(np.concatenate(
            [np.asarray(x).ravel() for x in jax.tree.leaves(params)]))
    assert np.array_equal(stepped[0], stepped[1]), "1x8 vs 2x4 params drift"
    assert np.array_equal(stepped[0], stepped[2]), "1x8 vs 8x1 params drift"
    print("CHECK mesh_reshape_logits OK")


def check_quantized_psum():
    """Block-scaled low-bit all-reduce over 8 devices: the mean lands within
    grid resolution of the float mean, the error-feedback residual stays
    bounded across steps (block_scale's no-clip exponent contract — a
    clipped top-of-block element would grow it linearly), and
    validate_overflow() stays quiet on benign payloads but fires on an
    error-feedback spillover that would saturate the integer range."""
    from repro.core.qformat import QuantConfig
    from repro.parallel.collectives import quantized_psum, validate_overflow

    mesh = auto_mesh((8,), ("dp",))
    cfg = QuantConfig(4, 32)
    g = jax.random.normal(jax.random.key(0), (8, 64)) * 0.1

    def f(gl, rl):
        out, new_r = quantized_psum(gl[0], "dp", cfg, mean=True,
                                    residual=rl[0])
        return out, new_r[None]

    run = shard_map_unchecked(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                              out_specs=(P(), P("dp")))
    r = jnp.zeros_like(g)
    for _ in range(6):
        out, r = run(g, r)
    # grid step per block: shared exponent from the cross-device block amax
    # (one octave of bump headroom), 4-bit payload
    amax = np.abs(np.asarray(g)).reshape(8, -1, cfg.block).max(axis=(0, 2))
    step = np.exp2(np.ceil(np.log2(amax)) - (cfg.bits - 1) + 1)
    ref = np.asarray(g).mean(0)
    err = np.abs(np.asarray(out) - ref).reshape(-1, cfg.block)
    assert (err <= 2 * step[:, None]).all(), "mean outside grid resolution"
    rmax = np.abs(np.asarray(r)).reshape(8, -1, cfg.block).max(axis=(0, 2))
    assert (rmax <= 2 * step).all(), "error-feedback residual not bounded"

    with validate_overflow():                       # benign: must not fire
        jax.block_until_ready(run(g, jnp.zeros_like(g)))
    fired = False
    try:
        with validate_overflow():                   # spillover: must fire
            jax.block_until_ready(run(g, 100.0 * jnp.ones_like(g)))
    except Exception:
        fired = True
    assert fired, "overflow guard silent on saturating spillover"
    print("CHECK quantized_psum OK")


def check_compressed_grads():
    from repro.parallel.collectives import CompressedGradReducer
    mesh = auto_mesh((8,), ("dp",))
    spec = AccumulatorSpec(ovf=4, msb=2, lsb=-8)   # coarse grid (compression)
    red = CompressedGradReducer(spec, "dp")
    g = jax.random.normal(jax.random.key(0), (8, 32)) * 0.1

    def f(gl):
        r = jnp.zeros((1, 32))
        out, new_r = red.reduce({"g": gl}, {"g": r})
        return out["g"], new_r["g"]

    out, resid = shard_map_unchecked(f, mesh=mesh, in_specs=P("dp"),
                                     out_specs=(P(), P("dp")))(g)
    ref = np.asarray(g).mean(0)
    # coarse grid: error bounded by grid step; residual carries the rest
    assert np.abs(np.asarray(out) - ref).max() < 2.0 ** -8 * 2
    assert np.abs(np.asarray(resid)).max() <= 2.0 ** -9 + 1e-7
    print("CHECK compressed_grads OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = {
        "reproducible_psum": check_reproducible_psum,
        "moe_tp_parity": check_moe_tp_parity,
        "moe_ep_parity": check_moe_ep_parity,
        "pipeline_parity": check_pipeline_parity,
        "sp_forward_parity": check_sp_forward_parity,
        "quantized_psum": check_quantized_psum,
        "compressed_grads": check_compressed_grads,
        "fdp_limb_psum": check_fdp_limb_psum,
        "mesh_reshape_logits": check_mesh_reshape_logits,
    }
    if which == "all":
        for fn in checks.values():
            fn()
    else:
        checks[which]()
