"""Mesh-aware numerics: fdp_psum / merge_states exactness, sharding-aware
dispatch (reduce_axis), the collective overflow guard, launch profile
plumbing, and the mesh-reshape workload — everything that runs on one device
(the 8-device sweeps live in tests/distributed_worker.py)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import accumulator as acc
from repro.core import fdp
from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import FDP91, MXU_FP32, gemm, use_policy
from repro.parallel.collectives import (fdp_psum, reproducible_psum,
                                        validate_overflow, _grid_quantize)
from repro.launch.mesh import auto_mesh
from repro.parallel.compat import shard_map_unchecked

SPEC = AccumulatorSpec(ovf=30, msb=30, lsb=-30)


def _mesh1():
    return auto_mesh((1,), ("x",))


# ---------------------------------------------------------------------------
# Partial-K reduction state: fdp_gemm_limbs / merge_states / fdp_psum
# ---------------------------------------------------------------------------
def test_fdp_gemm_limbs_is_the_gemm_register():
    a = jax.random.normal(jax.random.key(0), (4, 32))
    b = jax.random.normal(jax.random.key(1), (32, 8))
    limbs = fdp.fdp_gemm_limbs(a, b, SPEC)
    assert limbs.shape == (4, 8, SPEC.num_limbs)
    assert limbs.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(acc.to_float(SPEC, limbs)),
                                  np.asarray(fdp.fdp_gemm(a, b, SPEC)))


def test_merge_states_bit_identical_for_any_k_split():
    a = jax.random.normal(jax.random.key(2), (4, 64))
    b = jax.random.normal(jax.random.key(3), (64, 8))
    ref = np.asarray(fdp.fdp_gemm(a, b, SPEC))
    for splits in (2, 4, 8):
        s = 64 // splits
        parts = jnp.stack([fdp.fdp_gemm_limbs(a[:, i*s:(i+1)*s],
                                              b[i*s:(i+1)*s], SPEC)
                           for i in range(splits)])
        merged = acc.merge_states(SPEC, parts)
        np.testing.assert_array_equal(
            np.asarray(acc.to_float(SPEC, merged)), ref)


def test_fdp_psum_single_device_identity():
    a = jax.random.normal(jax.random.key(4), (4, 32))
    b = jax.random.normal(jax.random.key(5), (32, 8))
    ref = np.asarray(fdp.fdp_gemm(a, b, SPEC))

    def f(al, bl):
        return acc.to_float(SPEC, fdp_psum(
            fdp.fdp_gemm_limbs(al, bl, SPEC), "x", SPEC))

    out = shard_map_unchecked(f, mesh=_mesh1(),
                              in_specs=(P(None, "x"), P("x", None)),
                              out_specs=P())(a, b)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_fdp_psum_rejects_wrong_limb_count():
    def f(x):
        return fdp_psum(x, "x", SPEC)

    with pytest.raises(AssertionError):
        shard_map_unchecked(f, mesh=_mesh1(), in_specs=P("x"),
                            out_specs=P())(jnp.zeros((1, 3, 2), jnp.int32))


# ---------------------------------------------------------------------------
# Sharding-aware dispatch: gemm(reduce_axis=...)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", [FDP91, MXU_FP32],
                         ids=["fdp_simulate", "native"])
def test_gemm_reduce_axis_matches_local(policy):
    a = jax.random.normal(jax.random.key(6), (4, 32))
    b = jax.random.normal(jax.random.key(7), (32, 8))
    with use_policy(policy):
        ref = np.asarray(gemm(a, b, site="probe"))

    def f(al, bl):
        return gemm(al, bl, site="probe", policy=policy, reduce_axis="x")

    out = shard_map_unchecked(f, mesh=_mesh1(),
                              in_specs=(P(None, "x"), P("x", None)),
                              out_specs=P())(a, b)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_gemm_reduce_axis_backward_needs_no_collectives():
    """dA_loc = G·B_locᵀ, dB_loc = A_locᵀ·G are already the local shards of
    the full gradients — a K-sharded fwd must grad exactly like local."""
    a = jax.random.normal(jax.random.key(8), (4, 32))
    b = jax.random.normal(jax.random.key(9), (32, 8))
    loss = lambda x, y, **kw: gemm(x, y, site="probe", policy=FDP91,
                                   **kw).sum()
    gref = jax.grad(loss, argnums=(0, 1))(a, b)

    def f(al, bl):
        return jax.grad(lambda x, y: loss(x, y, reduce_axis="x"),
                        argnums=(0, 1))(al, bl)

    got = shard_map_unchecked(f, mesh=_mesh1(),
                              in_specs=(P(None, "x"), P("x", None)),
                              out_specs=(P(None, "x"), P("x", None)))(a, b)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(gref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(gref[1]))


def test_gemm_reduce_axis_fdp_rejects_batched():
    def f(al, bl):
        return gemm(al, bl, site="probe", policy=FDP91, reduce_axis="x")

    with pytest.raises(NotImplementedError):
        shard_map_unchecked(f, mesh=_mesh1(),
                            in_specs=(P(None, None, "x"), P("x", None)),
                            out_specs=P())(
            jnp.zeros((2, 4, 8)), jnp.zeros((8, 4)))


# ---------------------------------------------------------------------------
# Collective payload overflow guard + axis_size
# ---------------------------------------------------------------------------
def test_overflow_guard_raises_under_validation():
    with validate_overflow():
        with pytest.raises(OverflowError):
            _grid_quantize(jnp.array([1e9]), -16, 16)


def test_overflow_guard_clean_path_and_default_off():
    with validate_overflow():
        q = _grid_quantize(jnp.array([0.25]), -16, 16)
    assert int(q[0]) == 16384
    # off by default: saturating payloads clip silently (production path)
    q = _grid_quantize(jnp.array([1e9]), -16, 16)
    assert int(q[0]) == 2 ** 15 - 1


def test_axis_size_and_mean_psum():
    def f(xl):
        return reproducible_psum(xl[0], "x", AccumulatorSpec(8, 8, -16),
                                 mean=True), jax.lax.axis_size("x")

    x = jax.random.normal(jax.random.key(10), (1, 16))
    out, n = shard_map_unchecked(f, mesh=_mesh1(), in_specs=P("x"),
                                 out_specs=(P(), P()))(x)
    assert int(n) == 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(x[0]),
                               atol=2.0 ** -16)


# ---------------------------------------------------------------------------
# Launch profile plumbing
# ---------------------------------------------------------------------------
def test_parse_mesh():
    from repro.launch.sharding import parse_mesh
    assert parse_mesh("2x4") == (2, 4)
    assert parse_mesh("8") == (8, 1)
    assert parse_mesh("1X8") == (1, 8)
    with pytest.raises(ValueError):
        parse_mesh("2x4x2")
    with pytest.raises(ValueError):
        parse_mesh("ax4")


def test_distribution_for_carries_policy():
    from repro.launch.sharding import distribution_for, make_mesh
    mesh = make_mesh("1x1")
    dist = distribution_for(mesh, "decode_tp", numerics_policy=FDP91)
    assert dist.joint_tp and dist.numerics_policy is FDP91
    assert distribution_for(mesh, "fsdp").numerics_policy is None
    with pytest.raises(ValueError):
        distribution_for(mesh, "nope")
    with pytest.raises(ValueError):
        make_mesh("3x9")


def test_make_train_step_policy_falls_back_to_dist():
    from repro.models.layers import Distribution
    from repro.train.loop import make_train_step
    from repro.train.optimizer import adamw
    from repro.configs import get_config

    cfg = get_config("paper-mlp").reduced()
    from repro.workloads import WorkloadContext
    ctx = WorkloadContext.for_model(cfg)
    dist = Distribution(mesh=None, numerics_policy=MXU_FP32)
    opt = adamw(lr=1e-3)
    step = make_train_step(cfg, opt, dist, remat="none", donate=False)
    (params, _), metrics = step((ctx.params, opt.init(ctx.params)),
                                ctx.grad_batch)
    assert np.isfinite(float(metrics["loss"]))


def test_make_mesh_train_step_1x1_matches_local():
    """On the degenerate 1x1 mesh the sharded step is the local step."""
    from repro.launch.sharding import distribution_for, make_mesh
    from repro.train.loop import make_mesh_train_step
    from repro.train.optimizer import adamw
    from repro.configs import get_config
    from repro.workloads import WorkloadContext

    cfg = get_config("paper-mlp").reduced()
    ctx = WorkloadContext.for_model(cfg)
    opt = adamw(lr=1e-3)
    dist = distribution_for(make_mesh("1x1"), "ddp",
                            numerics_policy=MXU_FP32)
    step = make_mesh_train_step(cfg, opt, dist,
                                fdp_grad_spec=AccumulatorSpec(10, 10, -20))
    (params, _), metrics = step((ctx.params, opt.init(ctx.params)),
                                ctx.grad_batch)
    assert np.isfinite(float(metrics["loss"]))
    changed = jax.tree.map(
        lambda p0, p1: not np.array_equal(np.asarray(p0), np.asarray(p1)),
        ctx.params, params)
    assert any(jax.tree.leaves(changed))


# ---------------------------------------------------------------------------
# Mesh-reshape workload + report provenance
# ---------------------------------------------------------------------------
def test_mesh_workload_registered_and_runs():
    from repro.workloads import (MeshReshapeStability, WorkloadContext,
                                 available_workloads, build_validators)
    assert "mesh" in available_workloads()
    (v,) = build_validators(("mesh",), WorkloadContext(budget_bits=10.0))
    rep = v.run(FDP91)
    assert rep.passed and rep.mesh == "1x1"
    assert rep.to_json()["mesh"] == "1x1"


def test_mesh_shapes_enumerates_factorizations():
    from repro.workloads.mesh import mesh_shapes
    assert mesh_shapes(8) == [(1, 8), (2, 4), (4, 2), (8, 1)]
    assert mesh_shapes(1) == [(1, 1)]


def test_report_mesh_field_absent_by_default():
    from repro.workloads import ValidationReport
    rep = ValidationReport(workload="w", score=1.0, threshold=0.0)
    assert rep.mesh is None and "mesh" not in rep.to_json()
    with_mesh = dataclasses.replace(rep, mesh="2x4")
    assert with_mesh.to_json()["mesh"] == "2x4"
