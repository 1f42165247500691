"""Jitted public wrappers around the Pallas FDP GEMM kernels.

Handles non-block-multiple shapes by zero padding (exact: zero products
contribute nothing to the fixed-point register in either rounding mode),
batch-dim broadcasting for N-D inputs, and picks interpret mode automatically
off-TPU.

Tiling is **GemmPlan-first**: every entry point takes ``plan: GemmPlan``
(normally resolved by ``repro.core.dispatch`` from the plan cache / schedule
zoo) and clamps it through ``GemmPlan.fit`` — the one place a deployable
schedule is constructed, enforcing the ``SAFE_CHUNK`` carry-headroom bound
shared with the kernel. (The pre-zoo loose ``bm``/``bn``/``bk`` ints rode
one release behind a DeprecationWarning and are gone: passing them now is a
TypeError.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import GemmPlan
from repro.core.formats import FP32

from .fdp_gemm import (MAX_BK, fdp_gemm_pallas, fdp_gemm_pallas_batched,
                       fdp_ragged_dw_pallas, fdp_ragged_gemm_pallas)

# Default tile when a caller passes no plan (fitted like any other).
_DEFAULT_TILE = (128, 128, 512)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_plan(plan, M: int, N: int, K: int) -> GemmPlan:
    """Normalize the tiling argument of one kernel call into a fitted
    GemmPlan — the one deployable-schedule constructor."""
    if plan is None:
        plan = GemmPlan(*_DEFAULT_TILE)
    return plan.fit(M, N, K)


@partial(jax.jit,
         static_argnames=("spec", "fmt", "bm", "bn", "bk", "interpret"))
def _fdp_gemm_jit(a, b, *, spec, fmt, bm, bn, bk, interpret):
    M, K = a.shape
    _, N = b.shape
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    interp = (not _on_tpu()) if interpret is None else interpret
    out = fdp_gemm_pallas(a, b, spec=spec, fmt=fmt, bm=bm, bn=bn, bk=bk,
                          interpret=interp)
    return out[:M, :N]


def fdp_gemm(a: jax.Array, b: jax.Array, *, spec: AccumulatorSpec, fmt=FP32,
             plan: GemmPlan | None = None,
             interpret: bool | None = None) -> jax.Array:
    """GEMM with tailored FDP accumulation: (M,K)@(K,N) -> (M,N) f32."""
    M, K = a.shape
    _, N = b.shape
    p = resolve_plan(plan, M, N, K)
    return _fdp_gemm_jit(a, b, spec=spec, fmt=fmt, bm=p.bm, bn=p.bn, bk=p.bk,
                         interpret=interpret)


@partial(jax.jit,
         static_argnames=("spec", "fmt", "bm", "bn", "bk", "interpret"))
def _fdp_gemm_batched_jit(a, b, *, spec, fmt, bm, bn, bk, interpret):
    B, M, K = a.shape
    B2, K2, N = b.shape
    assert B == B2 and K == K2, (a.shape, b.shape)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, 0), (0, pk), (0, pn)))
    interp = (not _on_tpu()) if interpret is None else interpret
    out = fdp_gemm_pallas_batched(a, b, spec=spec, fmt=fmt, bm=bm, bn=bn,
                                  bk=bk, interpret=interp)
    return out[:, :M, :N]


def fdp_gemm_batched(a: jax.Array, b: jax.Array, *, spec: AccumulatorSpec,
                     fmt=FP32, plan: GemmPlan | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Batched GEMM through the native 4-D grid: (B,M,K)@(B,K,N) -> (B,M,N)
    f32 as one pallas_call (the batch dim needs no padding — its block is 1)."""
    _, M, K = a.shape
    _, _, N = b.shape
    p = resolve_plan(plan, M, N, K)
    return _fdp_gemm_batched_jit(a, b, spec=spec, fmt=fmt, bm=p.bm, bn=p.bn,
                                 bk=p.bk, interpret=interpret)


def matmul_batching(f2d, f3d):
    """Wrap a 2-D kernel and a flat-batched 3-D kernel into one
    jnp.matmul-shaped callable: 1-D operands are promoted (and the result
    squeezed back, down to a scalar for vector·vector), an N-D ``a`` times
    a 2-D ``b`` (activations times a weight) folds its leading dims into M
    so the weight is never broadcast, and otherwise leading batch dims
    broadcast numpy-style and flatten into the 3-D kernel's batch axis."""
    def call(a: jax.Array, b: jax.Array) -> jax.Array:
        squeeze_a = a.ndim == 1
        squeeze_b = b.ndim == 1
        if squeeze_a:
            a = a[None, :]
        if squeeze_b:
            b = b[:, None]
        if b.ndim == 2:
            out = f2d(a.reshape(-1, a.shape[-1]), b)
            out = out.reshape(a.shape[:-1] + out.shape[-1:])
        else:
            batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
            a = jnp.broadcast_to(a, batch + a.shape[-2:])
            b = jnp.broadcast_to(b, batch + b.shape[-2:])
            out = f3d(a.reshape((-1,) + a.shape[-2:]),
                      b.reshape((-1,) + b.shape[-2:]))
            out = out.reshape(batch + out.shape[-2:])
        if squeeze_a:
            out = out[..., 0, :]
        if squeeze_b:
            out = out[..., 0] if squeeze_a else out[..., :, 0]
        return out

    return call


def fdp_gemm_nd(a: jax.Array, b: jax.Array, *, spec: AccumulatorSpec,
                fmt=FP32, plan: GemmPlan | None = None,
                interpret: bool | None = None) -> jax.Array:
    """jnp.matmul-shaped entry point: 1-D promotion, numpy broadcasting of
    leading batch dims, then the 2-D kernel or the native batched grid."""
    f2d = lambda x, y: fdp_gemm(x, y, spec=spec, fmt=fmt, plan=plan,
                                interpret=interpret)
    f3d = lambda x, y: fdp_gemm_batched(x, y, spec=spec, fmt=fmt, plan=plan,
                                        interpret=interpret)
    return matmul_batching(f2d, f3d)(a, b)


# ---------------------------------------------------------------------------
# Sorted-segment (ragged / MoE) entry points
# ---------------------------------------------------------------------------
@partial(jax.jit,
         static_argnames=("spec", "fmt", "bm", "bn", "bk", "interpret"))
def _fdp_ragged_gemm_jit(x, w, group_sizes, *, spec, fmt, bm, bn, bk,
                         interpret):
    T, d = x.shape
    E, d2, f = w.shape
    assert d == d2, (x.shape, w.shape)
    pm, pn, pk = (-T) % bm, (-f) % bn, (-d) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    interp = (not _on_tpu()) if interpret is None else interpret
    out = fdp_ragged_gemm_pallas(x, w, group_sizes.astype(jnp.int32),
                                 spec=spec, fmt=fmt, bm=bm, bn=bn, bk=bk,
                                 interpret=interp)
    return out[:T, :f]


def fdp_ragged_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                    spec: AccumulatorSpec, fmt=FP32,
                    plan: GemmPlan | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Sorted-segment grouped GEMM: ``x (T, d)`` rows sorted by group,
    ``w (E, d, f)``, ``group_sizes (E,)`` -> ``(T, f)`` f32.

    Row ``t`` contracts against its group's weight matrix through the exact
    ⟨ovf,msb,lsb⟩ datapath in O(T·d·f) MACs: the Pallas grid walks one tile
    per (row-block, group) segment intersection — ``T/bm + E - 1`` tiles, not
    ``E`` passes over all ``T`` rows — with a scalar-prefetched index map
    picking each tile's expert weight block. Rows beyond ``sum(group_sizes)``
    produce zeros (matching ``jax.lax.ragged_dot``). Bit-identical to
    dispatching one GEMM per group: exact limb accumulation is
    order-invariant and rounds once at read-out.
    """
    T, d = x.shape
    f = w.shape[2]
    p = resolve_plan(plan, T, f, d)
    return _fdp_ragged_gemm_jit(x, w, group_sizes, spec=spec, fmt=fmt,
                                bm=p.bm, bn=p.bn, bk=p.bk, interpret=interpret)


@partial(jax.jit,
         static_argnames=("spec", "fmt", "bm", "bn", "bk", "interpret"))
def _fdp_ragged_dw_jit(x, g, group_sizes, *, spec, fmt, bm, bn, bk,
                       interpret):
    T, d = x.shape
    T2, f = g.shape
    assert T == T2, (x.shape, g.shape)
    pm, pn, pk = (-d) % bm, (-f) % bn, (-T) % bk
    if pk or pm:
        x = jnp.pad(x, ((0, pk), (0, pm)))
    if pk or pn:
        g = jnp.pad(g, ((0, pk), (0, pn)))
    interp = (not _on_tpu()) if interpret is None else interpret
    out = fdp_ragged_dw_pallas(x, g, group_sizes.astype(jnp.int32),
                               spec=spec, fmt=fmt, bm=bm, bn=bn, bk=bk,
                               interpret=interp)
    return out[:, :d, :f]


def fdp_ragged_dw(x: jax.Array, g: jax.Array, group_sizes: jax.Array, *,
                  num_groups: int, spec: AccumulatorSpec, fmt=FP32,
                  plan: GemmPlan | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Sorted-segment grouped weight gradient: ``dW[e] = X_eᵀ · G_e`` for
    ``x (T, d)`` / ``g (T, f)`` rows sorted by group -> ``(E, d, f)`` f32.

    The contraction dim is the ragged token dim: one tile per (token-block,
    group) intersection, routed to its group's output block — O(T·d·f) MACs.
    Zero-size groups (including leading/trailing ones) get exact-zero
    gradients. ``plan`` is fitted to the (d, f, T) problem, so ``plan.bk``
    is the token-block size (carry-safe by ``GemmPlan.fit``).
    """
    T, d = x.shape
    f = g.shape[1]
    if group_sizes.shape != (num_groups,):
        raise ValueError(f"group_sizes {group_sizes.shape} != ({num_groups},)")
    p = resolve_plan(plan, d, f, T)
    return _fdp_ragged_dw_jit(x, g, group_sizes, spec=spec, fmt=fmt,
                              bm=p.bm, bn=p.bn, bk=p.bk, interpret=interpret)
