"""Pallas TPU kernel: GEMM with a ⟨ovf,msb,lsb⟩ fixed-point FDP accumulator.

TPU adaptation of the paper's FPGA systolic GEMM (FCCM'22): the MXU cannot be
re-wired, so the exact accumulator lives in **VMEM scratch as int32 limbs** and
the per-product decode/align/accumulate micro-ops run on the VPU. Tiling is
classic Pallas matmul: grid (M/bm, N/bn, K/bk) with K innermost; the limb
register persists in scratch across the K grid dimension and is rounded to
f32 once, on the last K step — "never round between accumulations".

Layout: the register is limb-leading, ``(L, bm, bn)`` int32, so each limb is
a plane of whole (8, 128) vector tiles (a limb-minor ``(bm, bn, L)`` register
pads L to 128 lanes and its per-limb slices do not lower). Each grid step
writes its A tile K-major into a ``(bk, bm)`` scratch, then a ``fori_loop``
walks K in sub-chunks of ``kc`` rows: both operands' rows are decoded, their
``(kc, bm, bn)`` product contributions are summed limb by limb into the
planes (``accumulator.product_planes``), and the register is
carry-normalized ONCE per K block. A batched variant runs
``(B, M, K) @ (B, K, N)`` as a single ``pallas_call`` over a 4-D grid.

Int32 carry discipline: each product contributes < 2^17 per limb, so a K block
of ``bk <= SAFE_CHUNK`` (= 2^13) products is safe between carry
normalizations; the bound is derived in ``repro.core.accumulator`` and
enforced here via ``MAX_BK`` (callers: ops.py).

Block shapes follow the TPU tiling rule that ``GemmPlan.fit`` enforces (each
block dim a multiple of 128, or the whole padded dim, which is a multiple of
8). The kernels are validated bit-exactly against the pure-jnp oracle
(``repro.core.fdp``) in interpret mode, which executes this same body on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import accumulator as acc
from repro.core.accumulator import SAFE_CHUNK, AccumulatorSpec

# Single source of truth for the carry-headroom contract: a K block may
# accumulate at most SAFE_CHUNK products between carry normalizations.
MAX_BK = SAFE_CHUNK

# Bytes of one (kc, bm, bn) int32 slab per K sub-chunk. The planes reduction
# keeps about a dozen such temporaries live. Interpret mode runs through
# XLA:CPU, where cache-sized slabs and few loop trips win; on the TPU the
# temporaries share VMEM with the operand tiles and the register.
_SLAB_BYTES_INTERPRET = 16 << 20
_SLAB_BYTES_TPU = 64 << 10


def _k_subchunk(bm: int, bn: int, bk: int, interpret: bool) -> int:
    """K rows per loop step: the largest power of two that divides ``bk``
    and keeps one (kc, bm, bn) int32 slab within the backend's budget."""
    budget = _SLAB_BYTES_INTERPRET if interpret else _SLAB_BYTES_TPU
    rows = max(1, budget // (bm * bn * 4))
    kc = 1
    while kc * 2 <= min(rows, bk) and bk % (kc * 2) == 0:
        kc *= 2
    return kc


def _carrier(x: jax.Array) -> jax.Array:
    """Float operands travel as f32 (exact for every format the decoder
    reads); posit bit patterns stay int32. One 32-bit tile layout for all."""
    return x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x


def _scratch(bm: int, bn: int, bk: int, spec: AccumulatorSpec, dtype):
    """Limb register (L, bm, bn) int32 + the K-major A tile (bk, bm)."""
    return [pltpu.VMEM((spec.num_limbs, bm, bn), jnp.int32),
            pltpu.VMEM((bk, bm), dtype)]


def _accumulate(acc_ref, at_ref, load_b, *, spec: AccumulatorSpec, fmt,
                kc: int):
    """acc (L, bm, bn) += at (bk, bm)ᵀ · b (bk, bn), exactly.

    ``load_b(k0, kc)`` returns B's rows [k0, k0+kc) as a (kc, bn) array. The
    sum of a K block's bk <= SAFE_CHUNK products fits the int32 headroom, so
    the register is carry-normalized once, after the loop."""
    L = spec.num_limbs
    bk = at_ref.shape[0]

    def step(c, planes):
        k0 = pl.multiple_of(c * kc, kc)
        da = fmt.decode(at_ref[pl.ds(k0, kc), :])                 # (kc, bm)
        db = fmt.decode(load_b(k0, kc))                           # (kc, bn)
        da = jax.tree.map(lambda x: x[:, :, None], da)            # (kc, bm, 1)
        db = jax.tree.map(lambda x: x[:, None, :], db)            # (kc, 1, bn)
        sums = acc.product_planes(spec, da, db, reduce_leading=True)
        return tuple(p + s for p, s in zip(planes, sums))

    planes = jax.lax.fori_loop(0, bk // kc, step,
                               tuple(acc_ref[l] for l in range(L)))
    for l, p in enumerate(acc.normalize_planes(planes)):
        acc_ref[l] = p


def _read_out(spec: AccumulatorSpec, acc_ref) -> jax.Array:
    """The register rounded once to f32, (bm, bn)."""
    return acc.planes_to_float(
        spec, [acc_ref[l] for l in range(spec.num_limbs)])


def fdp_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, at_ref, *,
                    spec: AccumulatorSpec, fmt, k_grid: int, kc: int,
                    batched: bool):
    """Kernel body (2-D and batched grids).

    2-D:     a (bm, bk), b (bk, bn), o (bm, bn) f32, grid (Mg, Ng, Kg).
    batched: a (1, bm, bk), b (1, bk, bn), o (1, bm, bn), grid (B, Mg, Ng, Kg).
    acc scratch: (L, bm, bn) int32, persists across the (innermost) K axis.
    """
    kidx = pl.program_id(3 if batched else 2)

    @pl.when(kidx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at_ref[...] = (a_ref[0] if batched else a_ref[...]).T
    if batched:
        load_b = lambda k0, n: b_ref[0, pl.ds(k0, n), :]
    else:
        load_b = lambda k0, n: b_ref[pl.ds(k0, n), :]
    _accumulate(acc_ref, at_ref, load_b, spec=spec, fmt=fmt, kc=kc)

    @pl.when(kidx == k_grid - 1)
    def _emit():
        out = _read_out(spec, acc_ref)
        o_ref[...] = out[None] if batched else out


def fdp_gemm_pallas(a: jax.Array, b: jax.Array, *, spec: AccumulatorSpec,
                    fmt, bm: int = 128, bn: int = 128, bk: int = 512,
                    interpret: bool = True) -> jax.Array:
    """Raw pallas_call wrapper; shapes must be multiples of the block sizes
    (ops.py pads). Inputs: float arrays, or int32 posit patterns."""
    a, b = _carrier(a), _carrier(b)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk <= MAX_BK, (
        f"bk={bk} exceeds SAFE_CHUNK={SAFE_CHUNK} (= 2^13): int32 limbs take "
        f"< 2^17 per product, so at most SAFE_CHUNK products may accumulate "
        f"between carry normalizations")
    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(
        fdp_gemm_kernel, spec=spec, fmt=fmt, k_grid=grid[2],
        kc=_k_subchunk(bm, bn, bk, interpret), batched=False)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=_scratch(bm, bn, bk, spec, a.dtype),
        interpret=interpret,
        name="fdp_gemm",
    )(a, b)


# ---------------------------------------------------------------------------
# Sorted-segment (ragged / MoE) kernels
#
# Tokens arrive sorted by expert (models/moe.py sort-based dispatch), so a
# grouped GEMM need not run every expert over every row: the grid walks one
# tile per (row-block, expert) *segment intersection* — at most
# ``T/bm + E - 1`` tiles by telescoping, since the segment id is
# non-decreasing — and a scalar-prefetched metadata table steers each tile's
# block index maps to its expert's weight (or output) block. Rows outside a
# tile's segment are masked to the zero pattern before decode; zero products
# contribute nothing to the limb register, so accumulating tiles of one
# output block in sequence is exact and order-invariant (bit-identical to
# one dispatched GEMM per expert).
# ---------------------------------------------------------------------------
_META_ROWS = 6            # (block, group, row_lo, row_hi, first, last)


def ragged_num_tiles(n_rows: int, block: int, num_groups: int) -> int:
    """Static tile count of the sorted-segment grids: one tile per
    (row-block, group) intersection, ≤ n_rows/block + num_groups - 1."""
    assert n_rows % block == 0, (n_rows, block)
    return n_rows // block + num_groups - 1


def _ragged_meta(group_sizes: jax.Array, n_rows: int, block: int, *,
                 cover_all_groups: bool) -> jax.Array:
    """Build the (6, NT) int32 scalar-prefetch table for a sorted-segment
    grid over ``n_rows`` (padded, block-multiple) rows in ``num_groups``
    groups. Rows: tile's row-block index, its group index, the global row
    bounds [lo, hi) it owns, and first/last markers for its accumulation
    window (per row-block for the forward, per group when
    ``cover_all_groups`` — the wgrad layout, where every group's output
    block must be visited even for zero-size groups).

    Shapes are static (NT from the telescoping bound); values are data.
    Tiles beyond the used count collapse to empty [0, 0) windows on the last
    block/group with first=last=0, so they accumulate nothing and never
    emit."""
    E = int(group_sizes.shape[0])
    Bg = n_rows // block
    NT = ragged_num_tiles(n_rows, block, E)
    gs = group_sizes.astype(jnp.int32)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(gs, dtype=jnp.int32)])      # (E+1,)
    row0 = jnp.arange(Bg, dtype=jnp.int32) * block
    seg = lambda r: jnp.clip(
        jnp.searchsorted(bounds[1:], r, side="right"), 0, E - 1
    ).astype(jnp.int32)
    e_first = seg(row0)
    e_last = seg(row0 + block - 1)
    if cover_all_groups:
        # wgrad: groups skipped between consecutive row-blocks (zero-size
        # groups) attach to the later block, and the end blocks stretch to
        # group 0 / E-1, so every output block gets (at least) one tile.
        e_first = jnp.concatenate([jnp.zeros((1,), jnp.int32), e_last[:-1]])
        e_last = e_last.at[-1].set(E - 1)

    tiles = e_last - e_first + 1                                     # (Bg,)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(tiles, dtype=jnp.int32)])      # (Bg+1,)
    n_used = off[Bg]
    t_ids = jnp.arange(NT, dtype=jnp.int32)
    blk = jnp.clip(jnp.searchsorted(off[1:], t_ids, side="right"),
                   0, Bg - 1).astype(jnp.int32)
    grp = e_first[blk] + (t_ids - off[blk])
    valid = t_ids < n_used
    # spare tiles park on the last block/group (output index maps stay
    # non-decreasing) with an empty row window
    grp = jnp.where(valid, grp, E - 1)
    lo = jnp.where(valid, jnp.maximum(bounds[grp], blk * block), 0)
    hi = jnp.where(valid,
                   jnp.minimum(bounds[grp + 1], (blk + 1) * block), 0)
    if cover_all_groups:
        prev_grp = jnp.concatenate([jnp.full((1,), -1, jnp.int32), grp[:-1]])
        first = valid & (grp != prev_grp)
        last = valid & ((t_ids == n_used - 1) | (t_ids + 1 >= NT)
                        | (jnp.concatenate(
                            [grp[1:], jnp.full((1,), -1, jnp.int32)]) != grp))
    else:
        first = valid & (t_ids == off[blk])
        last = valid & (t_ids == off[blk] + tiles[blk] - 1)
    return jnp.stack([blk, grp, lo, hi,
                      first.astype(jnp.int32), last.astype(jnp.int32)])


def _segment_mask(x, block_idx, block: int, lo, hi, axis: int):
    """Zero the token rows (``axis`` 0) or token columns (``axis`` 1) of an
    operand tile outside its segment's global [lo, hi) window. Exact for
    every format: 0.0 is the zero float carrier and 0 the zero posit
    pattern, and zero products add nothing to the limb register."""
    shape = (block, 1) if axis == 0 else (1, block)
    tok = block_idx * block + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return jnp.where((tok >= lo) & (tok < hi), x, jnp.zeros((), x.dtype))


def fdp_ragged_kernel(meta_ref, x_ref, w_ref, o_ref, acc_ref, at_ref, *,
                      spec: AccumulatorSpec, fmt, bm: int, k_grid: int,
                      kc: int):
    """Sorted-segment forward body. Grid (Ng, NT, Kg), K innermost:
    x (bm, bk) at (block[t], k), w (1, bk, bn) at (group[t], k, j),
    o (bm, bn) at (block[t], j). The limb scratch spans all tiles of one
    row-block (their row windows are disjoint): zeroed on the block's first
    tile, emitted on its last."""
    t = pl.program_id(1)
    kidx = pl.program_id(2)
    tm = meta_ref[0, t]
    lo = meta_ref[2, t]
    hi = meta_ref[3, t]
    first = meta_ref[4, t]
    last = meta_ref[5, t]

    @pl.when((first == 1) & (kidx == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at_ref[...] = _segment_mask(x_ref[...], tm, bm, lo, hi, axis=0).T
    _accumulate(acc_ref, at_ref, lambda k0, n: w_ref[0, pl.ds(k0, n), :],
                spec=spec, fmt=fmt, kc=kc)

    @pl.when((last == 1) & (kidx == k_grid - 1))
    def _emit():
        o_ref[...] = _read_out(spec, acc_ref)


def fdp_ragged_gemm_pallas(x: jax.Array, w: jax.Array,
                           group_sizes: jax.Array, *, spec: AccumulatorSpec,
                           fmt, bm: int = 128, bn: int = 128, bk: int = 512,
                           interpret: bool = True) -> jax.Array:
    """Raw sorted-segment grouped GEMM: x (T, d) @ w[group(t)] -> (T, f).
    T/d/f must be block multiples (ops.py pads); rows beyond
    sum(group_sizes) yield zeros."""
    x, w = _carrier(x), _carrier(w)
    T, d = x.shape
    E, d2, f = w.shape
    assert d == d2, (x.shape, w.shape)
    assert T % bm == 0 and f % bn == 0 and d % bk == 0, (T, d, f, bm, bn, bk)
    assert bk <= MAX_BK, (
        f"bk={bk} exceeds SAFE_CHUNK={SAFE_CHUNK} carry headroom")
    NT = ragged_num_tiles(T, bm, E)
    k_grid = d // bk
    meta = _ragged_meta(group_sizes, T, bm, cover_all_groups=False)
    kernel = functools.partial(
        fdp_ragged_kernel, spec=spec, fmt=fmt, bm=bm, k_grid=k_grid,
        kc=_k_subchunk(bm, bn, bk, interpret))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f // bn, NT, k_grid),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, t, k, meta: (meta[0, t], k)),
            pl.BlockSpec((1, bk, bn),
                         lambda j, t, k, meta: (meta[1, t], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda j, t, k, meta: (meta[0, t], j)),
        scratch_shapes=_scratch(bm, bn, bk, spec, x.dtype),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, f), jnp.float32),
        interpret=interpret,
        name="fdp_ragged_gemm",
    )(meta, x, w)


def fdp_ragged_dw_kernel(meta_ref, xt_ref, g_ref, o_ref, acc_ref, at_ref, *,
                         spec: AccumulatorSpec, fmt, bkt: int, kc: int):
    """Sorted-segment wgrad body. Grid (Mg, Ng, NT), tiles innermost:
    xᵀ (bm, bkt) at (i, block[t]), g (bkt, bn) at (block[t], j),
    o (1, bm, bn) at (group[t], i, j). The contraction dim is the ragged
    token dim; the limb scratch spans all tiles of one *group* (first/last
    markers are per group), so zero-size groups emit exact zeros from their
    single empty tile."""
    t = pl.program_id(2)
    tb = meta_ref[0, t]
    lo = meta_ref[2, t]
    hi = meta_ref[3, t]
    first = meta_ref[4, t]
    last = meta_ref[5, t]

    @pl.when(first == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at_ref[...] = _segment_mask(xt_ref[...], tb, bkt, lo, hi, axis=1).T
    _accumulate(acc_ref, at_ref, lambda k0, n: g_ref[pl.ds(k0, n), :],
                spec=spec, fmt=fmt, kc=kc)

    @pl.when(last == 1)
    def _emit():
        o_ref[...] = _read_out(spec, acc_ref)[None]


def fdp_ragged_dw_pallas(x: jax.Array, g: jax.Array, group_sizes: jax.Array,
                         *, spec: AccumulatorSpec, fmt, bm: int = 128,
                         bn: int = 128, bk: int = 512,
                         interpret: bool = True) -> jax.Array:
    """Raw sorted-segment grouped weight gradient:
    dW[e] = x[rows of e]ᵀ @ g[rows of e] -> (E, d, f). ``bk`` blocks the
    ragged token dim (T must be a bk multiple; ops.py pads)."""
    x, g = _carrier(x), _carrier(g)
    T, d = x.shape
    T2, f = g.shape
    assert T == T2, (x.shape, g.shape)
    E = int(group_sizes.shape[0])
    assert T % bk == 0 and d % bm == 0 and f % bn == 0, (T, d, f, bm, bn, bk)
    assert bk <= MAX_BK, (
        f"bk={bk} exceeds SAFE_CHUNK={SAFE_CHUNK} carry headroom")
    NT = ragged_num_tiles(T, bk, E)
    meta = _ragged_meta(group_sizes, T, bk, cover_all_groups=True)
    kernel = functools.partial(
        fdp_ragged_dw_kernel, spec=spec, fmt=fmt, bkt=bk,
        kc=_k_subchunk(bm, bn, bk, interpret))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bm, f // bn, NT),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t, meta: (i, meta[0, t])),
            pl.BlockSpec((bk, bn), lambda i, j, t, meta: (meta[0, t], j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda i, j, t, meta: (meta[1, t], i, j)),
        scratch_shapes=_scratch(bm, bn, bk, spec, x.dtype),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, d, f), jnp.float32),
        interpret=interpret,
        name="fdp_ragged_dw",
    )(meta, x.T, g)


def fdp_gemm_pallas_batched(a: jax.Array, b: jax.Array, *,
                            spec: AccumulatorSpec, fmt, bm: int = 128,
                            bn: int = 128, bk: int = 512,
                            interpret: bool = True) -> jax.Array:
    """Native batched grid: (B, M, K) @ (B, K, N) -> (B, M, N) as ONE
    pallas_call over grid (B, M/bm, N/bn, K/bk) — no vmap-of-kernel. The limb
    scratch persists across the innermost K axis only, so each (batch, i, j)
    tile accumulates independently."""
    a, b = _carrier(a), _carrier(b)
    B, M, K = a.shape
    B2, K2, N = b.shape
    assert B == B2 and K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk <= MAX_BK, (
        f"bk={bk} exceeds SAFE_CHUNK={SAFE_CHUNK} carry headroom")
    grid = (B, M // bm, N // bn, K // bk)
    kernel = functools.partial(
        fdp_gemm_kernel, spec=spec, fmt=fmt, k_grid=grid[3],
        kc=_k_subchunk(bm, bn, bk, interpret), batched=True)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), jnp.float32),
        scratch_shapes=_scratch(bm, bn, bk, spec, a.dtype),
        interpret=interpret,
        name="fdp_gemm_batched",
    )(a, b)
