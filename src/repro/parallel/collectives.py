"""Distributed numerics: the paper's fixed-point accumulation applied to
cross-replica collectives.

Floating-point all-reduce is order-dependent: different reduction topologies
(ring vs tree, different replica counts after elastic rescale) give different
bits. ``reproducible_psum`` quantizes onto the ⟨ovf,msb,lsb⟩ grid and reduces
in int32/int64-free integer space — integer addition is associative, so the
result is bitwise identical for ANY reduction order, topology or replica
count (the paper's reproducibility property, lifted to the collective layer).

With a coarse grid (few bits) + error feedback this doubles as gradient
compression: see ``CompressedGradReducer``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import accumulator as acc
from repro.core import qformat
from repro.core.accumulator import AccumulatorSpec
from repro.core.qformat import QuantConfig
from repro.obs.registry import default_registry as _obs_registry

_VALIDATE_OVERFLOW: Optional[str] = None     # None | "raise" | "warn"

# Saturation events land in the unified obs registry under the same family
# the GEMM envelope monitor uses, so "zero overflow events" is one number
# across accumulator wraps and collective spillover.
_OVERFLOW_EVENTS = _obs_registry().counter(
    "repro_overflow_events_total",
    "overflow/saturation events (accumulator wrap risk, non-finite "
    "outputs, quantized-collective spillover)", ("site", "source"))
_WARNED_SITES: set = set()


@contextlib.contextmanager
def validate_overflow(enabled: bool = True, *, mode: str = "raise"):
    """Validation mode: a quantized collective payload that would saturate
    its grid width is detected instead of silently clipping (clipping breaks
    the 'same bits as single device' contract).

    ``mode="raise"`` (default) raises ``OverflowError`` naming the offending
    site; ``mode="warn"`` is for monitoring-only production deployments —
    events still increment ``repro_overflow_events_total{source=collective}``
    and emit one ``RuntimeWarning`` per site, but serving keeps running.
    The mode is captured when a computation is *traced* (it is staged into
    the debug callback), like the check itself.
    """
    if mode not in ("raise", "warn"):
        raise ValueError(f"validate_overflow mode {mode!r} "
                         "(expected 'raise' or 'warn')")
    global _VALIDATE_OVERFLOW
    prev = _VALIDATE_OVERFLOW
    _VALIDATE_OVERFLOW = mode if enabled else None
    try:
        yield
    finally:
        _VALIDATE_OVERFLOW = prev


def _on_saturation(site: str, mode: str, saturated) -> None:
    if not saturated:
        return
    _OVERFLOW_EVENTS.inc(site=site, source="collective")
    msg = (f"[{site}] quantized collective payload saturates the grid "
           "width — the clipped reduction would not match single-device "
           "bits; widen the spec (ovf/msb) or rescale the payload")
    if mode == "warn":
        if site not in _WARNED_SITES:      # counter has the event count;
            _WARNED_SITES.add(site)        # warn once per site, not per step
            warnings.warn(msg, RuntimeWarning)
        return
    raise OverflowError(msg)


def _check_overflow(y: jax.Array, lim: float,
                    site: str = "collective") -> None:
    """Under ``validate_overflow()``: flag any |y| exceeding the signed
    range, attributed to ``site``. Works both eagerly and under trace (via
    debug.callback)."""
    mode = _VALIDATE_OVERFLOW
    if mode is None:
        return
    saturated = jnp.any(jnp.abs(y) > lim)
    jax.debug.callback(partial(_on_saturation, site, mode), saturated)


def _grid_quantize(x: jax.Array, lsb: int, width: int, stochastic_key=None,
                   site: str = "grid_quantize"):
    """Round-to-nearest onto 2^lsb grid, clip to signed ``width`` bits."""
    scale = 2.0 ** lsb
    y = x.astype(jnp.float32) / scale
    if stochastic_key is not None:
        y = jnp.floor(y + jax.random.uniform(stochastic_key, y.shape))
    else:
        y = jnp.round(y)
    lim = 2.0 ** (width - 1) - 1
    _check_overflow(y, lim, site)
    return jnp.clip(y, -lim, lim).astype(jnp.int32)


def _grid_dequantize(q: jax.Array, lsb: int, dtype=jnp.float32):
    return (q.astype(jnp.float32) * 2.0 ** lsb).astype(dtype)


def quantize_tree(tree, spec: AccumulatorSpec, site: str = "quantize_tree"):
    return jax.tree.map(
        lambda x: _grid_quantize(x, spec.lsb, spec.width, site=site), tree)


def dequantize_tree(tree, spec: AccumulatorSpec, like=None):
    if like is None:
        return jax.tree.map(lambda q: _grid_dequantize(q, spec.lsb), tree)
    return jax.tree.map(
        lambda q, l: _grid_dequantize(q, spec.lsb, l.dtype), tree, like)


def reproducible_psum(x: jax.Array, axis_name: str, spec: AccumulatorSpec,
                      mean: bool = False) -> jax.Array:
    """Order-invariant psum: quantize -> integer psum -> dequantize.

    Must be called inside shard_map/pmap with ``axis_name`` bound. The int32
    payload also halves wire bytes vs f32 when spec.width <= 16 (XLA packs
    int32; the width bound documents the *information* content — a production
    deployment would pack to int16/int8 wire format, which this emulates).
    """
    q = _grid_quantize(x, spec.lsb, spec.width,
                       site="reproducible_psum@coll")
    s = jax.lax.psum(q, axis_name)
    out = _grid_dequantize(s, spec.lsb, x.dtype)
    if mean:
        out = out / jax.lax.axis_size(axis_name)
    return out


def fdp_psum(limbs: jax.Array, axis_name, spec: AccumulatorSpec) -> jax.Array:
    """All-reduce of FDP accumulator registers in exact integer limb space.

    ``limbs`` is a carry-normalized partial-K state (trailing dim =
    ``spec.num_limbs``), e.g. from ``repro.core.fdp.fdp_gemm_limbs`` on a
    local K-shard, or any per-device exact partial accumulation. Integer limb
    addition is exact, associative and commutative, so the psum followed by
    one ``carry_normalize`` is bit-identical to accumulating everything on a
    single device — for ANY reduction order, ring/tree topology, or mesh
    factorization. No dequantized grid is involved: this reduces the
    *register itself*, so a K-sharded FDP GEMM lands on exactly the bits
    ``fdp_gemm`` would produce unsharded.

    Headroom: normalized digits 0..L-2 are in [0, 2^16) and the signed top
    limb carries the rest, so up to SAFE_CHUNK (2^13) device contributions
    sum without int32 digit overflow; top-limb int32 wrap is congruent to the
    register's own 2^ovf+msb wrap, preserving wrap-mode semantics. Call inside
    shard_map/pmap with ``axis_name`` bound.
    """
    assert limbs.shape[-1] == spec.num_limbs, (
        f"limb register has {limbs.shape[-1]} limbs, spec wants "
        f"{spec.num_limbs}")
    s = jax.lax.psum(limbs, axis_name)
    return acc.carry_normalize(spec, s)


def quantized_psum(x: jax.Array, axis_name: str, cfg: QuantConfig, *,
                   mean: bool = False, residual: Optional[jax.Array] = None,
                   site: str = qformat.GRAD_PSUM_SITE.key):
    """Block-scaled low-bit all-reduce — the bytes-*moved* counterpart to the
    optimizer's bytes-resident site (``CollectiveSite("grad_psum")``).

    Per-block shared exponents are agreed across devices first (pmax of the
    local block amax — max is exact and associative, so every device lands on
    the same exponent regardless of topology), then each device sends a
    ``cfg.bits``-wide integer payload on that 2^lsb grid and the reduction
    runs in exact integer space. Given the shared exponents, the result is
    order-invariant like ``reproducible_psum``, but the grid adapts per block
    instead of being fixed by an AccumulatorSpec — so 8-bit payloads survive
    the ~2^40 dynamic range a gradient tree spans. Wire cost is modeled by
    ``qformat.quant_bytes`` (bits/8 per element + one exponent byte per
    block) vs 4 bytes/element for the fp32 path.

    ``residual`` enables error feedback: what rounding/clipping dropped this
    step is returned and should be added back next step (1-bit-Adam-style).
    The grid is sized from ``x`` alone, NOT ``x + residual`` — accumulated
    residual that spills past the grid clips (and is re-carried), which is
    exactly what ``validate_overflow()`` + ``_check_overflow`` make loud.
    Returns ``out`` without residual, ``(out, new_residual)`` with.

    An fp32-mode cfg is the identity wire format (plain float psum).
    """
    if cfg.mode == "fp32":
        out = jax.lax.psum(x.astype(jnp.float32), axis_name)
        if mean:
            out = out / jax.lax.axis_size(axis_name)
        out = out.astype(x.dtype)
        if residual is None:
            return out
        return out, jnp.zeros(x.shape, jnp.float32)

    blocks = qformat._to_blocks(x, cfg.block)
    amax = jax.lax.pmax(jnp.max(jnp.abs(blocks), axis=1), axis_name)
    _, scale = qformat.block_scale(amax, cfg.bits)
    payload = blocks
    if residual is not None:
        payload = payload + qformat._to_blocks(residual, cfg.block)
    y = jnp.round(payload / scale[:, None])
    lim = 2.0 ** (cfg.bits - 1) - 1
    _check_overflow(y, lim, site)
    q = jnp.clip(y, -lim, lim).astype(jnp.int32)
    s = jax.lax.psum(q, axis_name)

    def unblock(b):
        return b.reshape(-1)[: x.size].reshape(x.shape)

    out = unblock(s.astype(jnp.float32) * scale[:, None])
    if mean:
        out = out / jax.lax.axis_size(axis_name)
    out = out.astype(x.dtype)
    if residual is None:
        return out
    sent = unblock(q.astype(jnp.float32) * scale[:, None])
    new_r = (x.astype(jnp.float32) + residual) - sent
    return out, new_r


@dataclasses.dataclass
class QuantizedGradReducer:
    """Error-feedback gradient averaging over ``quantized_psum`` — the
    block-scaled sibling of ``CompressedGradReducer`` (whose single global
    ⟨lsb,width⟩ grid can't span a whole gradient tree at low bits)."""

    cfg: QuantConfig
    axis_name: str

    def init(self, params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def reduce(self, grads, residual):
        """Returns (mean_grads, new_residual)."""
        def one(g, r):
            out, new_r = quantized_psum(g, self.axis_name, self.cfg,
                                        mean=True, residual=r)
            return out.astype(g.dtype), new_r

        flat_g, td = jax.tree.flatten(grads)
        flat_r = jax.tree.leaves(residual)
        out = [one(g, r) for g, r in zip(flat_g, flat_r)]
        return (jax.tree.unflatten(td, [o[0] for o in out]),
                jax.tree.unflatten(td, [o[1] for o in out]))


@dataclasses.dataclass
class CompressedGradReducer:
    """Error-feedback gradient compression on the fixed-point grid
    (1-bit-Adam-style residual carrying, but with the paper's ⟨lsb,width⟩
    knob instead of sign-only)."""

    spec: AccumulatorSpec
    axis_name: str

    def init(self, params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def reduce(self, grads, residual):
        """Returns (reduced_grads, new_residual)."""
        def one(g, r):
            g32 = g.astype(jnp.float32) + r
            q = _grid_quantize(g32, self.spec.lsb, self.spec.width,
                               site=qformat.GRAD_PSUM_SITE.key)
            sent = _grid_dequantize(q, self.spec.lsb)
            new_r = g32 - sent
            red = jax.lax.psum(q, self.axis_name)
            return (_grid_dequantize(red, self.spec.lsb) / n).astype(g.dtype), new_r

        n = jax.lax.axis_size(self.axis_name)
        flat_g, td = jax.tree.flatten(grads)
        flat_r = jax.tree.leaves(residual)
        out = [one(g, r) for g, r in zip(flat_g, flat_r)]
        return (jax.tree.unflatten(td, [o[0] for o in out]),
                jax.tree.unflatten(td, [o[1] for o in out]))
