"""Fused Dot Product (FDP) — the paper's operator, as composable JAX functions.

``fdp_dot``/``fdp_gemm`` compute dot products / GEMMs whose products are
accumulated in a ⟨ovf,msb,lsb⟩ fixed-point register with NO intermediate
rounding (one quantization at product entry, one rounding at read-out).

These are the *simulation-mode* (pure jnp, bit-exact) implementations; the
Pallas TPU kernel in ``repro.kernels.fdp_gemm`` implements identical semantics
and is validated against this module, which in turn is validated against a
python-``Fraction`` oracle in the tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import accumulator as acc
from .accumulator import SAFE_CHUNK, AccumulatorSpec
from .formats import FP32, FloatFormat, PositFormat

Array = jax.Array


def _decode(fmt, x: Array):
    """Decode an array to (sign, mant, exp) per the format. Float formats take
    float arrays; posit formats take int32 bit-pattern arrays."""
    return fmt.decode(x)


@partial(jax.jit, static_argnums=(2, 3))
def fdp_dot(a: Array, b: Array, spec: AccumulatorSpec,
            fmt: FloatFormat | PositFormat = FP32) -> Array:
    """Exact-accumulation dot product of 1-D vectors, -> f32 (RNE once)."""
    limbs = fdp_dot_limbs(a, b, spec, fmt)
    return acc.to_float(spec, limbs)


@partial(jax.jit, static_argnums=(2, 3))
def fdp_dot64(a: Array, b: Array, spec: AccumulatorSpec,
              fmt: FloatFormat | PositFormat = FP32) -> Array:
    """Exact-accumulation dot product with 53-bit (f64) read-out.
    Requires jax x64 mode (used by the SSH benchmark's correct-bits axis)."""
    limbs = fdp_dot_limbs(a, b, spec, fmt)
    return acc.to_float64(spec, limbs)


def fdp_dot_limbs(a: Array, b: Array, spec: AccumulatorSpec,
                  fmt: FloatFormat | PositFormat = FP32) -> Array:
    """Accumulator register (carry-normalized limbs) of dot(a, b)."""
    assert a.shape == b.shape and a.ndim == 1
    da, db = _decode(fmt, a), _decode(fmt, b)
    contrib = acc.product_limbs(spec, da, db)        # (K, L)
    return _reduce_contribs(spec, contrib, axis=0)


def _reduce_contribs(spec: AccumulatorSpec, contrib: Array, axis: int) -> Array:
    """Sum limb contributions along ``axis`` exactly, normalizing carries
    every SAFE_CHUNK partial sums (int32 overflow discipline)."""
    n = contrib.shape[axis]
    if n <= SAFE_CHUNK:
        return acc.carry_normalize(spec, jnp.sum(contrib, axis=axis))
    # chunked reduction: pad to a multiple of SAFE_CHUNK, scan over chunks
    pad = (-n) % SAFE_CHUNK
    contrib = jnp.moveaxis(contrib, axis, 0)
    if pad:
        contrib = jnp.concatenate(
            [contrib, jnp.zeros((pad, *contrib.shape[1:]), contrib.dtype)], 0)
    chunks = contrib.reshape(-1, SAFE_CHUNK, *contrib.shape[1:])

    def step(carry, chunk):
        # carry is normalized (digit magnitudes < 2^16) -> safe to add a chunk
        s = carry + jnp.sum(chunk, axis=0)
        return acc.carry_normalize(spec, s), None

    init = jnp.zeros(chunks.shape[2:], jnp.int32)
    out, _ = jax.lax.scan(step, init, chunks)
    return out


@partial(jax.jit, static_argnums=(2, 3))
def fdp_gemm_limbs(a: Array, b: Array, spec: AccumulatorSpec,
                   fmt: FloatFormat | PositFormat = FP32) -> Array:
    """The accumulator register of a GEMM: (M,K) @ (K,N) -> (M,N,L) int32
    carry-normalized limbs, with NO read-out rounding applied.

    This is the *partial-K reduction state*: because limb addition is exact
    integer arithmetic, the register of a full-K GEMM equals the limb-wise sum
    of the registers of any K-partition — ``carry_normalize(spec, Σ_k
    fdp_gemm_limbs(a_k, b_k))`` is bit-identical to
    ``fdp_gemm_limbs(a, b)`` for every split. That is what lets a K-sharded
    contraction reduce across devices through an integer ``psum`` of limbs
    (``repro.parallel.collectives.fdp_psum``) and land on exactly the bits a
    single device would produce. Up to SAFE_CHUNK normalized partial states
    may be summed before the next ``carry_normalize`` (digit magnitudes are
    < 2^16 after normalization; int32 headroom covers 2^13 of them — far more
    devices than any mesh).
    """
    return jnp.stack(_gemm_planes(a, b, spec, fmt), axis=-1)


# Bytes of one (kc, M, N) int32 slab of product contributions: XLA keeps a
# few of them live per K chunk, so the chunk shrinks as M*N grows (a decode
# step's lm_head is (slots x 151936)) and stays 512 deep for small GEMMs.
_SLAB_BYTES = 32 << 20


def _gemm_planes(a: Array, b: Array, spec: AccumulatorSpec, fmt) -> list:
    """The carry-normalized register of a @ b as L (M, N) int32 planes."""
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0]
    M, K = a.shape
    _, N = b.shape
    da, db = _decode(fmt, a), _decode(fmt, b)

    # chunk K to bound both memory and int32 carry headroom
    kc = max(1, min(K, 512, _SLAB_BYTES // (4 * M * N)))
    pad = (-K) % kc
    def padk(d, fill=0):
        return jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)], 0) if pad else x, d)

    da_k = jax.tree.map(lambda x: x.T if x.ndim == 2 else x, da)   # (K, M)
    db_k = db                                                      # (K, N)
    da_k, db_k = padk(da_k), padk(db_k)
    nchunks = (K + pad) // kc
    da_c = jax.tree.map(lambda x: x.reshape(nchunks, kc, *x.shape[1:]), da_k)
    db_c = jax.tree.map(lambda x: x.reshape(nchunks, kc, *x.shape[1:]), db_k)

    def step(planes, chunk):
        dac, dbc = chunk
        # broadcast to (kc, M, N): sign/mant/exp combine elementwise
        sums = acc.product_planes(
            spec, jax.tree.map(lambda x: x[:, :, None], dac),
            jax.tree.map(lambda x: x[:, None, :], dbc), reduce_leading=True)
        return tuple(acc.normalize_planes(
            [p + s for p, s in zip(planes, sums)])), None

    init = (jnp.zeros((M, N), jnp.int32),) * spec.num_limbs
    planes, _ = jax.lax.scan(step, init, (da_c, db_c))
    return list(planes)


@partial(jax.jit, static_argnums=(2, 3))
def fdp_gemm(a: Array, b: Array, spec: AccumulatorSpec,
             fmt: FloatFormat | PositFormat = FP32) -> Array:
    """GEMM with FDP accumulation: (M,K) @ (K,N) -> (M,N) f32.

    Memory note: materializes per-K limb contributions in K-chunks of size
    min(K, SAFE_CHUNK); intended for numerics experiments (simulation mode),
    not as the production fast path. ``fdp_gemm_limbs`` is the same
    computation stopped before the single read-out rounding — the partial-K
    state a sharded reduction merges across devices.
    """
    return acc.planes_to_float(spec, _gemm_planes(a, b, spec, fmt))


def quantize_products(a: Array, b: Array, spec: AccumulatorSpec,
                      fmt=FP32) -> Array:
    """The per-product entry quantization alone (diagnostic): q(a*b) * 2^lsb."""
    da, db = _decode(fmt, a), _decode(fmt, b)
    limbs = acc.product_limbs(spec, da, db)
    limbs = acc.carry_normalize(spec, limbs)
    return acc.to_float(spec, limbs)


def fdp_dot_posit(a: Array, b: Array, spec: AccumulatorSpec | None = None,
                  fmt=None, out_fmt=None) -> Array:
    """Posit-in, posit-out fused dot product through the quire: posit bit
    patterns are decoded, products accumulate exactly in the ⟨ovf,msb,lsb⟩
    register (default: the format's standard quire), and the result is
    rounded ONCE to the output posit format.

    Read-out goes through f32 (exact for posit16's <=13 fraction bits; for
    posit32's deepest regimes this is a documented double rounding)."""
    from .formats import POSIT16_1
    fmt = fmt or POSIT16_1
    out_fmt = out_fmt or fmt
    spec = spec or AccumulatorSpec.quire(fmt, max_terms=a.shape[0])
    limbs = fdp_dot_limbs(a, b, spec, fmt)
    return out_fmt.from_float(acc.to_float(spec, limbs))


# ---------------------------------------------------------------------------
# Baseline accumulators the paper compares against (ordered FMA chains)
# ---------------------------------------------------------------------------
def fma_dot(a: Array, b: Array, dtype=jnp.float32) -> Array:
    """Sequential FMA accumulation in ``dtype`` (rounds after every add) —
    the conventional-FPU baseline of Fig. 2."""
    a = a.astype(dtype)
    b = b.astype(dtype)

    def step(s, ab):
        x, y = ab
        return (s + x * y).astype(dtype), None

    s, _ = jax.lax.scan(step, jnp.zeros((), dtype), (a, b))
    return s


def two_sum(x, y):
    s = x + y
    bb = s - x
    err = (x - (s - bb)) + (y - bb)
    return s, err


def two_prod(x, y):
    """Exact product via Dekker splitting: x*y = p + e (p = rounded product)."""
    p = x * y
    return p, _dekker_err(x, y, p)


def _dekker_err(x, y, p):
    # split constant 2^ceil(prec/2)+1: f32 -> 4097, f64 -> 2^27+1
    c = jnp.asarray(134217729.0 if x.dtype == jnp.float64 else 4097.0, x.dtype)
    xh = (x * c) - (x * c - x); xl = x - xh
    yh = (y * c) - (y * c - y); yl = y - yh
    return ((xh * yh - p) + xh * yl + xl * yh) + xl * yl


def dd_dot(a: Array, b: Array, dtype=jnp.float64) -> Array:
    """Double-double (compensated) dot product in ``dtype`` — the emulated
    quad-precision FMA baseline of Fig. 2 (~2x mantissa bits)."""
    a = a.astype(dtype)
    b = b.astype(dtype)

    def step(carry, xy):
        s, c = carry
        x, y = xy
        p, pe = two_prod(x, y)
        s, se = two_sum(s, p)
        c = c + (se + pe)
        return (s, c), None

    (s, c), _ = jax.lax.scan(step, (jnp.zeros((), dtype),) * 2, (a, b))
    return s + c
