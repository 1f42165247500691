"""The numerically-tailored fixed-point accumulator (Kulisch scratchpad).

This is the paper's central object: a two's-complement fixed-point register
parameterized by ``⟨ovf, msb, lsb⟩`` into which exact floating-point products
are accumulated **without intermediate rounding**.  On the FPGA this is a wide
carry-save register; on TPU we represent it as a vector of int32 *limbs*, each
carrying a 16-bit digit plus carry headroom, so the whole algebra runs on the
vector unit (VPU) with plain int32 adds/shifts — exactly the kind of substrate
the MXU-adjacent VPU is good at.

The limb algebra is written over *planes*: a register is a sequence of L
int32 arrays of one shape, limb 0 first. That is the layout the Pallas
kernels keep in VMEM, ``(L, bm, bn)``, so every plane is a whole number of
(8, 128) vector tiles; the ``(..., L)`` array forms below (``carry_normalize``,
``to_float``, ...) are thin wrappers that unstack the last axis.

Normative semantics:
  * value(limbs) = Σ_l limbs[l] · 2^(lsb + 16·l)   (limbs int32, signed)
  * products are quantized ONCE at entry: round-toward-zero at 2^lsb
    (``trunc``, hardware default — drops the wires below lsb) or RNE,
  * additions are exact; carries are propagated lazily (≤ SAFE_CHUNK = 2^13
    products between normalizations, enforced by callers via chunking),
  * the register wraps (or saturates) at W = ovf + msb - lsb + 1 bits.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from .formats import Decoded, _ilog2

Array = jax.Array

LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1
# Max products safely accumulated between carry normalizations:
# per product, a limb receives < 2^17 in magnitude (two 16-bit digit halves);
# int32 headroom 2^31 -> stay strictly below: 2^13 * 2^17 = 2^30.
SAFE_CHUNK = 1 << 13


@dataclasses.dataclass(frozen=True)
class AccumulatorSpec:
    """⟨ovf, msb, lsb⟩ accumulator. Width W = ovf + msb - lsb + 1 bits.

    ``msb``: weight of the largest magnitude bit kept (2^msb).
    ``lsb``: weight of the smallest bit kept (2^lsb), lsb <= msb.
    ``ovf``: carry headroom bits on top of msb.
    """

    ovf: int
    msb: int
    lsb: int
    round_mode: str = "trunc"        # product-entry quantization: trunc | rne
    overflow_mode: str = "wrap"      # wrap | saturate

    def __post_init__(self):
        if self.lsb > self.msb:
            raise ValueError(f"lsb ({self.lsb}) > msb ({self.msb})")
        if self.round_mode not in ("trunc", "rne"):
            raise ValueError(self.round_mode)
        if self.overflow_mode not in ("wrap", "saturate"):
            raise ValueError(self.overflow_mode)

    @property
    def width(self) -> int:
        return self.ovf + self.msb - self.lsb + 1

    @property
    def num_limbs(self) -> int:
        return -(-self.width // LIMB_BITS)

    def describe(self) -> str:
        return (f"FDP<ovf:{self.ovf}, msb:{self.msb}, lsb:{self.lsb}> "
                f"({self.width}-bit, {self.num_limbs} limbs, {self.round_mode}/"
                f"{self.overflow_mode})")

    @classmethod
    def paper_91bit(cls) -> "AccumulatorSpec":
        """The paper's flagship 91-bit ⟨ovf:30, msb:30, lsb:-30⟩ instance."""
        return cls(ovf=30, msb=30, lsb=-30)

    @classmethod
    def for_exact(cls, fmt, max_terms: int) -> "AccumulatorSpec":
        """Size an accumulator so that accumulating ``max_terms`` products of
        ``fmt`` values is EXACT and overflow-free (FCCM'22 §IV sizing rule)."""
        p = fmt.precision
        emax, emin = fmt.emax, getattr(fmt, "emin", -fmt.emax)
        msb = 2 * emax + 2                   # |a*b| < 2^(2emax+2)
        lsb = 2 * (emin - (p - 1))           # smallest product bit (subnormal²)
        ovf = max(1, math.ceil(math.log2(max(max_terms, 2))))
        return cls(ovf=ovf, msb=msb, lsb=lsb)

    @classmethod
    def quire(cls, posit_fmt, max_terms: int = 1 << 20) -> "AccumulatorSpec":
        """The posit standard's *quire* for posit⟨n,es⟩: an accumulator wide
        enough that any dot product of posits is exact (maxpos² down to
        minpos²) with carry headroom — the posit-native instance of the
        paper's ⟨ovf,msb,lsb⟩ family."""
        n, es = posit_fmt.nbits, posit_fmt.es
        max_scale = (n - 2) * (1 << es)      # exponent of maxpos
        msb = 2 * max_scale + 2
        lsb = -2 * max_scale - 2 * posit_fmt.precision
        ovf = max(1, math.ceil(math.log2(max(max_terms, 2))))
        return cls(ovf=ovf, msb=msb, lsb=lsb)


Planes = Sequence[Array]


def _unstack(limbs: Array) -> list:
    return [limbs[..., l] for l in range(limbs.shape[-1])]


def _stack(planes: Planes) -> Array:
    return jnp.stack(planes, axis=-1)


_LIMB_SHIFT = LIMB_BITS.bit_length() - 1


def _limb_split(q: Array) -> tuple:
    """Grid bit offset -> (limb index, sub-shift 0..15): floor division by
    LIMB_BITS as an arithmetic shift, so it lowers to plain VPU shifts."""
    return jnp.right_shift(q, _LIMB_SHIFT), q & (LIMB_BITS - 1)


# ---------------------------------------------------------------------------
# Product entry: quantize an exact product onto the grid, as limb contributions
# ---------------------------------------------------------------------------
def _product_digits(a: Decoded, b: Decoded) -> tuple:
    """Exact 48-bit significand product a.mant*b.mant as three base-2^16
    digits (d0, d1, d2), computed in int32 via 12-bit digit splitting
    (24x24 -> 48 bits with exact carries)."""
    a_hi, a_lo = a.mant >> 12, a.mant & 0xFFF
    b_hi, b_lo = b.mant >> 12, b.mant & 0xFFF
    p0 = a_lo * b_lo                      # weight 2^0 , < 2^24
    p1 = a_lo * b_hi + a_hi * b_lo        # weight 2^12, < 2^25
    p2 = a_hi * b_hi                      # weight 2^24, < 2^24
    # digits of m = p0 + p1*2^12 + p2*2^24 in base 2^16 (exact carries)
    d0_raw = (p0 & 0xFFFF) + ((p1 & 0xF) << 12)
    d1_raw = (p0 >> 16) + ((p1 >> 4) & 0xFFFF) + ((p2 & 0xFF) << 8)
    d2_raw = (p1 >> 20) + (p2 >> 8)
    c0 = d0_raw >> 16
    d0 = d0_raw & 0xFFFF
    d1_raw = d1_raw + c0
    c1 = d1_raw >> 16
    d1 = d1_raw & 0xFFFF
    d2 = d2_raw + c1                      # < 2^17 is fine (top digit)
    return d0, d1, d2


def product_planes(spec: AccumulatorSpec, a: Decoded, b: Decoded, *,
                   reduce_leading: bool = False) -> list:
    """Exact limb contributions of the products a*b (elementwise, operands
    broadcast), quantized at 2^lsb per ``spec.round_mode``, as L planes.
    Each limb's magnitude is < 2^17, so up to SAFE_CHUNK contributions may be
    summed before ``normalize_planes``. ``reduce_leading`` sums each plane
    over the leading axis as soon as it is formed (the GEMM hot path: a K
    sub-chunk of products never exists as an L-fold tensor).

    The significand product is computed exactly in int32 via 12-bit digit
    splitting (24x24 -> 48 bits as three 16-bit digits), then aligned to the
    grid with a uniform shift. Pieces are placed as MAGNITUDES and the sign
    applied after: dropping below-limb-0 pieces of the non-negative form
    implements round-toward-zero exactly (a sign-folded two's-complement form
    would floor instead, off by 1 ulp for negative products)."""
    L = spec.num_limbs
    digits = _product_digits(a, b)
    q = a.exp + b.exp - spec.lsb                          # grid bit offset
    sign = 1 - 2 * (a.sign ^ b.sign)                      # +1 / -1
    j0, r = _limb_split(q)                                # limb of digit 0
    inc = (_rne_increment(digits, q) * sign
           if spec.round_mode == "rne" else None)         # lands on limb 0
    # compact 4-piece form: digit k's low part lands at limb j0+k, its high
    # part at j0+k+1, so piece i = lo[i] + hi[i-1] (|piece| < 2^17, the
    # headroom contract behind SAFE_CHUNK).
    lo = [jnp.left_shift(d, r) & LIMB_MASK for d in digits]
    hi = [jnp.right_shift(jnp.left_shift(d, r), LIMB_BITS) for d in digits]
    pieces = [lo[0], lo[1] + hi[0], lo[2] + hi[1], hi[2]]
    pieces = [p * sign for p in pieces]
    # Placement masks are shared across limbs (piece i of limb l needs
    # j0 == l-i, which only depends on l-i); each piece can only land on
    # limbs -3..L-1.
    npieces = len(pieces)
    mask = {d: (j0 == d).astype(jnp.int32) for d in range(1 - npieces, L)}
    out = []
    for l in range(L):
        acc_l = jnp.zeros(j0.shape, jnp.int32)
        for i, piece in enumerate(pieces):
            if l - i in mask:
                acc_l = acc_l + piece * mask[l - i]
        if inc is not None and l == 0:
            acc_l = acc_l + inc
        out.append(jnp.sum(acc_l, axis=0) if reduce_leading else acc_l)
    return out


def product_limbs(spec: AccumulatorSpec, a: Decoded, b: Decoded) -> Array:
    """``product_planes`` as one (*batch, L) int32 array."""
    return _stack(product_planes(spec, a, b))


def _rne_increment(digits, q: Array) -> Array:
    """The +1 ulp RNE increment (int32 0/1, magnitude) for products whose
    base-2^16 ``digits`` (sequence of arrays) sit at grid bit offset ``q``.

    guard = product bit at grid position -1, sticky = OR of bits below,
    lsb_bit = product bit at position 0 (pre-round). The increment applies to
    limb 0 (as magnitude; caller multiplies by sign afterwards, which matches
    round-half-away-from-zero-on-ties-odd — for RNE of the magnitude this is
    correct since negation of an RNE-magnitude equals RNE of the negation).
    """
    nd = len(digits)

    # bit at absolute product position p (0 <= p < 16*nd): p relative to grid = q + p
    # guard: grid pos -1 -> product bit pb = -1 - q ; valid if 0 <= pb < 16*nd
    def product_bit(pb):
        k, s = _limb_split(pb)
        val = jnp.zeros(pb.shape, jnp.int32)
        for kk in range(nd):
            val = val + jnp.where(k == kk,
                                  jnp.right_shift(digits[kk], s) & 1, 0)
        return jnp.where((pb >= 0) & (pb < LIMB_BITS * nd), val, 0)

    def bits_below(pb):   # OR of product bits strictly below pb
        any_below = jnp.zeros(pb.shape, jnp.bool_)
        for kk in range(nd):
            lo = pb - kk * LIMB_BITS     # bits of digit kk strictly below pb
            nbits = jnp.clip(lo, 0, LIMB_BITS)
            mask = jnp.left_shift(1, nbits) - 1
            any_below = any_below | ((digits[kk] & mask) != 0)
        return any_below

    pb_guard = -1 - q
    guard = product_bit(pb_guard)
    sticky = bits_below(pb_guard)
    lsb_bit = product_bit(-q)
    # entirely-below-grid products: guard position above all digits -> pb_guard >= 16nd
    # handled by product_bit bounds (guard=0 -> no correction; trunc-like).
    inc = (guard == 1) & (sticky | (lsb_bit == 1))
    return inc.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Carry normalization, wrap/saturate, read-out
# ---------------------------------------------------------------------------
def normalize_planes(planes: Planes) -> list:
    """Propagate carries so limbs 0..L-2 are in [0, 2^16); the top limb keeps
    the full signed remainder (NOT masked to W bits).

    Keeping the intermediate state exact in the extended (16L + int32
    headroom)-bit range makes the result independent of chunk/block
    boundaries; the W-bit wrap/saturation is applied ONCE at read-out
    (``planes_to_float``/``to_float``), which for wrap is equivalent (mod-2^W is a
    ring homomorphism) and for saturate is the only order-invariant
    definition."""
    out = []
    carry = 0
    for p in planes[:-1]:
        t = p + carry
        carry = jnp.right_shift(t, LIMB_BITS)      # arithmetic shift = floor
        out.append(t & LIMB_MASK)
    out.append(planes[-1] + carry)                 # top limb: full int32
    return out


def carry_normalize(spec: AccumulatorSpec, limbs: Array) -> Array:
    """``normalize_planes`` over a (..., L) array."""
    del spec
    return _stack(normalize_planes(_unstack(limbs)))


def _overflow_planes(spec: AccumulatorSpec, planes: Planes) -> list:
    """Wrap or saturate a carry-normalized register at W bits (two's
    complement)."""
    L, W = spec.num_limbs, spec.width
    top = planes[L - 1]
    top_bits = W - LIMB_BITS * (L - 1)              # 1..16 significant top bits
    if spec.overflow_mode == "wrap":
        # sign-extend the top limb from top_bits
        shift = 32 - top_bits
        return list(planes[:L - 1]) + [
            jnp.right_shift(jnp.left_shift(top, shift), shift)]
    lo, hi = -(1 << (top_bits - 1)), (1 << (top_bits - 1)) - 1
    over, under = top > hi, top < lo
    low = [jnp.where(over, LIMB_MASK, jnp.where(under, 0, p))
           for p in planes[:L - 1]]
    return low + [jnp.clip(top, lo, hi)]


def merge_states(spec: AccumulatorSpec, states: Array, axis: int = 0) -> Array:
    """Merge carry-normalized partial accumulator states (e.g. per-K-shard
    registers from ``fdp.fdp_gemm_limbs``) into one normalized register.

    Integer limb addition is exact, associative and commutative, so the
    merged register is bit-identical to accumulating all products on one
    device — for ANY partition of the reduction and ANY merge order. This is
    the single-host form of ``repro.parallel.collectives.fdp_psum``. Up to
    SAFE_CHUNK normalized states may be merged in one call (normalized digit
    magnitudes are < 2^16; int32 headroom covers 2^13 of them)."""
    assert states.shape[axis] <= SAFE_CHUNK
    return carry_normalize(spec, jnp.sum(states, axis=axis))


def _magnitude(spec: AccumulatorSpec, planes: Planes) -> tuple:
    """Finalized register -> (is_negative, magnitude digits, any nonzero,
    position of the highest set bit)."""
    planes = _overflow_planes(spec, planes)
    sign_neg = planes[-1] < 0
    mag = _negate_where(planes, sign_neg)
    any_nz = jnp.zeros(sign_neg.shape, jnp.bool_)
    top_idx = jnp.zeros(sign_neg.shape, jnp.int32)
    top_val = jnp.zeros(sign_neg.shape, jnp.int32)
    for l, m in enumerate(mag):
        nz = m != 0
        any_nz = any_nz | nz
        top_idx = jnp.where(nz, l, top_idx)
        top_val = jnp.where(nz, m, top_val)
    hb = _ilog2(jnp.maximum(top_val, 1)) + top_idx * LIMB_BITS
    return sign_neg, mag, any_nz, hb


def planes_to_float(spec: AccumulatorSpec, planes: Planes,
                    out_precision: int = 24) -> Array:
    """Round a carry-normalized register ONCE to a float (RNE at
    ``out_precision`` bits) and return f32. Exact for out_precision <= 24."""
    sign_neg, mag, any_nz, hb = _magnitude(spec, planes)
    # extract out_precision bits [hb-p+1 .. hb], guard at hb-p, sticky below
    p = out_precision
    take_from = hb - p + 1                                      # may be < 0
    mant = _extract_bits(mag, take_from, p)
    guard = _extract_bits(mag, take_from - 1, 1)
    sticky = _any_below(mag, take_from - 2)   # strictly below the guard bit
    rnd = (guard == 1) & (sticky | ((mant & 1) == 1))
    mant = mant + rnd.astype(jnp.int32)
    # mantissa overflow (2^p) -> exact power of two, bump exponent
    ovf = mant == (1 << p)
    mant = jnp.where(ovf, 1 << (p - 1), mant)
    exp = take_from + spec.lsb + jnp.where(ovf, 1, 0)
    v = jnp.ldexp(mant.astype(jnp.float32), exp)
    v = jnp.where(sign_neg, -v, v)
    return jnp.where(any_nz, v, jnp.float32(0.0))


def to_float(spec: AccumulatorSpec, limbs: Array, out_precision: int = 24) -> Array:
    """``planes_to_float`` over a carry-normalized (..., L) array."""
    return planes_to_float(spec, _unstack(limbs), out_precision)


def to_float64(spec: AccumulatorSpec, limbs: Array) -> Array:
    """Round the accumulator ONCE to float64 (53-bit RNE). Requires x64 to be
    enabled (benchmark processes); the mantissa is assembled from two int32
    pieces so the limb algebra itself stays int32/TPU-shaped."""
    sign_neg, mag, any_nz, hb = _magnitude(spec, _unstack(limbs))
    p = 53
    take_from = hb - p + 1
    lo_bits = 29
    hi = _extract_bits(mag, take_from + lo_bits, p - lo_bits)   # 24 bits
    lo = _extract_bits(mag, take_from, lo_bits)                 # 29 bits
    guard = _extract_bits(mag, take_from - 1, 1)
    sticky = _any_below(mag, take_from - 2)
    mant = hi.astype(jnp.float64) * (1 << lo_bits) + lo.astype(jnp.float64)
    rnd = (guard == 1) & (sticky | ((lo & 1) == 1))
    mant = mant + rnd.astype(jnp.float64)
    v = jnp.ldexp(mant, take_from + spec.lsb)
    v = jnp.where(sign_neg, -v, v)
    return jnp.where(any_nz, v, jnp.float64(0.0))


def _negate_where(planes: Planes, cond: Array) -> list:
    """Two's-complement negate across base-2^16 limbs where ``cond``.

    Input must be carry-normalized (digits 0..L-2 in [0,2^16), top limb a
    small signed value). Output where cond: magnitude digits, all in
    [0, 2^16)."""
    out = []
    borrow = 0
    for p in planes:
        t = -p - borrow
        neg = (t < 0).astype(jnp.int32)
        out.append(jnp.where(cond, t + neg * (1 << LIMB_BITS), p))
        borrow = neg
    return out


def _extract_bits(mag: Planes, start: Array, nbits: int) -> Array:
    """Bits [start, start+nbits) of the magnitude register as int32.
    start may be negative (those bits read as 0). nbits <= 29."""
    # value >> start, truncated to nbits: gathered from 3 adjacent limbs.
    j, s = _limb_split(start)
    part0 = jnp.right_shift(_limb_at(mag, j), s)
    part1 = jnp.left_shift(_limb_at(mag, j + 1), LIMB_BITS - s)
    # part2 only matters when s > 2*16 - nbits; clamp the shift.
    sh2 = jnp.clip(2 * LIMB_BITS - s, 0, 31)
    part2 = jnp.where(s > 2 * LIMB_BITS - nbits,
                      jnp.left_shift(_limb_at(mag, j + 2), sh2), 0)
    res = part0 | part1 | part2
    return res & ((1 << nbits) - 1)


def _limb_at(mag: Planes, idx: Array) -> Array:
    """Digit ``idx`` of the register (0 outside [0, L)), as a select chain —
    no gather, so it lowers inside a kernel."""
    out = jnp.zeros(idx.shape, jnp.int32)
    for l, m in enumerate(mag):
        out = jnp.where(idx == l, m, out)
    return out


def _any_below(mag: Planes, below: Array) -> Array:
    """OR of the magnitude bits at positions <= ``below`` (the sticky bit)."""
    any_set = jnp.zeros(below.shape, jnp.bool_)
    for l, m in enumerate(mag):
        lo = below + 1 - l * LIMB_BITS            # #bits of limb l at pos <= below
        nbits = jnp.clip(lo, 0, LIMB_BITS)
        mask = jnp.left_shift(1, nbits) - 1
        any_set = any_set | ((m & mask) != 0)
    return any_set
