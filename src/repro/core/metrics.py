"""Numerical-quality metrics used throughout the paper's evaluation.

``correct_bits`` is the paper's Fig. 2 y-axis: the number of leading mantissa
bits of a result that agree with the infinitely-precise reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from fractions import Fraction

Array = jax.Array


def correct_bits(value, reference, cap: float = 53.0):
    """-log2(|v - ref| / |ref|), clipped to [0, cap]; cap when exact.

    Accepts python floats / numpy / jax arrays; computed in float64 on host
    (metrics are an offline reduction, never part of a jitted path).
    """
    v = np.asarray(jax.device_get(value), dtype=np.float64)
    r = np.asarray(jax.device_get(reference), dtype=np.float64)
    err = np.abs(v - r)
    denom = np.maximum(np.abs(r), np.finfo(np.float64).tiny)
    rel = err / denom
    with np.errstate(divide="ignore"):
        bits = -np.log2(rel)
    bits = np.where(rel == 0.0, cap, bits)
    return np.clip(bits, 0.0, cap)


def exact_dot_fraction(a, b) -> Fraction:
    """Infinitely-precise dot product via python Fractions (host oracle)."""
    a = np.asarray(jax.device_get(a), dtype=np.float64)
    b = np.asarray(jax.device_get(b), dtype=np.float64)
    s = Fraction(0)
    for x, y in zip(a.tolist(), b.tolist()):
        s += Fraction(x) * Fraction(y)
    return s


def fraction_to_float(f: Fraction) -> float:
    return float(f)


def frac_to_f32_rne(f: Fraction) -> np.float32:
    """Correct single RNE from Fraction to float32 (host oracle)."""
    if f == 0:
        return np.float32(0.0)
    s = -1 if f < 0 else 1
    f = abs(f)
    e = f.numerator.bit_length() - f.denominator.bit_length() - 23
    while f / Fraction(2) ** e >= 2 ** 24:
        e += 1
    while f / Fraction(2) ** e < 2 ** 23:
        e -= 1
    m = f / Fraction(2) ** e
    mi = int(m)
    rem = m - mi
    if rem > Fraction(1, 2) or (rem == Fraction(1, 2) and mi % 2 == 1):
        mi += 1
    return np.float32(s * np.ldexp(np.float64(mi), e))


def fdp_oracle(a, b, spec) -> np.float32:
    """Host-side normative semantics: per-product trunc at 2^lsb, exact sum,
    W-bit wrap, single RNE to f32."""
    exact = Fraction(0)
    scale = Fraction(2) ** spec.lsb
    for x, y in zip(np.asarray(a, np.float64).tolist(),
                    np.asarray(b, np.float64).tolist()):
        p = Fraction(x) * Fraction(y)
        exact += int(abs(p) / scale) * (1 if p >= 0 else -1)
    W = spec.width
    wrapped = ((int(exact) + 2 ** (W - 1)) % 2 ** W) - 2 ** (W - 1)
    return frac_to_f32_rne(Fraction(wrapped) * scale)


def reproducibility_deviation(fn, a, b, n_orders: int = 8, seed: int = 0):
    """Max absolute deviation of fn(a,b) across random input permutations —
    the paper's reproducibility probe (0.0 for the FDP by construction)."""
    rng = np.random.default_rng(seed)
    a = np.asarray(jax.device_get(a))
    b = np.asarray(jax.device_get(b))
    vals = []
    for i in range(n_orders):
        perm = rng.permutation(a.shape[0]) if i else np.arange(a.shape[0])
        vals.append(float(jax.device_get(fn(jnp.asarray(a[perm]),
                                            jnp.asarray(b[perm])))))
    vals = np.asarray(vals, dtype=np.float64)
    return float(np.max(np.abs(vals - vals[0]))), vals


def top1_agreement(logits, ref_logits) -> float:
    """Fig. 3 proxy metric: fraction of samples whose argmax matches the
    exact-accumulator reference."""
    l = np.asarray(jax.device_get(logits))
    r = np.asarray(jax.device_get(ref_logits))
    return float(np.mean(l.argmax(-1) == r.argmax(-1)))
