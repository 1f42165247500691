"""Grouped attention contractions with one row per (batch, kv head).

At ``G * Sq == 1`` (MHA decode) the native einsum gets a zero second row
so that the TPU keeps it a dot reading K/V in place (the v5e compile
rehearsal in ``tests/test_chip_compile.py`` checks the lowering). Here on
the CPU: the padded contraction gives the plain einsum's numbers and
gradients, the zero row reaches neither the output nor the calibration
hook, and ``repro_dispatch_row_pad_total`` counts exactly the shapes that
take it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import (FP32, MXU_FP32, GemmConfig, NumericsPolicy,
                                 grouped_av, grouped_qk, set_trace_hook,
                                 use_policy)
from repro.models import decode_step, init, init_cache
from repro.models.layers import decode_attention, quantize_kv
from repro.obs.registry import default_registry

HIGHEST = jax.lax.Precision.HIGHEST
SIMULATE = NumericsPolicy(GemmConfig(FP32, AccumulatorSpec.paper_91bit(),
                                     "simulate"))
# decode shapes: 4 slots, 6 kv heads (= query heads), 64 cache rows of 32
B, KH, SK, HD = 4, 6, 64, 32


def _row_pads(site: str) -> float:
    counter = default_registry().get("repro_dispatch_row_pad_total")
    return 0.0 if counter is None else counter.value(site=site)


def _normal(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _plain_qk(q, k):
    return jnp.einsum("bkgqd,bksd->bkgqs", q, k, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _plain_av(p, v):
    return jnp.einsum("bkgqs,bksd->bkgqd", p, v, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _exact(rng, shape, denominator=1):
    """Small integers over a power of two: every product and every sum of
    a contraction here is exact in f32, in any order, so the padded and
    the plain contraction must agree bit for bit."""
    return jnp.asarray(rng.integers(-8, 9, shape) / denominator, jnp.float32)


def test_one_row_contractions_equal_the_plain_einsum(rng):
    q = _exact(rng, (B, KH, 1, 1, HD))
    k, v = _exact(rng, (B, KH, SK, HD)), _exact(rng, (B, KH, SK, HD))
    p = _exact(rng, (B, KH, 1, 1, SK), 64)
    with use_policy(MXU_FP32):
        s, o = jax.jit(grouped_qk)(q, k), jax.jit(grouped_av)(p, v)
    assert s.shape == (B, KH, 1, 1, SK) and s.dtype == jnp.float32
    assert o.shape == (B, KH, 1, 1, HD) and o.dtype == jnp.float32
    np.testing.assert_array_equal(s, _plain_qk(q, k))
    np.testing.assert_array_equal(o, _plain_av(p, v))


def _reference_decode(q, k, v, cache_len, k_scale=None, v_scale=None):
    """float64 single-step attention over the first ``cache_len`` rows."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    s = np.einsum("bhqd,bhsd->bhqs", q, k) * q.shape[-1] ** -0.5
    if k_scale is not None:
        s = s * np.asarray(k_scale, np.float64)[:, :, None, :]
    s = np.where(np.arange(k.shape[2]) < cache_len, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    if v_scale is not None:
        p = p * np.asarray(v_scale, np.float64)[:, :, None, :]
    return np.einsum("bhqs,bhsd->bhqd", p, v)


@pytest.mark.parametrize("cache", ["f32", "int8"])
def test_mha_decode_attention_matches_reference(rng, cache):
    """``decode_attention`` at G = 1, the f32 cache and the int8 cache with
    its per-position scales, against a float64 reference on the same
    (dequantized-by-scale) operands."""
    q = _normal(rng, (B, KH, 1, HD))
    k, v = _normal(rng, (B, KH, SK, HD)), _normal(rng, (B, KH, SK, HD))
    scales = {}
    if cache == "int8":
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
        scales = dict(k_scale=ks, v_scale=vs)
    n = SK - 5
    before = _row_pads("attn_qk"), _row_pads("attn_av")
    with use_policy(MXU_FP32):
        out = jax.jit(lambda q, k, v: decode_attention(
            q, k, v, cache_len=jnp.int32(n), **scales))(q, k, v)
    assert (_row_pads("attn_qk"), _row_pads("attn_av")) == (before[0] + 1,
                                                           before[1] + 1)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(out, _reference_decode(q, k, v, n, **scales),
                               rtol=1e-5, atol=1e-5)


def test_one_row_gradients_equal_the_plain_einsum(rng):
    """The custom VJPs, whose dQ and dP are one-row contractions too, give
    the plain einsum's gradients (exact operands, linear loss: bit for
    bit)."""
    q, p = _exact(rng, (B, KH, 1, 1, HD)), _exact(rng, (B, KH, 1, 1, SK), 64)
    k, v = _exact(rng, (B, KH, SK, HD)), _exact(rng, (B, KH, SK, HD))
    wq, wa = _exact(rng, (B, KH, 1, 1, SK)), _exact(rng, (B, KH, 1, 1, HD))

    def grads(qk, av):
        def f(q, k, p, v):
            return jnp.sum(wq * qk(q, k)) + jnp.sum(wa * av(p, v))
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3)))(q, k, p, v)

    with use_policy(MXU_FP32):
        got = grads(grouped_qk, grouped_av)
    for g, w in zip(got, grads(_plain_qk, _plain_av)):
        assert g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def test_zero_row_reaches_neither_output_nor_hook(rng):
    """The calibration hook sees the real contraction, in ``jnp.matmul``
    shape, with one row: the output it is shown is the one returned."""
    seen = []

    def hook(site_key, cfg, a, b, out):
        seen.append((site_key, a, b, out))

    q = _normal(rng, (B, KH, 1, 1, HD))
    k, v = _normal(rng, (B, KH, SK, HD)), _normal(rng, (B, KH, SK, HD))
    prev = set_trace_hook(hook)
    try:
        with use_policy(MXU_FP32):
            s = grouped_qk(q, k)
            o = grouped_av(jax.nn.softmax(s, -1), v)
    finally:
        set_trace_hook(prev)
    (qk_site, qa, qb, qout), (av_site, pa, pb, aout) = seen
    assert (qk_site, av_site) == ("attn_qk", "attn_av")
    assert qa.shape == (B, KH, 1, HD) and qb.shape == (B, KH, HD, SK)
    assert pa.shape == (B, KH, 1, SK) and pb.shape == (B, KH, SK, HD)
    np.testing.assert_array_equal(qa, q.reshape(B, KH, 1, HD))
    np.testing.assert_array_equal(qout, s.reshape(B, KH, 1, SK))
    np.testing.assert_array_equal(aout, o.reshape(B, KH, 1, HD))


# (G, Sq, policy) -> whether the contraction takes the padded path
SHAPES = [
    (1, 1, MXU_FP32, True),          # MHA decode
    (2, 1, MXU_FP32, False),         # GQA decode
    (1, 3, MXU_FP32, False),         # MHA prefill / training
    (1, 1, SIMULATE, False),         # FDP: the flattened 2-D dispatch
]


@pytest.mark.parametrize("g,sq,policy,pads", SHAPES,
                         ids=["mha_decode", "gqa_decode", "mha_prefill",
                              "fdp_simulate"])
def test_row_pad_counter_counts_one_row_native_shapes(rng, g, sq, policy,
                                                      pads):
    q = _normal(rng, (2, 2, g, sq, 8))
    k, v = _normal(rng, (2, 2, 16, 8)), _normal(rng, (2, 2, 16, 8))
    before = _row_pads("attn_qk"), _row_pads("attn_av")
    with use_policy(policy):
        grouped_av(jax.nn.softmax(grouped_qk(q, k), -1), v)
    assert (_row_pads("attn_qk") - before[0],
            _row_pads("attn_av") - before[1]) == (pads, pads)


@pytest.mark.parametrize("arch,pads", [("qwen1.5-4b", True),
                                       ("qwen3-0.6b", False)])
def test_row_pad_counter_over_a_decode_step(arch, pads):
    """Traced at published widths (two layers, nothing computed): the MHA
    step takes the padded path at both attention sites, the GQA one never."""
    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    params = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, 8, 1024,
                                              dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    before = _row_pads("attn_qk"), _row_pads("attn_av")
    with use_policy(MXU_FP32):
        jax.eval_shape(lambda p, c, t: decode_step(p, cfg, c, t),
                       params, cache, tok)
    qk, av = (_row_pads("attn_qk") - before[0],
              _row_pads("attn_av") - before[1])
    assert (qk > 0, av > 0) == (pads, pads) and qk == av
