"""Transformer layer substrate. Every matmul routes through the paper's BLAS
dispatch layer (repro.core.dispatch.gemm) so numerics policies apply
transparently to the whole zoo.

Sites are threaded as plain strings (``site + "_qk"`` composition below);
``GemmSite.parse`` in the dispatch layer lifts them to structured identities,
and differentiating through any of these layers dispatches each backward GEMM
under its own phase-qualified site (``attn_qk@bwd.dA`` / ``@bwd.dB``) — so a
PrecisionPlan can give training gradients wider numerics than the forward
pass without this file changing at all."""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch

Array = jax.Array


# ---------------------------------------------------------------------------
# Distribution context: optional mesh + constraint helper
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Distribution:
    mesh: object = None                       # jax.sharding.Mesh | None
    dp_axes: tuple = ("data",)                # batch axes (may include "pod")
    tp_axis: Optional[str] = "model"          # tensor/sequence-parallel axis
    # MLP activation pattern (§Perf hillclimb #2):
    #  "megatron": x gathered over tp, f-sharded compute, reduce at output
    #  "sp":       x stays sequence-sharded, weights ZeRO-gathered per layer
    #              (no per-layer activation collectives on the tp axis)
    mlp_pattern: str = "sp"
    # decode_tp profile (§Perf hillclimb: weights-stay-put serving): MoE
    # weights are sharded over the JOINT (dp..., tp) axes and activations
    # replicated; moe_block psums over all axes instead of gathering weights.
    joint_tp: bool = False
    # NumericsPolicy riding with the distribution: launch profiles carry the
    # deployed plan's policy here so make_train_step / serve pick it up
    # without a separate argument (None = caller's ambient policy).
    numerics_policy: object = None

    @property
    def dp(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def constrain(self, x: Array, *spec) -> Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, P(*spec)))


LOCAL = Distribution()


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def activate(x: Array, kind: str) -> Array:
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


# ---------------------------------------------------------------------------
# Dense projection through the numerics dispatch layer
# ---------------------------------------------------------------------------
def dense(x: Array, w: Array, site: str, bias: Optional[Array] = None,
          plan: Optional["dispatch.GemmPlan"] = None) -> Array:
    """x (..., K) @ w (K, N) via the BLAS dispatch; returns x.dtype.

    Leading dims are passed through un-flattened: a reshape that merged a
    data-sharded batch dim with a model-sharded sequence dim would force XLA
    to all-gather the activations (unrepresentable merged sharding).

    Under ``jax.grad`` the activation gradient dispatches as ``<site>@bwd.dA``
    and the weight gradient (one flattened Aᵀ·G GEMM) as ``<site>@bwd.dB``.

    ``plan`` pins Pallas block sizes for this call-site; by default the
    dispatch layer resolves one from its GemmPlan cache per operand shape.
    The bias add runs under the site's scope too, so a profile charges it
    to the site."""
    out = dispatch.gemm(x, w, site=site, plan=plan)
    if bias is not None:
        with jax.named_scope(dispatch.GemmSite.parse(site).scope):
            out = out + bias
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, H, S, hd), positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xr = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return xr.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, chunked online-softmax for long sequences)
# ---------------------------------------------------------------------------
def _grouped_scores(q: Array, k: Array, site: str) -> Array:
    """q (B,Kh,G,Sq,hd) x k (B,Kh,Sk,hd) -> (B,Kh,G,Sq,Sk) via dispatch."""
    return dispatch.grouped_qk(q, k, site=site)


def _grouped_values(p: Array, v: Array, site: str) -> Array:
    """p (B,Kh,G,Sq,Sk) x v (B,Kh,Sk,hd) -> (B,Kh,G,Sq,hd)."""
    return dispatch.grouped_av(p, v, site=site)


def attention(q: Array, k: Array, v: Array, *, causal: bool,
              chunk: int = 1024, prefix_len: int = 0,
              q_offset: int | Array = 0, site: str = "attn") -> Array:
    """Chunked (flash-style) attention with online softmax.

    q: (B, H, Sq, hd); k, v: (B, Hkv, Sk, hd). GQA via head grouping (no kv
    materialized repeat). ``prefix_len``: bidirectional prefix (VLM prefix-LM).
    ``q_offset``: absolute position of q[0] (incremental decode).
    Returns (B, H, Sq, hd) in q.dtype.
    """
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    q = q.reshape(B, Hkv, G, Sq, hd)
    scale = hd ** -0.5

    nc = -(-Sk // chunk)
    pad = nc * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = jnp.moveaxis(k.reshape(B, Hkv, nc, chunk, hd), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, Hkv, nc, chunk, hd), 2, 0)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, xs):
        m, l, acc = carry
        ci, kci, vci = xs
        s = _grouped_scores(q, kci, site + "_qk").astype(jnp.float32) * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        valid = k_pos < Sk
        if causal:
            ok = (k_pos[None, :] <= q_pos[:, None]) | (k_pos[None, :] < prefix_len)
        else:
            ok = jnp.ones((Sq, chunk), jnp.bool_)
        ok = ok & valid[None, :]
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * alpha + p.sum(-1)
        pv = _grouped_values(p.astype(v.dtype), vci, site + "_av")
        acc = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    # checkpoint the chunk step: backward recomputes the (Sq x chunk) score
    # block per chunk instead of materializing all of them (flash-attn bwd)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (jnp.arange(nc), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, Sq, hd).astype(q.dtype)


def decode_attention(q: Array, k: Array, v: Array, *, cache_len: Array,
                     k_scale: Optional[Array] = None,
                     v_scale: Optional[Array] = None,
                     start: Optional[Array] = None,
                     site: str = "attn") -> Array:
    """Single-step attention against a (possibly longer-than-valid) KV cache.
    q: (B, H, 1, hd); k, v: (B, Hkv, Smax, hd); cache_len: valid prefix.

    Quantized cache (the paper's ⟨msb,lsb⟩ tailoring applied to KV storage):
    k/v int8 with per-position scales (B, Hkv, Smax); dequantization is
    folded into the einsums (scores x k_scale; probs x v_scale)."""
    B, H, Sq, hd = q.shape
    Hkv, Smax = k.shape[1], k.shape[2]
    qv = q.reshape(B, Hkv, H // Hkv, Sq, hd)
    kk = k.astype(q.dtype) if k.dtype == jnp.int8 else k
    s = _grouped_scores(qv, kk, site + "_qk").astype(jnp.float32) * hd ** -0.5
    if k_scale is not None:
        s = s * k_scale.astype(jnp.float32)[:, :, None, None, :]
    valid = jnp.arange(Smax)[None, :] < jnp.atleast_1d(cache_len)[:, None]
    if start is not None:
        # continuous batching: slots reused mid-stream only attend to their
        # own request's prefix [start, len)
        valid = valid & (jnp.arange(Smax)[None, :]
                         >= jnp.atleast_1d(start)[:, None])
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale.astype(jnp.float32)[:, :, None, None, :]
    vv = v.astype(q.dtype) if v.dtype == jnp.int8 else v
    out = _grouped_values(p.astype(vv.dtype), vv, site + "_av")
    return out.reshape(B, H, Sq, hd).astype(q.dtype)


def _write_row(cache: Array, row: Array, pos: Array,
               layer: Optional[Array] = None) -> Array:
    """``row`` (B, Hkv, S, ...) written into ``cache`` (B, Hkv, Smax, ...)
    at sequence position ``pos``; with ``layer``, into that layer of the
    stacked cache (n_layers, B, Hkv, Smax, ...)."""
    row = row.astype(cache.dtype)
    if layer is None:
        return jax.lax.dynamic_update_slice_in_dim(cache, row, pos, axis=2)
    start = (layer, 0, 0, pos) + (0,) * (cache.ndim - 4)
    return jax.lax.dynamic_update_slice(cache, row[None], start)


def quantize_kv(x: Array):
    """Per-position symmetric int8 quantization: x (B, Hkv, S, hd) ->
    (int8 values, scales (B, Hkv, S))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


# ---------------------------------------------------------------------------
# Attention block (projections + rope + norm options)
# ---------------------------------------------------------------------------
def init_attention(key, cfg, dtype=jnp.float32):
    d, H, Kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, H * hd), dtype) * scale,
        "wk": jax.random.normal(ks[1], (d, Kh * hd), dtype) * scale,
        "wv": jax.random.normal(ks[2], (d, Kh * hd), dtype) * scale,
        "wo": jax.random.normal(ks[3], (H * hd, d), dtype) * (H * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Kh * hd,), dtype)
        p["bv"] = jnp.zeros((Kh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_block(x: Array, p, cfg, dist: Distribution, *,
                    causal: bool = True, prefix_len: int = 0,
                    positions: Optional[Array] = None,
                    kv_cache: Optional[dict] = None,
                    kv_override: Optional[tuple] = None,
                    site: str = "attn"):
    """Full attention sub-block. Returns (out, new_kv_cache | None).

    kv_cache: {"k": (B,Hkv,Smax,hd), "v": ..., "len": int32[B?]} for decode,
    or the stacked leaves (n_layers,B,Hkv,Smax,hd) plus "layer": the index
    of this layer, whose row is written into the stack (the new cache then
    holds the whole stack).
    kv_override: precomputed (k, v) (whisper cross-attention).
    """
    B, S, d = x.shape
    H, Kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], site + "_q", p.get("bq"))
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    if kv_override is not None:
        k, v = kv_override
    else:
        k = dense(x, p["wk"], site + "_k", p.get("bk"))
        v = dense(x, p["wv"], site + "_v", p.get("bv"))
        k = k.reshape(B, S, Kh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, Kh, hd).transpose(0, 2, 1, 3)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if positions is None:
        positions = jnp.arange(S)
    if kv_override is None:   # no rope on cross-attention
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if kv_cache is None and S > 1:
        # SP: q stays sequence-sharded; K/V are the (all-gathered) small side
        q = dist.constrain(q, dist.dp, None, dist.tp_axis, None)
        k = dist.constrain(k, dist.dp, None, None, None)
        v = dist.constrain(v, dist.dp, None, None, None)

    new_cache = None
    if kv_cache is not None:
        # incremental decode: write k,v at position len, attend to prefix,
        # under a ``kv_cache`` scope so a profile names the cache's time.
        # With a "layer" index the leaves are the whole stack (n_layers, B,
        # Hkv, Smax, ...), carried by the layer scan and updated in place.
        # Attention reads this layer's slice, taken before the stack is
        # written, with the row written into it; the stack then takes the
        # row back from that slice. The order matters to the compilers: with
        # the read after the write, the CPU copies the whole stack to keep
        # the read intact, and the TPU relays the whole stack for attention
        # at every step's entry and exit.
        ln, layer = kv_cache["len"], kv_cache.get("layer")
        with jax.named_scope("kv_cache"):
            if "k_scale" in kv_cache:      # int8 tailored cache
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                rows = {"k": k, "v": v}
            if layer is None:
                cur = new_cache = {n: _write_row(kv_cache[n], r, ln)
                                   for n, r in rows.items()}
            else:
                cur = {n: _write_row(jax.lax.dynamic_index_in_dim(
                    kv_cache[n], layer, 0, keepdims=False), r, ln)
                    for n, r in rows.items()}
                new_cache = {n: _write_row(
                    kv_cache[n], jax.lax.dynamic_slice_in_dim(c, ln, S, 2),
                    ln, layer) for n, c in cur.items()}
        out = decode_attention(q, cur["k"], cur["v"], cache_len=ln + S,
                               k_scale=cur.get("k_scale"),
                               v_scale=cur.get("v_scale"),
                               start=kv_cache.get("start"), site=site)
        new_cache["len"] = ln + S
    else:
        out = attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                        prefix_len=prefix_len, site=site)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return dense(out, p["wo"], site + "_o"), new_cache


# ---------------------------------------------------------------------------
# MLP (GLU)
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "w_in": jax.random.normal(ks[0], (d, f), dtype) * d ** -0.5,
        "w_gate": jax.random.normal(ks[1], (d, f), dtype) * d ** -0.5,
        "w_out": jax.random.normal(ks[2], (f, d), dtype) * f ** -0.5,
    }


def mlp_block(x: Array, p, cfg, dist: Distribution, site: str = "mlp") -> Array:
    S = x.shape[1]
    sp = (dist.mlp_pattern == "sp" and dist.mesh is not None
          and S > 1 and S % dist.mesh.shape[dist.tp_axis] == 0)
    if sp:
        # sequence stays sharded over tp; the (small) per-layer weights are
        # gathered just-in-time instead of the (huge) full-sequence
        # activations — force XLA onto the weight-gather side by pinning
        # both matmul inputs (x seq-sharded, w replicated).
        x = dist.constrain(x, dist.dp, dist.tp_axis, None)
        w_in = dist.constrain(p["w_in"], None, None)
        w_gate = dist.constrain(p["w_gate"], None, None)
        w_out = dist.constrain(p["w_out"], None, None)
        h = dense(x, w_in, site + "_in")
        g = dense(x, w_gate, site + "_gate")
        h = activate(g, cfg.act) * h
        h = dist.constrain(h, dist.dp, dist.tp_axis, None)
        return dense(h, w_out, site + "_out")
    h = dense(x, p["w_in"], site + "_in")
    g = dense(x, p["w_gate"], site + "_gate")
    h = activate(g, cfg.act) * h
    h = dist.constrain(h, dist.dp, None, dist.tp_axis)
    return dense(h, p["w_out"], site + "_out")
