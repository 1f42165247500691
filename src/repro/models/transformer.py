"""Model assembly for every architecture family.

One uniform API across families:

    params = init(cfg, key)                        (pure; eval_shape-able)
    logits = forward(params, cfg, batch, dist)     (train / prefill logits)
    cache  = init_cache(cfg, B, max_len)           (serving)
    logits, cache = decode_step(params, cfg, cache, tokens, dist)

Layers are scanned (stacked parameters) so the lowered HLO stays compact for
every depth; hybrid models scan groups (inner scan over SSM layers, shared
attention block between groups); encoder-decoder runs two scans.

Every GEMM site in this file is a *forward* site name; differentiating
``forward`` (training, calibration with ``--phases fwd,bwd``) dispatches the
matching ``<site>@bwd.dA``/``<site>@bwd.dB`` gradient sites automatically
through the dispatch layer's custom_vjp — model assembly never names a phase.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as MOE
from . import ssm as SSM
from .config import ModelConfig
from .layers import Distribution

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, cfg, dtype, *, cross: bool = False):
    """One decoder block's params (attention [+cross] + mlp/moe/ssm)."""
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.family in ("ssm", "hybrid"):
        p["ssm_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["ssm"] = SSM.init_ssm(ks[0], cfg, dtype)
        return p
    p["attn_norm"] = jnp.ones((cfg.d_model,), dtype)
    p["attn"] = L.init_attention(ks[0], cfg, dtype)
    if cross:
        p["cross_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = L.init_attention(ks[1], cfg, dtype)
    p["mlp_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.n_experts:
        p["moe"] = MOE.init_moe(ks[2], cfg.d_model, cfg.d_ff, cfg.n_experts,
                                dtype)
    elif cfg.d_ff:
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init(cfg: ModelConfig, key) -> dict:
    """Full parameter pytree (layer params stacked for scan)."""
    dtype = jnp.dtype(cfg.param_dtype)
    k_embed, k_layers, k_head, k_extra = jax.random.split(key, 4)
    V, d = cfg.padded_vocab, cfg.d_model
    params = {
        "embed": jax.random.normal(k_embed, (V, d), dtype) * d ** -0.5,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": jax.random.normal(k_head, (d, V), dtype) * d ** -0.5,
    }

    def stack_init(key, n, fn):
        return jax.vmap(fn)(jax.random.split(key, n))

    if cfg.family == "encdec":
        enc_cfg = cfg
        params["enc_layers"] = stack_init(
            k_extra, cfg.n_enc_layers, lambda k: _init_block(k, enc_cfg, dtype))
        params["dec_layers"] = stack_init(
            k_layers, cfg.n_layers,
            lambda k: _init_block(k, cfg, dtype, cross=True))
        params["enc_norm"] = jnp.ones((d,), dtype)
    elif cfg.family == "hybrid":
        ng = cfg.n_layers // cfg.attn_every
        flat = stack_init(k_layers, cfg.n_layers,
                          lambda k: _init_block(k, cfg, dtype))
        params["layers"] = jax.tree.map(
            lambda x: x.reshape(ng, cfg.attn_every, *x.shape[1:]), flat)
        # the weight-tied shared attention + MLP block
        ks = jax.random.split(k_extra, 3)
        shared_cfg = cfg
        params["shared"] = {
            "attn_norm": jnp.ones((d,), dtype),
            "attn": L.init_attention(ks[0], shared_cfg, dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            "mlp": L.init_mlp(ks[1], d, cfg.d_ff, dtype),
        }
    else:
        params["layers"] = stack_init(k_layers, cfg.n_layers,
                                      lambda k: _init_block(k, cfg, dtype))
    return params


def init_abstract(cfg: ModelConfig) -> dict:
    """ShapeDtypeStruct pytree of params (no allocation; for dry-runs)."""
    return jax.eval_shape(lambda: init(cfg, jax.random.key(0)))


# ---------------------------------------------------------------------------
# Blocks (forward)
# ---------------------------------------------------------------------------
def _decoder_block(x, p, cfg, dist, *, positions, prefix_len=0,
                   kv_cache=None, enc_out=None, moe_impl="tp"):
    """Returns (x, new_kv_cache)."""
    new_cache = None
    if cfg.family in ("ssm", "hybrid"):
        h, new_cache = SSM.ssm_block(
            L.rms_norm(x, p["ssm_norm"], cfg.norm_eps), p["ssm"], cfg, dist,
            cache=kv_cache)
        x = x + h
        return x, new_cache

    h, new_cache = L.attention_block(
        L.rms_norm(x, p["attn_norm"], cfg.norm_eps), p["attn"], cfg, dist,
        causal=True, prefix_len=prefix_len, positions=positions,
        kv_cache=kv_cache)
    x = x + h
    if enc_out is not None:
        kc, vc = enc_out
        h, _ = L.attention_block(
            L.rms_norm(x, p["cross_norm"], cfg.norm_eps), p["cross"], cfg,
            dist, causal=False, kv_override=(kc, vc))
        x = x + h
    if cfg.n_experts:
        fn = MOE.moe_block_ep if moe_impl == "ep" else MOE.moe_block
        x = x + fn(L.rms_norm(x, p["mlp_norm"], cfg.norm_eps), p["moe"], cfg,
                   dist)
    elif cfg.d_ff:
        x = x + L.mlp_block(L.rms_norm(x, p["mlp_norm"], cfg.norm_eps),
                            p["mlp"], cfg, dist)
    return x, new_cache


def _encoder_block(x, p, cfg, dist):
    h, _ = L.attention_block(
        L.rms_norm(x, p["attn_norm"], cfg.norm_eps), p["attn"], cfg, dist,
        causal=False)
    x = x + h
    x = x + L.mlp_block(L.rms_norm(x, p["mlp_norm"], cfg.norm_eps), p["mlp"],
                        cfg, dist)
    return x


def _shared_block(x, p, cfg, dist, *, positions, kv_cache=None):
    """Zamba2-style weight-shared full-attention + MLP block."""
    h, new_cache = L.attention_block(
        L.rms_norm(x, p["attn_norm"], cfg.norm_eps), p["attn"], cfg, dist,
        causal=True, positions=positions, kv_cache=kv_cache)
    x = x + h
    x = x + L.mlp_block(L.rms_norm(x, p["mlp_norm"], cfg.norm_eps), p["mlp"],
                        cfg, dist)
    return x, new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def _embed(params, cfg, tokens, dist):
    x = jnp.take(params["embed"], tokens, axis=0)
    return dist.constrain(x, dist.dp, dist.tp_axis, None)


def _logits(params, cfg, x, dist):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.dense(x.astype(jnp.float32), params["lm_head"].astype(jnp.float32),
                     "lm_head")
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        mask = jnp.concatenate([jnp.zeros((cfg.vocab_size,), jnp.float32),
                                jnp.full((pad,), -jnp.inf, jnp.float32)])
        logits = logits + mask
    return logits


# ---------------------------------------------------------------------------
# Forward (train / prefill): full-sequence logits
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch: dict, dist: Distribution = L.LOCAL,
            *, remat: str = "block", moe_impl: str = "tp",
            return_hidden: bool = False) -> Array:
    """batch: {"tokens": (B, S_text)} plus family extras:
    vlm: {"patches": (B, n_patches, d)}; encdec: {"frames": (B, enc_seq, d)}.
    Returns logits (B, S_total, padded_vocab) f32 (or final-norm hidden
    states (B, S_total, d) when return_hidden — used by the chunked loss)."""
    tokens = batch["tokens"]
    x = _embed(params, cfg, tokens, dist)
    prefix_len = 0

    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = cfg.n_patches
    x = dist.constrain(x, dist.dp, dist.tp_axis, None)
    B, S, _ = x.shape
    positions = jnp.arange(S)

    enc_kv = None
    if cfg.family == "encdec":
        enc = batch["frames"].astype(x.dtype)
        enc = dist.constrain(enc, dist.dp, None, None)

        def enc_body(h, lp):
            return _encoder_block(h, lp, cfg, dist), None

        enc_body = _maybe_remat(enc_body, remat)
        enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
        enc = L.rms_norm(enc, params["enc_norm"], cfg.norm_eps)

    def body_raw(h, lp):
        if cfg.family == "encdec":
            # per-layer cross K/V from encoder output
            kc = L.dense(enc, lp["cross"]["wk"], "cross_k")
            vc = L.dense(enc, lp["cross"]["wv"], "cross_v")
            Bk = kc.shape[0]
            kc = kc.reshape(Bk, -1, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
            vc = vc.reshape(Bk, -1, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
            h, _ = _decoder_block(h, lp, cfg, dist, positions=positions,
                                  enc_out=(kc, vc), moe_impl=moe_impl)
        else:
            h, _ = _decoder_block(h, lp, cfg, dist, positions=positions,
                                  prefix_len=prefix_len, moe_impl=moe_impl)
        h = dist.constrain(h, dist.dp, dist.tp_axis, None)
        return h, None

    body = _maybe_remat(body_raw, remat)

    if cfg.family == "hybrid":
        def group_body(h, gp):
            h, _ = jax.lax.scan(body_raw, h, gp)     # remat at group level
            h, _ = _shared_block(h, params["shared"], cfg, dist,
                                 positions=positions)
            h = dist.constrain(h, dist.dp, dist.tp_axis, None)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(group_body, remat), x,
                            params["layers"])
    elif cfg.family == "encdec":
        x, _ = jax.lax.scan(body, x, params["dec_layers"])
    else:
        x, _ = jax.lax.scan(body, x, params["layers"])

    if return_hidden:
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x, dist)


def _maybe_remat(fn, remat: str):
    if remat in ("block", "full"):
        return jax.checkpoint(fn)
    return fn


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode_step
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, quantized: bool = False) -> dict:
    """Abstract-safe cache pytree for incremental decoding.

    quantized=True: int8 KV with per-position scales — the paper's
    numerically-tailored storage applied to the cache (halves HBM).
    Supported for the decoder-only families (dense/moe/vlm)."""
    Bq = batch
    quantized = quantized and cfg.family in ("dense", "moe", "vlm")

    def attn_cache(n):
        kv_dtype = jnp.int8 if quantized else dtype
        c = {
            "k": jnp.zeros((n, Bq, cfg.n_kv_heads, max_len, cfg.head_dim),
                           kv_dtype),
            "v": jnp.zeros((n, Bq, cfg.n_kv_heads, max_len, cfg.head_dim),
                           kv_dtype),
        }
        if quantized:
            c["k_scale"] = jnp.zeros((n, Bq, cfg.n_kv_heads, max_len),
                                     jnp.float32)
            c["v_scale"] = jnp.zeros((n, Bq, cfg.n_kv_heads, max_len),
                                     jnp.float32)
        return c

    def ssm_cache(n):
        g, e, p, s = (cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups,
                      cfg.ssm_head_dim, cfg.ssm_state)
        w, di, gn = cfg.ssm_conv, cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
        return {
            "conv_x": jnp.zeros((n, Bq, w - 1, di), dtype),
            "conv_B": jnp.zeros((n, Bq, w - 1, gn), dtype),
            "conv_C": jnp.zeros((n, Bq, w - 1, gn), dtype),
            "state": jnp.zeros((n, Bq, g, e, p, s), jnp.float32),
        }

    cache = {"len": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        cache["layers"] = attn_cache(cfg.n_layers)
    elif cfg.family == "ssm":
        cache["layers"] = ssm_cache(cfg.n_layers)
    elif cfg.family == "hybrid":
        ng = cfg.n_layers // cfg.attn_every
        inner = ssm_cache(cfg.n_layers)
        cache["layers"] = jax.tree.map(
            lambda x: x.reshape(ng, cfg.attn_every, *x.shape[1:]), inner)
        shared = attn_cache(ng)
        cache["shared"] = shared
    elif cfg.family == "encdec":
        cache["layers"] = attn_cache(cfg.n_layers)
        cache["cross"] = {
            "k": jnp.zeros((cfg.n_layers, Bq, cfg.n_kv_heads, cfg.enc_seq,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((cfg.n_layers, Bq, cfg.n_kv_heads, cfg.enc_seq,
                            cfg.head_dim), dtype),
        }
    return cache


def decode_step(params, cfg: ModelConfig, cache: dict, tokens: Array,
                dist: Distribution = L.LOCAL, *, moe_impl: str = "tp"):
    """One incremental decode step. tokens: (B, 1) int32.
    Returns (logits (B, 1, V), new_cache)."""
    x = _embed(params, cfg, tokens, dist)
    pos = cache["len"] + jnp.zeros((x.shape[0], 1), jnp.int32)
    ln = cache["len"]
    kv_keys = [k for k in ("k", "v", "k_scale", "v_scale")
               if k in cache.get("layers", {})]
    slot_start = cache.get("start")      # (B,) continuous-batching lower bound

    if cfg.family in ("dense", "moe", "vlm"):
        # The stacked cache rides in the carry and each layer writes its row
        # into it: with the cache donated, the step updates it in place. As
        # scan xs/ys every layer's slice would be copied out and back.
        def body(c, xs):
            h, stack = c
            i, lp = xs
            kv = stack | {"len": ln, "start": slot_start, "layer": i}
            h, nc = _decoder_block(h, lp, cfg, dist, positions=pos,
                                   kv_cache=kv, moe_impl=moe_impl)
            return (h, {k: nc[k] for k in kv_keys}), None

        (carry, new_layers), _ = jax.lax.scan(
            body, (x, {k: cache["layers"][k] for k in kv_keys}),
            (jnp.arange(cfg.n_layers), params["layers"]))
        new_cache = {"len": ln + 1, "layers": new_layers}
        if slot_start is not None:
            new_cache["start"] = slot_start

    elif cfg.family == "ssm":
        def body(h, lc):
            sc = {k: lc[k] for k in ("conv_x", "conv_B", "conv_C", "state")}
            h, nc = _decoder_block(h, lc["p"], cfg, dist, positions=pos,
                                   kv_cache=sc)
            return h, nc

        carry, new_layers = jax.lax.scan(
            body, x, {"p": params["layers"], **cache["layers"]})
        new_cache = {"len": ln + 1, "layers": new_layers}

    elif cfg.family == "hybrid":
        def group_body(h, gc):
            def body(hh, lc):
                sc = {k: lc[k] for k in ("conv_x", "conv_B", "conv_C", "state")}
                hh, nc = _decoder_block(hh, lc["p"], cfg, dist, positions=pos,
                                        kv_cache=sc)
                return hh, nc

            h, new_inner = jax.lax.scan(
                body, h, {"p": gc["p"], **gc["ssm"]})
            kv = {"k": gc["shared"]["k"], "v": gc["shared"]["v"], "len": ln}
            h, nkv = _shared_block(h, params["shared"], cfg, dist,
                                   positions=pos, kv_cache=kv)
            return h, {"ssm": new_inner,
                       "shared": {"k": nkv["k"], "v": nkv["v"]}}

        gc = {"p": params["layers"],
              "ssm": cache["layers"], "shared": cache["shared"]}
        carry, new_groups = jax.lax.scan(group_body, x, gc)
        new_cache = {"len": ln + 1, "layers": new_groups["ssm"],
                     "shared": new_groups["shared"]}

    elif cfg.family == "encdec":
        def body(h, lc):
            kv = {"k": lc["k"], "v": lc["v"], "len": ln}
            h, nc = _decoder_block(h, lc["p"], cfg, dist, positions=pos,
                                   kv_cache=kv,
                                   enc_out=(lc["ck"], lc["cv"]))
            return h, {"k": nc["k"], "v": nc["v"]}

        carry, new_layers = jax.lax.scan(
            body, x, {"p": params["dec_layers"], **cache["layers"],
                      "ck": cache["cross"]["k"], "cv": cache["cross"]["v"]})
        new_cache = {"len": ln + 1, "layers": new_layers,
                     "cross": cache["cross"]}
    else:
        raise ValueError(cfg.family)

    logits = _logits(params, cfg, carry, dist)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, batch: dict, cache: dict,
            dist: Distribution = L.LOCAL):
    """Fill the cache from a prompt by running decode_step over positions.
    (Small-scale serving helper; the big prefill shapes lower ``forward``.)"""
    tokens = batch["tokens"]
    B, S = tokens.shape

    if cfg.family == "encdec":
        enc = batch["frames"]

        def enc_body(h, lp):
            return _encoder_block(h, lp, cfg, dist), None

        enc_out, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
        enc_out = L.rms_norm(enc_out, params["enc_norm"], cfg.norm_eps)

        def cross_kv(lp):
            kc = L.dense(enc_out, lp["cross"]["wk"], "cross_k")
            vc = L.dense(enc_out, lp["cross"]["wv"], "cross_v")
            kc = kc.reshape(B, -1, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
            vc = vc.reshape(B, -1, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
            return kc, vc

        ck, cv = jax.lax.map(cross_kv, params["dec_layers"])
        cache["cross"] = {"k": ck.astype(cache["cross"]["k"].dtype),
                          "v": cv.astype(cache["cross"]["v"].dtype)}

    def step(carry, t):
        cache, last = carry
        logits, cache = decode_step(params, cfg, cache, t[:, None], dist)
        return (cache, logits[:, 0]), None

    (cache, last), _ = jax.lax.scan(step, (cache, jnp.zeros(
        (B, cfg.padded_vocab), jnp.float32)), tokens.T)
    return last, cache
