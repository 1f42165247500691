"""Plain reference of the dense decoder (Qwen3, Qwen1.5): full-sequence
causal forward in straightforward ``jax.numpy``, with no cache, batching,
dispatch or kernels.

It follows the published Qwen architecture, with rope_theta and the norm
eps read from the configuration file, and with the departures that file
lists (interleaved rotary pairs, an untied lm_head). It reads the
parameter tree by name and imports nothing of the program.

``dtype=float32`` computes at ``Precision.HIGHEST``; ``dtype=bfloat16`` is
the control: weights and activations in bfloat16 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale.astype(x.dtype)


def _rope(x, theta):
    """x (S, heads, hd): rotate the (x[2i], x[2i+1]) pairs by pos * f_i."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def forward(params: dict, c: dict, tokens, dtype=jnp.float32):
    """tokens (S,) int32 -> logits (S, vocab) in ``dtype``."""
    prec = (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)
    mm = lambda a, b: jnp.matmul(a, b.astype(dtype), precision=prec)
    H, Kh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    G, eps, theta = H // Kh, c["rms_norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["attn_norm"], eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if "bq" in a:
            q, k, v = (q + a["bq"].astype(dtype), k + a["bk"].astype(dtype),
                       v + a["bv"].astype(dtype))
        q, k, v = (q.reshape(S, H, hd), k.reshape(S, Kh, hd),
                   v.reshape(S, Kh, hd))
        if c.get("qk_norm"):
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(S, Kh, G, hd)
        s = jnp.einsum("skgd,tkd->kgst", q, k, precision=prec)
        s = s * jnp.asarray(hd ** -0.5, dtype)
        s = jnp.where(causal, s, jnp.asarray(-jnp.inf, dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", w, v, precision=prec)
        x = x + mm(o.reshape(S, H * hd), a["wo"])
        m = p["mlp"]
        h = _rms(x, p["mlp_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_in"]),
                   m["w_out"])
        return x, None

    x = params["embed"][tokens].astype(dtype)
    x, _ = lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"], eps)
    return mm(x, params["lm_head"])[:, :c["vocab_size"]]
