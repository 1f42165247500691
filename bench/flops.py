"""Model FLOPs, counted from the configuration and the shapes alone.

The count is the same whatever runs a GEMM (the MXU, the ``simulate``
oracle or a Pallas kernel): two operations per multiply-add of the
projections, the MLP and the lm_head, plus the attention scores and values
over the positions a token attends to. The embedding gather, norms,
softmax and rotary embedding are left out.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights one token multiplies by (dense decoder)."""
    d, f, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, Kh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * H * hd * 2 + d * Kh * hd * 2
    return c["num_hidden_layers"] * (attn + 3 * d * f) + d * V


def token_flops(c: dict, context: int) -> float:
    """One token fed at 1-based position ``context``: projections and MLP
    plus QK^T and PV over ``context`` positions."""
    attn = 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * context
    return 2.0 * matmul_params(c) + attn

