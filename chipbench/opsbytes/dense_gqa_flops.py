"""Operations a dense GQA decoder requires, from its shapes alone.

A multiply-add counts as two. Nothing recomputed is counted: the
backward pass is twice the forward pass (one product for the input's
gradient, one for the weight's), so a training step is three forward
passes whatever the program rematerialises. Embedding look-ups, norms,
rotary and softmax are left out (under one percent at these widths).
"""

from __future__ import annotations


def matmul_params(widths: dict, layers: int) -> int:
    """Weights that a token is multiplied with: the projections, the
    MLP and the head (not the embedding table, which is looked up)."""
    h, i = widths["hidden_size"], widths["intermediate_size"]
    d = widths["head_dim"]
    q, kv = widths["num_attention_heads"] * d, \
        widths["num_key_value_heads"] * d
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * i
    return layers * per_layer + h * widths["vocab_size"]


def attention_flops(widths: dict, layers: int, q_len: int,
                    ctx_len: float) -> float:
    """QK^T and PV for ``q_len`` queries that each see ``ctx_len`` keys
    on average (causal prefill of s tokens: ctx_len = (s + 1) / 2)."""
    q = widths["num_attention_heads"] * widths["head_dim"]
    return layers * 2 * 2 * q_len * ctx_len * q


def forward(widths: dict, layers: int, tokens: int,
            ctx_len: float) -> float:
    """Forward pass over ``tokens`` tokens."""
    return 2.0 * matmul_params(widths, layers) * tokens \
        + attention_flops(widths, layers, tokens, ctx_len)


def train_step(widths: dict, layers: int, batch: int, seq: int) -> float:
    """Forward and backward of ``batch`` causal rows of ``seq``."""
    return 3.0 * forward(widths, layers, batch * seq, (seq + 1) / 2)
