"""Operations one chip's share of LFM2-MoE requires, from its shapes.

A multiply-add counts as two; nothing recomputed is counted: the
backward pass is twice the forward pass, so a training step is three
forward passes. Counted, a token:

- every weight it is multiplied with: a short convolution's ``in_proj``
  and ``out_proj``, q/k/v/o, a dense MLP's three matrices, the router,
  the tied head;
- a **routed expert for the rows it is expected to get**: a token
  chooses ``num_experts_per_tok`` of the router's experts and this chip
  holds ``num_experts`` of ``router_num_experts``, so with even routing
  a token meets k * held / all experts' worth of weights here
  (4 * 8 / 32 = 1 expert at the published sizes). The run's own share
  is ``swiglu_experts_rows_share.train``;
- causal attention's half square, QK^T and PV, at the true head size.

Left out (under one percent): the embedding look-up, norms (the heads'
q/k norms too), RoPE, the short convolutions' taps and both gates, the
SwiGLU gates, softmax.
"""

from __future__ import annotations


def matmul_params(w: dict, layers: int) -> float:
    """Weights a token is multiplied with on this chip, even routing."""
    h = w["hidden_size"]
    q = w["num_attention_heads"] * w["head_dim"]
    kv = w["num_key_value_heads"] * w["head_dim"]
    operator = {"conv": h * 3 * h + h * h,
                "full_attention": h * q + 2 * h * kv + q * h}
    routed_share = w["num_experts_per_tok"] * w["num_experts"] \
        / w["router_num_experts"]
    sparse = h * w["router_num_experts"] \
        + routed_share * 3 * h * w["moe_intermediate_size"]
    dense = 3 * h * w["intermediate_size"]
    return sum(operator[kind] + (dense if i < w["num_dense_layers"]
                                 else sparse)
               for i, kind in enumerate(w["layer_types"][:layers])) \
        + h * w["vocab_size"]


def attention_flops(w: dict, layers: int, q_len: int,
                    ctx_len: float) -> float:
    q = w["num_attention_heads"] * w["head_dim"]
    return w["layer_types"][:layers].count("full_attention") \
        * 2 * 2 * q_len * ctx_len * q


def forward(w: dict, layers: int, tokens: int, ctx_len: float) -> float:
    return 2.0 * matmul_params(w, layers) * tokens \
        + attention_flops(w, layers, tokens, ctx_len)


def train_step(w: dict, layers: int, batch: int, seq: int) -> float:
    """Forward and backward of ``batch`` causal rows of ``seq``."""
    return 3.0 * forward(w, layers, batch * seq, (seq + 1) / 2)
