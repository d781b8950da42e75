"""Operations one chip's share of Nemotron-H requires, from its shapes.

A multiply-add counts as two; nothing recomputed is counted: the
backward pass is twice the forward pass, so a training step is three
forward passes whatever the program rematerialises. Counted, a token:

- every weight it is multiplied with: the Mamba-2 ``in_proj`` and
  ``out_proj``, q/k/v/o, the router, the shared expert, the head;
- a **routed expert for the rows it is expected to get**: a token
  chooses ``num_experts_per_tok`` of the router's experts and this chip
  holds ``n_routed_experts`` of ``router_num_experts``, so with even
  routing a token meets k * held / all experts' worth of weights here
  (6 * 8 / 128 = 0.375 at the published sizes). The run's own share is
  ``moe_held_rows_share.train``;
- the Mamba-2 recurrence as the mathematics states it, whatever form
  computes it: per head the state update and the read-out, 2 * p * n
  multiply-adds (4 * heads * p * n operations a token);
- causal attention's half square, QK^T and PV.

Left out (under one percent): the embedding look-up, norms, the conv's
four taps, softplus, the gates, softmax.
"""

from __future__ import annotations


def matmul_params(w: dict, layers: int) -> float:
    """Weights a token is multiplied with on this chip, even routing."""
    h = w["hidden_size"]
    d_in = w["mamba_num_heads"] * w["mamba_head_dim"]
    gn = w["n_groups"] * w["ssm_state_size"]
    mamba = h * (2 * d_in + 2 * gn + w["mamba_num_heads"]) + d_in * h
    d = w["head_dim"]
    q, kv = w["num_attention_heads"] * d, w["num_key_value_heads"] * d
    attn = h * q + 2 * h * kv + q * h
    routed_share = w["num_experts_per_tok"] * w["n_routed_experts"] \
        / w["router_num_experts"]
    moe = h * w["router_num_experts"] \
        + 2 * h * w["moe_shared_expert_intermediate_size"] \
        + routed_share * 2 * h * w["moe_intermediate_size"]
    pat = w["hybrid_override_pattern"][:layers]
    return pat.count("M") * mamba + pat.count("*") * attn \
        + pat.count("E") * moe + h * w["vocab_size"]


def recurrence_flops(w: dict, layers: int, tokens: int) -> float:
    pat = w["hybrid_override_pattern"][:layers]
    return pat.count("M") * tokens * 4.0 * w["mamba_num_heads"] \
        * w["mamba_head_dim"] * w["ssm_state_size"]


def attention_flops(w: dict, layers: int, q_len: int,
                    ctx_len: float) -> float:
    pat = w["hybrid_override_pattern"][:layers]
    q = w["num_attention_heads"] * w["head_dim"]
    return pat.count("*") * 2 * 2 * q_len * ctx_len * q


def forward(w: dict, layers: int, tokens: int, ctx_len: float) -> float:
    return 2.0 * matmul_params(w, layers) * tokens \
        + recurrence_flops(w, layers, tokens) \
        + attention_flops(w, layers, tokens, ctx_len)


def train_step(w: dict, layers: int, batch: int, seq: int) -> float:
    """Forward and backward of ``batch`` causal rows of ``seq``."""
    return 3.0 * forward(w, layers, batch * seq, (seq + 1) / 2)
