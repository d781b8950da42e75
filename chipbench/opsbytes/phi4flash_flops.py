"""Operations a cut of Phi-4-mini-flash requires, from its shapes.

A multiply-add counts as two; nothing recomputed is counted: the
backward pass is twice the forward pass, so a training step is three
forward passes whatever the program rematerialises. Counted, a token:

- every weight it is multiplied with, by the kind of each held layer
  (its published index decides): Mamba-1 ``in_proj``, ``x_proj``,
  ``dt_proj``, ``out_proj``; attention ``Wqkv`` and ``out_proj``; a
  gated memory unit's two matrices; cross-attention's ``Wq`` and
  ``out_proj``; every layer's ``fc1`` and ``fc2``; the head (the tied
  embedding as a matrix; the look-up is not a product);
- the Mamba-1 recurrence as the mathematics states it, whatever form
  computes it: per channel and state column the state's update and the
  read-out, 2 * d_inner * n multiply-adds a token a Mamba layer;
- differential attention over what the mask lets through (the causal
  half square, or the window's band where the layer has one): two maps a
  query pair, each a score product at head size d and a value product at
  2 d.

Left out (under one percent): the embedding look-up, norms, the conv's
four taps, softplus, the gates, softmax, the lambdas.
"""

from __future__ import annotations

from chipbench.reference.phi4flash import layer_kind


def held(w: dict, layers: int) -> list:
    return list(w["published_layer_indices"])[:layers]


def matmul_params(w: dict, layers: int) -> float:
    """Weights a token is multiplied with."""
    h, f = w["hidden_size"], w["intermediate_size"]
    d_in = w["mamba_expand"] * h
    q = w["num_attention_heads"] * w["head_dim"]
    kv = w["num_key_value_heads"] * w["head_dim"]
    r, n = w["mamba_dt_rank"], w["mamba_d_state"]
    mixer = {
        "mamba": h * 2 * d_in + d_in * (r + 2 * n) + r * d_in + d_in * h,
        "attention": h * (q + 2 * kv) + q * h,
        "gmu": h * d_in + d_in * h,
        "cross": h * q + q * h}
    mlp = h * 2 * f + f * h
    return sum(mixer[layer_kind(l, w)] + mlp for l in held(w, layers)) \
        + h * w["vocab_size"]


def recurrence_flops(w: dict, layers: int, tokens: int) -> float:
    mamba = sum(layer_kind(l, w) == "mamba" for l in held(w, layers))
    return mamba * tokens * 4.0 * w["mamba_expand"] * w["hidden_size"] \
        * w["mamba_d_state"]


def visible_pairs(seq: int, window: int) -> float:
    """(query, key) pairs a causal mask lets through in one row of
    ``seq`` tokens; with ``window`` > 0 a query sees its own key and the
    ``window - 1`` before it."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def attention_flops(w: dict, layers: int, batch: int, seq: int) -> float:
    d = w["head_dim"]
    half = w["published_num_hidden_layers"] // 2
    per_pair = 2 * (2 * d + 2 * 2 * d)  # two maps: scores at d, values 2 d
    total = 0.0
    for l in held(w, layers):
        kind = layer_kind(l, w)
        if kind in ("attention", "cross"):
            window = w["sliding_window"] if l < half else 0
            total += batch * visible_pairs(seq, window) \
                * (w["num_attention_heads"] // 2) * per_pair
    return total


def forward(w: dict, layers: int, batch: int, seq: int) -> float:
    """One causal forward pass over ``batch`` rows of ``seq`` tokens."""
    tokens = batch * seq
    return 2.0 * matmul_params(w, layers) * tokens \
        + recurrence_flops(w, layers, tokens) \
        + attention_flops(w, layers, batch, seq)


def train_step(w: dict, layers: int, batch: int, seq: int) -> float:
    """Forward and backward of ``batch`` causal rows of ``seq``."""
    return 3.0 * forward(w, layers, batch, seq)
