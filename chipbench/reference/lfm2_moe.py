"""The plain reference of LFM2-MoE: gated short convolutions, q/k-normed
grouped-query attention, dense and sparse SwiGLU feed-forwards, one
chip's share of the experts.

From ``config.json`` of LiquidAI/LFM2-8B-A1B and the ``lfm2_moe`` model
code; what the config does not state is listed under ``assumed`` in
``configs/lfm2-8b-a1b-train.json``. Straight ``jax.numpy`` in float32,
every matrix product at precision ``highest``; it imports nothing of the
program. No layer has a recurrence, so nothing is walked step by step.

With ``rms(x; g) = x * rsqrt(mean(x^2) + norm_eps) * g``, layer ``i``:

    h <- h + operator_i(rms(h; g_op));  h <- h + ffn_i(rms(h; g_ffn))

``conv`` operator.  B, C, x = split3(u W_in) (in that order);
z_t = sum_{j < L} w[:, j] * (B * x)_{t - L + 1 + j}, zeros before the
sequence, no bias, no activation; out = (C * z) W_out.

``full_attention`` operator.  q, k, v without bias; rms over the
head_dim of EACH q and k head (one weight of head_dim each), THEN
rotate-half RoPE over the whole head (``rope_theta``); causal
softmax(q k^T / sqrt(d)) v, KV heads shared by groups of queries, one
query head at a time so that the scores fit; ``out_proj``.

Dense ffn (``i < num_dense_layers``) and every expert:
f(x) = (silu(x W_a) * (x W_b)) W_2, where the program's leaves name
``W_a`` ``w1`` and ``W_b`` ``w3`` in the dense MLP (the published
names) and ``w3`` and ``w1`` in the experts (``ExpertFFN``'s).

Sparse ffn.  s = sigmoid(h W_g) over ALL the router's experts; choose
the top k of s + expert_bias (zero: ``assumed``); g = s[chosen] without
the bias, g <- g / (sum g + 1e-6), g <- routed_scaling_factor * g.
Out = sum over the experts that are chosen AND held of g_e f_e(h); no
shared expert. The held experts are ``held_experts_first ..`` of the
router's numbering: a loop over them with a mask, no sort, no grouped
product. What the absent experts would add is left out here as in the
program.

After the last layer rms (``embedding_norm``) and the logits with the
embedding's own matrix; the loss is the mean next-token cross-entropy
over the (sliced) vocabulary.

``mm`` is the one seam every weight product goes through; the control
is this file with ``int8_mm`` in its place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ROUTER_EPS = 1e-6


def f32_mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _fake_int8(x, axis):
    """Symmetric int8, one scale along ``axis``; the gradient passes
    straight through, as quantised training takes it."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def int8_mm(x, w):
    """W8A8: a scale per token and per output channel."""
    return jnp.matmul(_fake_int8(x, -1), _fake_int8(w, 0), precision=HI)


def rope_tables(head_dim: int, length: int, theta: float):
    """cos, sin [length, head_dim / 2]."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    ang = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    return jnp.cos(ang), jnp.sin(ang)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(t, cos, sin):
    """Rotate-half over the whole head; t [b, s, heads, d]."""
    t1, t2 = jnp.split(t, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)


# ------------------------------------------------------- the operators
def short_conv(u, lp: dict, w: dict, mm=f32_mm):
    s = u.shape[1]
    B, C, x = jnp.split(mm(u, lp["conv.in_proj.weight"]), 3, axis=-1)
    taps = lp["conv.conv_weight"]  # [channels, L]; tap L-1 is "now"
    k = taps.shape[1]
    padded = jnp.pad(B * x, ((0, 0), (k - 1, 0), (0, 0)))
    z = sum(padded[:, i:i + s] * taps[:, i] for i in range(k))
    return mm(C * z, lp["conv.out_proj.weight"])


def attention(h, lp: dict, w: dict, mm=f32_mm):
    b, s, _ = h.shape
    d, hq, kvh = w["head_dim"], w["num_attention_heads"], \
        w["num_key_value_heads"]
    q = mm(h, lp["self_attn.q_proj.weight"]).reshape(b, s, hq, d)
    k = mm(h, lp["self_attn.k_proj.weight"]).reshape(b, s, kvh, d)
    v = mm(h, lp["self_attn.v_proj.weight"]).reshape(b, s, kvh, d)
    cos, sin = rope_tables(d, s, w["rope_theta"])
    q = _rope(_rms(q, lp["self_attn.q_layernorm.weight"], w["norm_eps"]),
              cos, sin)
    k = _rope(_rms(k, lp["self_attn.k_layernorm.weight"], w["norm_eps"]),
              cos, sin)
    k = jnp.repeat(k, hq // kvh, axis=2)
    v = jnp.repeat(v, hq // kvh, axis=2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args  # [s, d] each
        sc = jnp.matmul(qi, ki.T, precision=HI) / (d ** 0.5)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.matmul(p, vi, precision=HI)

    heads = tuple(t.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
                  for t in (q, k, v))
    a = jax.lax.map(one, heads).reshape(b, hq, s, d)
    return mm(a.transpose(0, 2, 1, 3).reshape(b, s, hq * d),
              lp["self_attn.out_proj.weight"])


# --------------------------------------------------- the feed-forwards
def _swiglu(h, w_silu, w_lin, w_out, mm):
    return mm(jax.nn.silu(mm(h, w_silu)) * mm(h, w_lin), w_out)


def dense_mlp(h, lp: dict, w: dict, mm=f32_mm):
    return _swiglu(h, lp["feed_forward.w1.weight"],
                   lp["feed_forward.w3.weight"],
                   lp["feed_forward.w2.weight"], mm)


def route(h, lp: dict, w: dict, mm=f32_mm):
    """(chosen experts [.., k], their weights [.., k]) over all the
    router's experts."""
    s = jax.nn.sigmoid(mm(h, lp["feed_forward.gate_weight"]))
    bias = lp.get("feed_forward.e_score_correction_bias", 0.0)
    _, idx = jax.lax.top_k(s + bias, w["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, -1, keepdims=True) + ROUTER_EPS)
    return idx, g * w["routed_scaling_factor"]


def sparse_mlp(h, lp: dict, w: dict, mm=f32_mm):
    idx, g = route(h, lp, w, mm)
    first = w["held_experts_first"]
    out = jnp.zeros_like(h)
    for e in range(lp["feed_forward.experts.w1"].shape[0]):
        weight = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        out = out + weight[..., None] * _swiglu(
            h, lp["feed_forward.experts.w3"][e],
            lp["feed_forward.experts.w1"][e],
            lp["feed_forward.experts.w2"][e], mm)
    return out


def decoder_layer(x, lp: dict, widths: dict, i: int, mm=f32_mm):
    """Layer ``i`` on x [b, s, hidden]; ``lp`` holds float32 leaves under
    their short names (``weights/lfm2_moe.py``)."""
    w = widths
    op = attention if w["layer_types"][i] == "full_attention" \
        else short_conv
    ffn = dense_mlp if i < w["num_dense_layers"] else sparse_mlp
    x = x + op(_rms(x, lp["operator_norm.weight"], w["norm_eps"]),
               lp, w, mm)
    return x + ffn(_rms(x, lp["ffn_norm.weight"], w["norm_eps"]), lp, w, mm)


def head_logits(x, top: dict, widths: dict, mm=f32_mm):
    """Tied: the embedding's own matrix, transposed."""
    return mm(_rms(x, top["model.embedding_norm.weight"],
                   widths["norm_eps"]), top["model.embed_tokens.weight"].T)


def split_params(params: dict, layers: int):
    """The program's flat names -> (top, [layer dicts])."""
    top = {n: v for n, v in params.items() if ".layers." not in n}
    per = []
    for i in range(layers):
        pre = f"model.layers.{i}."
        per.append({n[len(pre):]: v for n, v in params.items()
                    if n.startswith(pre)})
    return top, per


def lm_loss(params: dict, ids, widths: dict, layers: int, mm=f32_mm):
    """Mean next-token cross-entropy of ids [b, s] (the labels are the
    ids, shifted by one); each layer is rematerialised in the backward
    pass so that one layer's activations live at a time."""
    top, per = split_params(params, layers)
    x = top["model.embed_tokens.weight"][ids]
    for i, lp in enumerate(per):
        x = jax.checkpoint(functools.partial(
            decoder_layer, widths=widths, i=i, mm=mm))(x, lp)
    logits = head_logits(x, top, widths, mm)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


# --------------------------------------------------------------- AdamW
def clip_scale(sq_norms: dict, clip: float):
    total = jnp.sqrt(sum(sq_norms.values()))
    return jnp.minimum(1.0, clip / jnp.maximum(total, 1e-12))


def adamw_from_history(p, hist, step: int, hp: dict):
    """Decoupled AdamW's ``step``-th update of one float32 leaf, the
    moments rebuilt from the clipped gradients so far (oldest first):
    m_k = sum_j (1-b1) b1^(k-j) g_j, likewise v_k, both bias-corrected."""
    b1, b2 = hp["beta1"], hp["beta2"]
    k = len(hist)
    m = sum((1 - b1) * b1 ** (k - 1 - j) * g for j, g in enumerate(hist))
    v = sum((1 - b2) * b2 ** (k - 1 - j) * g * g
            for j, g in enumerate(hist))
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    upd = mhat / (jnp.sqrt(vhat) + hp["epsilon"]) + hp["weight_decay"] * p
    return p - hp["learning_rate"] * upd
