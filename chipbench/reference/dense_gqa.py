"""The plain reference of a dense decoder with grouped-query attention.

Mistral-7B as published (``configs/*.json``): token embedding, then per
layer RMSNorm -> q/k/v projections -> rotary embedding (half-rotation)
-> causal grouped-query attention -> output projection -> residual ->
RMSNorm -> SwiGLU MLP -> residual; final RMSNorm, untied head; the loss
is the mean next-token cross-entropy. Straight ``jax.numpy`` in float32
with every matrix product at precision ``highest``: no kernel, no cache,
no batching tricks. It imports nothing of the program. Attention runs
one (row, KV head) at a time so that the scores fit; a caller that has
no room for all the weights hands the layers in one at a time.

``mm`` is the one seam: every weight product goes through it, so the
control of "How correct is decided" (the same mathematics one precision
below bf16) is this file with ``int8_mm`` in its place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


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
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    freqs = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def _rope(x, cos, sin):
    """x [b, s, heads, d]; positions are 0..s-1."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal GQA. q [b, s, kvh, g, d]; k, v [b, s, kvh, d]."""
    b, s, kvh, g, d = q.shape
    qf = q.transpose(0, 2, 3, 1, 4).reshape(b * kvh, g, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one(args):
        qi, ki, vi = args
        sc = jnp.einsum("gsd,td->gst", qi, ki, precision=HI) / (d ** 0.5)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->gsd", p, vi, precision=HI)

    out = jax.lax.map(one, (qf, kf, vf))  # [b*kvh, g, s, d]
    return out.reshape(b, kvh, g, s, d).transpose(0, 3, 1, 2, 4).reshape(
        b, s, kvh * g * d)


def decoder_layer(x, lp: dict, widths: dict, cos, sin, mm=f32_mm):
    """One layer on x [b, s, hidden]; ``lp`` holds float32 leaves under
    their short names (``weights/dense_gqa.py``, ``LAYER_LEAVES``)."""
    b, s, _ = x.shape
    d, kvh = widths["head_dim"], widths["num_key_value_heads"]
    g = widths["num_attention_heads"] // kvh
    eps = widths["rms_norm_eps"]
    h = _rms(x, lp["input_layernorm.weight"], eps)
    q = mm(h, lp["self_attn.q_proj.weight"]).reshape(b, s, kvh * g, d)
    k = mm(h, lp["self_attn.k_proj.weight"]).reshape(b, s, kvh, d)
    v = mm(h, lp["self_attn.v_proj.weight"]).reshape(b, s, kvh, d)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    a = _attention(q.reshape(b, s, kvh, g, d), k, v)
    x = x + mm(a, lp["self_attn.o_proj.weight"])
    h = _rms(x, lp["post_attention_layernorm.weight"], eps)
    gate = mm(h, lp["mlp.gate_proj.weight"])
    up = mm(h, lp["mlp.up_proj.weight"])
    return x + mm(jax.nn.silu(gate) * up, lp["mlp.down_proj.weight"])


def head_logits(x, top: dict, widths: dict, mm=f32_mm):
    return mm(_rms(x, top["model.norm.weight"], widths["rms_norm_eps"]),
              top["lm_head.weight"])


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
    """Mean next-token cross-entropy of ids [b, s] (labels are the ids
    themselves, shifted by one), each layer rematerialised in the
    backward pass so that the activations of one layer live at a time."""
    top, per = split_params(params, layers)
    cos, sin = rope_tables(widths["head_dim"], ids.shape[1],
                           widths["rope_theta"])
    x = top["model.embed_tokens.weight"][ids]
    layer = jax.checkpoint(
        functools.partial(decoder_layer, widths=widths, mm=mm))
    for lp in per:
        x = layer(x, lp, cos=cos, sin=sin)
    logits = head_logits(x, top, widths, mm)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# --------------------------------------------------------------- AdamW
def clip_scale(sq_norms: dict, clip: float):
    total = jnp.sqrt(sum(sq_norms.values()))
    return jnp.minimum(1.0, clip / jnp.maximum(total, 1e-12))


def adamw_from_history(p, hist, step: int, hp: dict):
    """Decoupled AdamW's ``step``-th update of one float32 leaf, the
    moments rebuilt from the clipped gradients so far (oldest first):
    m_k = sum_j (1-b1) b1^(k-j) g_j, likewise v_k, both bias-corrected.
    Holding the gradients, not the moments, lets the caller keep the
    earlier ones on the host."""
    b1, b2 = hp["beta1"], hp["beta2"]
    k = len(hist)
    m = sum((1 - b1) * b1 ** (k - 1 - j) * g for j, g in enumerate(hist))
    v = sum((1 - b2) * b2 ** (k - 1 - j) * g * g
            for j, g in enumerate(hist))
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    upd = mhat / (jnp.sqrt(vhat) + hp["epsilon"]) + hp["weight_decay"] * p
    return p - hp["learning_rate"] * upd
