"""The plain reference of Nemotron-H: Mamba-2, sparse-expert and attention
blocks, one chip's share.

From ``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 and the
``nemotron_h`` model code; what the config does not state is listed
under ``assumed`` in ``configs/nemotron-3-nano-30b-a3b-train.json``.
Straight ``jax.numpy`` in float32, every matrix product at precision
``highest``; it imports nothing of the program.

Block ``i`` of kind ``pattern[i]``:  x <- x + mixer_i(RMSNorm(x; w_i)).
After the last block a final RMSNorm and the untied head; the loss is
the mean next-token cross-entropy over the (sliced) vocabulary. The
embedding is a plain look-up.

``M``, Mamba-2.  [z | xBC | dt] = h W_in (d_inner | d_inner + 2 g n |
heads). xBC <- silu(causal depthwise conv1d(xBC, kernel k) + b_conv);
split x (heads x p), B, C (g groups x n; head j uses group
j // (heads / g)). dt <- softplus(dt + dt_bias), A = -exp(A_log), one
scalar a head. Per head, with a state S in [p, n], S_0 = 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t

walked **step by step** (a ``lax.scan`` over time; the program runs the
chunked form, and the two checking each other is the point). Then the
gated norm, gate first: y <- GroupRMSNorm(y * silu(z); g groups, weight
[d_inner]); out = y W_out. The scan is rematerialised in segments of
``SEGMENT`` steps, so that the backward pass holds one segment's states
at a time.

``E``, experts.  s = sigmoid(h W_r) over ALL the router's experts;
choose the top k of s + b_corr (b_corr zero: ``assumed``; no group
limit); g = s[chosen], g <- g / (sum g + 1e-20), g <- scale * g. Expert
e: f_e(h) = relu(h W1_e)^2 W2_e. Out = sum over the experts that are
chosen AND held of g_e f_e(h), plus the shared expert (the same form,
every token). The held experts are ``held_experts_first ..`` of the
router's numbering: a loop over them with a mask, no sort, no grouped
product. What the absent experts would add is left out here as in the
program.

``*``, attention.  q, k, v, o without bias, causal
softmax(q k^T / sqrt(d)) v, grouped queries, **no positional term**
(``assumed``), one query head at a time so that the scores fit.

``mm`` is the one seam every weight product goes through; the control
is this file with ``int8_mm`` in its place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
SEGMENT = 128  # steps of the recurrence between saved states


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
    """This architecture's attention has no positional term."""
    return None, None


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ------------------------------------------------------------- Mamba-2
def _recurrence(x, dt, A, B, C):
    """x [b, s, h, p]; dt [b, s, h]; A [h]; B, C [b, s, h, n] (already
    one per head). Returns S_t C_t for every t, the state walked one
    step at a time."""
    b, s, h, p = x.shape
    n = B.shape[-1]

    def step(S, args):
        xt, dtt, Bt, Ct = args
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :]
        return S, jnp.sum(S * Ct[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(S, args):
        return jax.lax.scan(step, S, args)

    pad = -s % SEGMENT  # steps of dt = 0 and x = 0 leave the state alone
    seq = [jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
           for v in (x, dt, B, C)]
    seq = tuple(v.swapaxes(0, 1).reshape(
        (s + pad) // SEGMENT, SEGMENT, *v.shape[:1], *v.shape[2:])
        for v in seq)
    _, ys = jax.lax.scan(segment, jnp.zeros((b, h, p, n), jnp.float32),
                         seq)
    return ys.reshape(s + pad, b, h, p)[:s].swapaxes(0, 1)


def mamba2_mixer(h, lp: dict, w: dict, mm=f32_mm):
    b, s, _ = h.shape
    nh, p = w["mamba_num_heads"], w["mamba_head_dim"]
    g, n = w["n_groups"], w["ssm_state_size"]
    d_in = nh * p
    z, xBC, dt = jnp.split(mm(h, lp["mixer.in_proj.weight"]),
                           [d_in, 2 * d_in + 2 * g * n], axis=-1)
    taps = lp["mixer.conv_weight"]  # [channels, k]; tap k-1 is "now"
    k = taps.shape[1]
    padded = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    xBC = sum(padded[:, i:i + s] * taps[:, i] for i in range(k))
    xBC = jax.nn.silu(xBC + lp["mixer.conv_bias"])
    x, B, C = jnp.split(xBC, [d_in, d_in + g * n], axis=-1)
    x = x.reshape(b, s, nh, p)
    B = jnp.repeat(B.reshape(b, s, g, n), nh // g, axis=2)
    C = jnp.repeat(C.reshape(b, s, g, n), nh // g, axis=2)
    dt = jax.nn.softplus(dt + lp["mixer.dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(lp["mixer.A_log"]), B, C) \
        + lp["mixer.D"][:, None] * x
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, d_in // g)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, -1, keepdims=True) + w["layer_norm_epsilon"])
    return mm(yg.reshape(b, s, d_in) * lp["mixer.norm_weight"],
              lp["mixer.out_proj.weight"])


# ------------------------------------------------------------- experts
def route(h, lp: dict, w: dict, mm=f32_mm):
    """(chosen experts [.., k], their weights [.., k]) over all the
    router's experts."""
    s = jax.nn.sigmoid(mm(h, lp["mixer.gate_weight"]))
    b_corr = lp.get("mixer.e_score_correction_bias", 0.0)
    _, idx = jax.lax.top_k(s + b_corr, w["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if w["norm_topk_prob"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx, g * w["routed_scaling_factor"]


def _expert(h, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(h, w1))), w2)


def moe_mixer(h, lp: dict, w: dict, mm=f32_mm):
    idx, g = route(h, lp, w, mm)
    first = w["held_experts_first"]
    out = _expert(h, lp["mixer.shared_experts.w1"][0],
                  lp["mixer.shared_experts.w2"][0], mm)
    for e in range(lp["mixer.experts.w1"].shape[0]):
        weight = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        out = out + weight[..., None] * _expert(
            h, lp["mixer.experts.w1"][e], lp["mixer.experts.w2"][e], mm)
    return out


# ----------------------------------------------------------- attention
def attention_mixer(h, lp: dict, w: dict, mm=f32_mm):
    b, s, _ = h.shape
    d, kvh = w["head_dim"], w["num_key_value_heads"]
    hq = w["num_attention_heads"]
    q = mm(h, lp["mixer.q_proj.weight"]).reshape(b, s, hq, d)
    k = mm(h, lp["mixer.k_proj.weight"]).reshape(b, s, kvh, d)
    v = mm(h, lp["mixer.v_proj.weight"]).reshape(b, s, kvh, d)
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
              lp["mixer.o_proj.weight"])


MIXERS = {"M": mamba2_mixer, "E": moe_mixer, "*": attention_mixer}


def decoder_layer(x, lp: dict, widths: dict, kind: str, mm=f32_mm):
    """One block of ``kind`` on x [b, s, hidden]; ``lp`` holds float32
    leaves under their short names (``weights/nemotron_h.py``)."""
    h = _rms(x, lp["norm.weight"], widths["layer_norm_epsilon"])
    return x + MIXERS[kind](h, lp, widths, mm)


def head_logits(x, top: dict, widths: dict, mm=f32_mm):
    return mm(_rms(x, top["backbone.norm_f.weight"],
                   widths["layer_norm_epsilon"]), top["lm_head.weight"])


def split_params(params: dict, layers: int):
    """The program's flat names -> (top, [block dicts])."""
    top = {n: v for n, v in params.items() if ".layers." not in n}
    per = []
    for i in range(layers):
        pre = f"backbone.layers.{i}."
        per.append({n[len(pre):]: v for n, v in params.items()
                    if n.startswith(pre)})
    return top, per


def lm_loss(params: dict, ids, widths: dict, layers: int, mm=f32_mm):
    """Mean next-token cross-entropy of ids [b, s] (the labels are the
    ids, shifted by one); each block is rematerialised in the backward
    pass so that one block's activations live at a time."""
    top, per = split_params(params, layers)
    x = top["backbone.embeddings.weight"][ids]
    for lp, kind in zip(per, widths["hybrid_override_pattern"]):
        x = jax.checkpoint(functools.partial(
            decoder_layer, widths=widths, kind=kind, mm=mm))(x, lp)
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
