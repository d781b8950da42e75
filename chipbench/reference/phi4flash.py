"""The plain reference of Phi-4-mini-flash: Mamba-1 scans, differential
window/full attention, and a cross-decoder of gated memory units and
shared-KV attention.

From ``config.json`` of microsoft/Phi-4-mini-flash-reasoning and the
published model code ``modeling_phi4flash.py``; what the config does
not state is listed under ``assumed`` in
``configs/phi-4-mini-flash-reasoning-train.json``. Straight
``jax.numpy`` in float32, every matrix product at precision ``highest``;
it imports nothing of the program.

Every layer, with LN = LayerNorm (weight and bias, eps
``layer_norm_eps``):

    x <- x + mixer_l(LN(x));   x <- x + MLP(LN(x))
    MLP:  g, u = split(x W1);  (u * silu(g)) W2            (no bias)

No positional term, no embedding scale; after the last layer a final LN
and the head ``h E^T`` with the embedding's own ``E``; the loss is the
mean next-token cross-entropy over the (sliced) vocabulary.

The mixer goes by the layer's PUBLISHED index ``l`` of
``published_num_hidden_layers`` (32; ``half`` = 16):

``l`` even, ``l <= half``: **Mamba-1**.  x, z = split(h W_in);
x <- silu(causal depthwise conv1d(x, kernel 4) + b);
dt, B, C = split(x W_x) (dt_rank, n, n); delta = softplus(dt W_dt +
b_dt); A = -exp(A_log), one decay a channel and state column. With a
state S in [d_inner, n], S_0 = 0:

    S_t = exp(delta_t A) * S_{t-1} + (delta_t x_t) B_t^T
    y_t = S_t C_t + D * x_t

walked **step by step** (a ``lax.scan`` over time; the program runs a
chunked kernel, and the two checking each other is the point), in
segments of ``SEGMENT`` steps that the backward pass makes again one at
a time. out = (y * silu(z)) W_out. Layer ``half`` hands ``y``, before
the gate, on as the memory ``m``.

``l`` odd, ``l <= half + 1``: **differential attention**, causal, a
window of ``sliding_window`` keys (the query's own included) for
``l < half``, full at ``half + 1``. q, k, v = split(h Wqkv + b) as
heads of d; consecutive heads pair: q1, q2 = q[2i], q[2i+1], k and v
likewise, and a query pair reads the KV pair ``i // (query pairs / KV
pairs)``. With p_j = softmax(q_j k_j^T / sqrt(d)) under the mask, **the
four products of the published form**: a1 = [p1 v1 | p1 v2],
a2 = [p2 v1 | p2 v2] (each softmax made once, where the published code
makes it for each of its four flash calls; the program makes two calls
with V = [v1 | v2]). lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init(l), lambda_init(l) = 0.8 - 0.6 exp(-0.3 l);
o = RMSNorm_2d(a1 - lambda a2; learned scale) * (1 - lambda_init(l));
out = concat(o) W_o + b. Rows are worked in blocks of ``ROWS`` so that
the scores fit. Layer ``half + 1`` hands its k and v on.

``l`` even, ``l > half + 1``: **gated memory unit**:
out = (m * silu(h W1)) W2.

``l`` odd, ``l > half + 1``: **cross-attention**: q = h Wq + b only;
k, v are layer ``half + 1``'s; the same differential form, causal,
full, with the layer's own lambdas, sub-norm and lambda_init(l).

``mm`` is the one seam every weight product goes through; the control
is this file with ``int8_mm`` in its place.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
SEGMENT = 128  # steps of the recurrence between saved states
ROWS = 1024    # query rows whose scores are live at once


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
    raise NotImplementedError(
        "phi4flash has no positional term: neither rotary tables nor any "
        "other (the serve kind, which asks for them, has no cell of this "
        "architecture)")


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def layer_kind(l: int, widths: dict) -> str:
    cross_decoder = l >= widths["published_num_hidden_layers"] // 2 + 2
    if l % widths["mb_per_layer"] == 0:
        return "gmu" if cross_decoder else "mamba"
    return "cross" if cross_decoder else "attention"


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


# ------------------------------------------------------------- Mamba-1
def _recurrence(x, delta, A, B, C):
    """x, delta [b, s, d]; A [d, n]; B, C [b, s, n]. Returns S_t C_t for
    every t, the state walked one step at a time."""
    b, s, d = x.shape
    n = A.shape[1]

    def step(S, args):
        xt, dt, Bt, Ct = args
        S = jnp.exp(dt[..., None] * A) * S \
            + (dt * xt)[..., None] * Bt[:, None, :]
        return S, jnp.sum(S * Ct[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(S, args):
        return jax.lax.scan(step, S, args)

    pad = -s % SEGMENT  # steps of delta = 0 and x = 0 leave the state
    seq = tuple(jnp.pad(v, ((0, 0), (0, pad), (0, 0))).swapaxes(0, 1)
                .reshape((s + pad) // SEGMENT, SEGMENT, b, v.shape[-1])
                for v in (x, delta, B, C))
    _, ys = jax.lax.scan(segment, jnp.zeros((b, d, n), jnp.float32), seq)
    return ys.reshape(s + pad, b, d)[:s].swapaxes(0, 1)


def mamba1_mixer(h, lp: dict, w: dict, mm=f32_mm):
    """(out, y): ``y`` is the scan's output before the gate."""
    s = h.shape[1]
    n, r = w["mamba_d_state"], w["mamba_dt_rank"]
    x, z = jnp.split(mm(h, lp["mixer.in_proj.weight"]), 2, axis=-1)
    taps = lp["mixer.conv_weight"]  # [channels, k]; tap k-1 is "now"
    k = taps.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = sum(padded[:, i:i + s] * taps[:, i] for i in range(k))
    x = jax.nn.silu(x + lp["mixer.conv_bias"])
    dt, B, C = jnp.split(mm(x, lp["mixer.x_proj.weight"]), [r, r + n],
                         axis=-1)
    delta = jax.nn.softplus(mm(dt, lp["mixer.dt_proj.weight"])
                            + lp["mixer.dt_proj.bias"])
    y = _recurrence(x, delta, -jnp.exp(lp["mixer.A_log"]), B, C) \
        + lp["mixer.D"] * x
    return mm(y * jax.nn.silu(z), lp["mixer.out_proj.weight"]), y


# ----------------------------------------------------------- attention
def _diff_maps(q, k, v, window: int):
    """q [b, s, hq, d], k and v [b, s, hk, d] -> (a1, a2), each
    [b, s, hq / 2, 2 d]: the two softmax maps of every query pair over
    the pair's two value heads, side by side."""
    b, s, hq, d = q.shape
    hk = k.shape[2]
    pq, pk = hq // 2, hk // 2
    rows = min(ROWS, s)
    if s % rows:
        raise ValueError(f"sequence {s} is no multiple of {rows}")

    def halves(t, pairs):  # -> two of [b * pairs, s, d]
        t = t.reshape(b, s, pairs, 2, d).transpose(3, 0, 2, 1, 4)
        return t[0].reshape(b * pairs, s, d), t[1].reshape(b * pairs, s, d)

    q1, q2 = halves(q, pq)
    k1, k2 = halves(k, pk)
    v1, v2 = halves(v, pk)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        pair, r0 = i // (s // rows), (i % (s // rows)) * rows
        kv_pair = (pair // pq) * pk + (pair % pq) // (pq // pk)
        q_pos = r0 + jnp.arange(rows)[:, None]
        keep = key_pos[None, :] <= q_pos
        if window:
            keep = jnp.logical_and(keep, q_pos - key_pos[None, :] < window)

        def soft(qh, kh):
            qb = jax.lax.dynamic_slice_in_dim(qh[pair], r0, rows, 0)
            sc = jnp.matmul(qb, kh[kv_pair].T, precision=HI) / (d ** 0.5)
            return jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)

        def over_values(p):  # [p v1 | p v2]
            return jnp.concatenate(
                [jnp.matmul(p, v1[kv_pair], precision=HI),
                 jnp.matmul(p, v2[kv_pair], precision=HI)], axis=-1)

        return over_values(soft(q1, k1)), over_values(soft(q2, k2))

    a1, a2 = jax.lax.map(block, jnp.arange(b * pq * (s // rows)))

    def unfold(a):  # [b * pq * blocks, rows, 2 d] -> [b, s, pq, 2 d]
        return a.reshape(b, pq, s, 2 * d).transpose(0, 2, 1, 3)

    return unfold(a1), unfold(a2)


def diff_attention_mixer(h, lp: dict, w: dict, l: int, kv=None,
                         mm=f32_mm):
    """(out, (k, v)); with ``kv`` given (a cross layer) only queries are
    projected."""
    b, s, _ = h.shape
    d, hq = w["head_dim"], w["num_attention_heads"]
    hk = w["num_key_value_heads"]
    half = w["published_num_hidden_layers"] // 2
    if kv is None:
        q, k, v = jnp.split(
            mm(h, lp["mixer.Wqkv.weight"]) + lp["mixer.Wqkv.bias"],
            [hq * d, (hq + hk) * d], axis=-1)
        kv = (k.reshape(b, s, hk, d), v.reshape(b, s, hk, d))
    else:
        q = mm(h, lp["mixer.Wq.weight"]) + lp["mixer.Wq.bias"]
    window = w["sliding_window"] if l < half else 0
    a1, a2 = _diff_maps(q.reshape(b, s, hq, d), *kv, window)
    lam = jnp.exp(jnp.sum(lp["mixer.lambda_q1"] * lp["mixer.lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["mixer.lambda_q2"] * lp["mixer.lambda_k2"])) \
        + lambda_init(l)
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + w["layer_norm_eps"]) * lp["mixer.subln.weight"]
    o = (o * (1.0 - lambda_init(l))).reshape(b, s, hq * d)
    return mm(o, lp["mixer.out_proj.weight"]) + lp["mixer.out_proj.bias"], kv


# ------------------------------------------------- gated memory, MLP
def gmu_mixer(h, lp: dict, memory, mm=f32_mm):
    return mm(memory * jax.nn.silu(mm(h, lp["mixer.in_proj.weight"])),
              lp["mixer.out_proj.weight"])


def mlp(h, lp: dict, mm=f32_mm):
    g, u = jnp.split(mm(h, lp["mlp.fc1.weight"]), 2, axis=-1)
    return mm(u * jax.nn.silu(g), lp["mlp.fc2.weight"])


def decoder_layer(x, lp: dict, widths: dict, l: int, memory=None, kv=None,
                  mm=f32_mm):
    """Published layer ``l`` on x [b, s, hidden]; ``lp`` holds float32
    leaves under their short names (``weights/phi4flash.py``). Returns
    (x, memory, kv), the last two replaced where this layer makes
    them."""
    eps = widths["layer_norm_eps"]
    half = widths["published_num_hidden_layers"] // 2
    kind = layer_kind(l, widths)
    h = _ln(x, lp["input_layernorm.weight"], lp["input_layernorm.bias"],
            eps)
    if kind == "mamba":
        out, y = mamba1_mixer(h, lp, widths, mm)
        if l == half:
            memory = y
    elif kind == "gmu":
        out = gmu_mixer(h, lp, memory, mm)
    elif kind == "attention":
        out, new_kv = diff_attention_mixer(h, lp, widths, l, None, mm)
        if l == half + 1:
            kv = new_kv
    else:
        out, _ = diff_attention_mixer(h, lp, widths, l, kv, mm)
    x = x + out
    h = _ln(x, lp["post_attention_layernorm.weight"],
            lp["post_attention_layernorm.bias"], eps)
    return x + mlp(h, lp, mm), memory, kv


def head_logits(x, top: dict, widths: dict, mm=f32_mm):
    """Final LN, then the tied head: the embedding's own matrix."""
    h = _ln(x, top["model.final_layernorm.weight"],
            top["model.final_layernorm.bias"], widths["layer_norm_eps"])
    return mm(h, top["model.embed_tokens.weight"].T)


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
    memory = kv = None
    for lp, l in zip(per, widths["published_layer_indices"]):
        x, memory, kv = jax.checkpoint(functools.partial(
            decoder_layer, widths=widths, l=l, mm=mm))(
                x, lp, memory=memory, kv=kv)
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
