"""Fused-op functional surface (parity: python/paddle/incubate/nn/
functional/ — fused_rms_norm, fused_layer_norm, fused_rotary_position_
embedding, swiglu, fused_multi_head_attention, fused_linear,
fused_bias_act, fused_dropout_add; reference kernels in
paddle/phi/kernels/fusion/).

TPU-native note: "fused" is a calling convention here, not a promise of a
hand-written kernel — XLA fuses these compositions on its own, and the
genuinely hot ones (attention, rope at long seq) dispatch to the Pallas
kernels. The surface exists so PaddleNLP-style model code ports without
rewrites.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...kernels.rope import apply_rope, rope_frequencies
from ...nn import functional as F


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kw):
    y = F.rms_norm(x, norm_weight, epsilon)
    if norm_bias is not None:
        y = y + norm_bias
    return y


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, **kw):
    return F.layer_norm(x, weight=norm_weight, bias=norm_bias,
                        epsilon=epsilon)


def swiglu(x, y=None):
    return F.swiglu(x, y)


def fused_linear(x, weight, bias=None, transpose_weight=False):
    w = weight.T if transpose_weight else weight
    return F.linear(x, w, bias)


def fused_linear_cross_entropy(x, weight, labels, bias=None,
                               transpose_weight=False,
                               ignore_index=-100, seq_chunk=256):
    """Vocab-head projection + softmax cross-entropy without ever
    materializing the full ``[..., seq, vocab]`` logits tensor.

    Math-equivalent to ``F.cross_entropy(F.linear(x, w), labels)`` with
    mean reduction over non-ignored tokens — softmax is row-wise, so
    chunking the sequence axis is exact. Logits exist one seq-chunk at a
    time (``[..., seq_chunk, vocab]`` in the product's dtype, reduced by
    ``F.softmax_cross_entropy_rows``); the chunk body is
    ``jax.checkpoint``'ed so backward recomputes each chunk's logits and
    accumulates the weight cotangent inside the scan. For a causal-LM
    train step the full-logits pair (f32 log-softmax + bf16 matmul
    output) is the single largest activation — 2.2 GB at 6x2047x32k —
    and this drops peak memory to one chunk regardless of sequence
    length, buying batch (and thus MFU) headroom.

    Parity: the reference's fused softmax-with-cross-entropy CUDA path
    (paddle/phi/kernels/fusion/ + ParallelCrossEntropy family); here the
    fusion is a remat'd scan XLA pipelines.

    x: [..., S, H]; labels: [..., S] int; weight [H, V] (paddle linear
    layout; pass transpose_weight=True for a [V, H] tied-embedding
    matrix). seq_chunk: positions per chunk (S is padded to a multiple
    with ignore_index).
    """
    import jax

    w = weight.T if transpose_weight else weight  # [H, V]
    S, H = x.shape[-2], x.shape[-1]
    xb = x.reshape((-1, S, H))
    yb = labels.reshape((-1, S))
    C = int(min(seq_chunk, S))
    pad = (-S) % C
    if pad:
        xb = jnp.concatenate(
            [xb, jnp.zeros((xb.shape[0], pad, H), xb.dtype)], axis=1)
        yb = jnp.concatenate(
            [yb, jnp.full((yb.shape[0], pad), ignore_index, yb.dtype)],
            axis=1)
    n_chunks = (S + pad) // C

    # chunks are dynamic slices taken INSIDE the scan body — stacking
    # them as a scanned input would materialize a transposed copy of the
    # whole hidden tensor (measured as ~20ms/step of bitcast/copy
    # fusions on v5e)
    @jax.checkpoint
    def body(carry, i):
        h = jax.lax.dynamic_slice_in_dim(xb, i * C, C, 1)  # [B, C, H]
        t = jax.lax.dynamic_slice_in_dim(yb, i * C, C, 1)  # [B, C]
        logits = h @ w
        if bias is not None:
            logits = logits + bias
        valid = t != ignore_index
        nll = F.softmax_cross_entropy_rows(
            logits, jnp.where(valid, t, 0), valid)
        s, n = carry
        return (s + jnp.sum(nll).astype(jnp.float32),
                n + jnp.sum(valid.astype(jnp.int32))), None

    (loss_sum, count), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        jnp.arange(n_chunks))
    return loss_sum / jnp.maximum(count, 1).astype(jnp.float32)


def fused_bias_act(x, bias=None, act_method="gelu"):
    if bias is not None:
        x = x + bias
    act = getattr(F, act_method)
    return act(x)


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train",
                      rng_key=None):
    return F.dropout(x, p=p, training=training, mode=mode,
                     rng_key=rng_key) + y


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    max_position=None):
    """Parity: incubate fused_rope. q/k/v: [b, s, h, d]; rotates every
    tensor given. sin/cos may be the paddle-shaped [1, s, 1, d] tables
    (the duplicated-half layout) or the compact [s, d/2] this package's
    rope kernel uses; None builds default 10000-base tables."""
    s, d = q.shape[1], q.shape[-1]
    if sin is None or cos is None:
        max_pos = s
        if position_ids is not None:
            if max_position is not None:
                max_pos = int(max_position)
            else:
                try:  # concrete ids: size the table to cover them
                    max_pos = int(jnp.max(position_ids)) + 1
                except Exception as e:  # tracer (jit/vmap)
                    raise ValueError(
                        "fused_rope under jit with position_ids needs "
                        "max_position= (or precomputed sin/cos): the "
                        "default table cannot be sized from a traced "
                        "value") from e
        cos_t, sin_t = rope_frequencies(d, max(max_pos, s), dtype=q.dtype)
    else:
        # accept [..., L, d] (duplicated-half paddle layout) or
        # [..., L, d/2] (compact); L may exceed the current seq — keep the
        # table's own length, never regroup by seq
        cos_t = jnp.asarray(cos)
        sin_t = jnp.asarray(sin)
        last = cos_t.shape[-1]
        if last not in (d, d // 2):
            raise ValueError(
                f"fused_rope: sin/cos last dim {last} matches neither "
                f"head_dim {d} nor head_dim/2")
        cos_t = cos_t.reshape(-1, last)
        sin_t = sin_t.reshape(-1, last)
        if last == d:  # duplicated-half layout → compact
            cos_t, sin_t = cos_t[:, : d // 2], sin_t[:, : d // 2]
    def de_interleave(t):
        # interleaved (x0,x1),(x2,x3) pairs → split-half layout
        return t.reshape(*t.shape[:-1], d // 2, 2) \
            .swapaxes(-1, -2).reshape(*t.shape[:-1], d)

    def re_interleave(t):
        return t.reshape(*t.shape[:-1], 2, d // 2) \
            .swapaxes(-1, -2).reshape(*t.shape[:-1], d)

    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
            continue
        if not use_neox_rotary_style:
            t = de_interleave(t)
        rot, _ = apply_rope(t, t, cos_t, sin_t, position_ids=position_ids)
        if not use_neox_rotary_style:
            rot = re_interleave(rot)
        outs.append(rot)
    return tuple(outs)


def fused_multi_head_attention(x, qkv_weight, qkv_bias=None,
                               linear_weight=None, linear_bias=None,
                               num_heads=None, causal=False,
                               attn_mask=None, dropout_rate=0.0,
                               training=True):
    """Parity: incubate fused_multi_head_attention (phi fused_attention
    kernel): one qkv GEMM → attention → output GEMM."""
    b, s, h = x.shape
    qkv = x @ qkv_weight
    if qkv_bias is not None:
        qkv = qkv + qkv_bias
    d = h // num_heads
    qkv = qkv.reshape(b, s, 3, num_heads, d)
    out = F.scaled_dot_product_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
        attn_mask=attn_mask, is_causal=causal,
        dropout_p=dropout_rate, training=training,
    ).reshape(b, s, h)
    if linear_weight is not None:
        out = out @ linear_weight
        if linear_bias is not None:
            out = out + linear_bias
    return out
