"""Flash attention: Pallas TPU kernel + XLA reference fallback.

Parity: paddle's flash_attn integration (phi kernels flash_attn_kernel.cu
wrapping libflashattn.so; python API paddle.nn.functional.flash_attention).

The Pallas kernel (implemented in this module for TPU backends) tiles
q/k/v into VMEM blocks, keeps the online-softmax running max/denominator
in registers, and never materializes the [sq, sk] score matrix in HBM.
The fallback is the straightforward XLA program — on short sequences XLA's
own fusion is already competitive.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import _backend


def _reference_attention(q, k, v, causal=False, scale=None, bias=None,
                         window=0):
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window:
            mask = jnp.logical_and(
                mask, jnp.triu(jnp.ones((sq, sk), bool),
                               k=sk - sq - window + 1))
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _use_pallas(q) -> bool:
    b, s, h, d = q.shape
    # seq must tile into 128-blocks; head_dim only needs sublane (8)
    # alignment — the kernel zero-pads d to the lane width internally
    # (exact; see pallas_attention._fold), so 64/96-dim heads (GPT/ViT)
    # take the flash path instead of dense XLA attention.
    return _backend.use_kernel(s % 128 == 0 and d % 8 == 0)


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    dropout_p: float = 0.0,
    training: bool = True,
    scale: Optional[float] = None,
    segment_ids=None,
    window_size: int = 0,
):
    """[batch, seq, heads, head_dim] attention. ``segment_ids`` gives the
    varlen/packed-sequence form (parity: flash_attn_varlen). Dropout
    applies only on the fallback path (flash+dropout is rare in practice;
    parity with paddle's flash_attn dropout is provided via the reference
    path)."""
    if window_size and not causal:
        # enforced up front so EVERY path (pallas, dense, segment,
        # dropout) rejects it identically instead of silently ignoring
        raise ValueError("window_size requires causal=True")
    if dropout_p > 0.0 and training:
        from ..nn import functional as F

        attn_mask = None
        if segment_ids is not None:
            if isinstance(segment_ids, (tuple, list)):
                seg_q, seg_kv = segment_ids
            else:
                seg_q = seg_kv = segment_ids
            attn_mask = (seg_q[:, None, :, None]
                         == seg_kv[:, None, None, :])
        if window_size:
            sq, sk = q.shape[1], k.shape[1]
            q_pos = jnp.arange(sq)[:, None] + (sk - sq)
            band = (q_pos - jnp.arange(sk)[None, :]) < window_size
            band = band[None, None]
            attn_mask = band if attn_mask is None else (attn_mask & band)
        return F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
            is_causal=causal, scale=scale, training=training,
        )
    if _use_pallas(q):
        # no fallback from here: what the kernel or the chip's compiler
        # raises is the error, never a silent O(s²) dense attention
        return _pallas_flash_attention(q, k, v, causal=causal,
                                       scale=scale,
                                       segment_ids=segment_ids,
                                       window=window_size)
    if segment_ids is not None:
        return _segment_reference_attention(q, k, v, segment_ids,
                                            causal=causal, scale=scale,
                                            window=window_size)
    return _reference_attention(q, k, v, causal=causal, scale=scale,
                                window=window_size)


def _segment_reference_attention(q, k, v, segment_ids, causal=False,
                                 scale=None, window=0):
    if isinstance(segment_ids, (tuple, list)):
        seg_q, seg_kv = segment_ids
    else:
        seg_q = seg_kv = segment_ids
    bias_mask = seg_q[:, None, :, None] == seg_kv[:, None, None, :]
    bias = jnp.where(bias_mask, 0.0, jnp.float32(-1e30))
    return _reference_attention(q, k, v, causal=causal, scale=scale,
                                bias=bias, window=window)


# ---------------------------------------------------------------------------
# Pallas implementation
# ---------------------------------------------------------------------------
def _mesh_axes(mesh, names, n: int):
    """The mesh axes among ``names`` (in order, size > 1) a dim of
    extent ``n`` can be split over — a PartitionSpec entry, or None."""
    kept, ways = [], 1
    for a in names:
        size = mesh.shape.get(a, 1)
        if size > 1 and n % (ways * size) == 0:
            kept.append(a)
            ways *= size
    return tuple(kept) or None


def _pallas_flash_attention(q, k, v, causal=False, scale=None,
                            segment_ids=None, window=0):
    from ..distributed.sharding import constraints_suppressed, current_mesh
    from .pallas_attention import mha as pallas_mha

    def attend(q, k, v, segment_ids=None):
        return pallas_mha(
            q, k, v, causal=causal, sm_scale=scale,
            segment_ids=segment_ids, window=window)

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or constraints_suppressed():
        return attend(q, k, v, segment_ids)
    # The SPMD partitioner refuses a Pallas call ("Mosaic kernels cannot
    # be automatically partitioned. Please wrap the call in a
    # shard_map"), so under a mesh the kernel runs per shard, split the
    # way the model already lays its activations out: batch over
    # (dp, fsdp), heads over tp (and sep inside the Ulysses region).
    # Attention is independent across both, so no collective is needed;
    # a dim the axes do not divide stays whole.
    from jax.sharding import PartitionSpec as P

    batch = _mesh_axes(mesh, ("dp", "fsdp"), q.shape[0])
    heads = _mesh_axes(mesh, ("tp", "sep"),
                       math.gcd(q.shape[2], k.shape[2]))
    qkv = P(batch, None, heads, None)
    if segment_ids is None:
        return jax.shard_map(
            attend, mesh=mesh, in_specs=(qkv, qkv, qkv), out_specs=qkv,
            check_vma=False)(q, k, v)
    seg = jax.tree_util.tree_map(lambda _: P(batch, None), segment_ids)
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(qkv, qkv, qkv, seg), out_specs=qkv,
        check_vma=False)(q, k, v, segment_ids)
