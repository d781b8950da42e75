"""Pallas TPU paged decode-attention kernel.

Parity: phi ``masked_multihead_attention`` / ``fused_multi_transformer``
(paddle/phi/kernels/fusion/ — the reference's single-token decode
attention over per-sequence KV caches), upgraded to a vLLM-style page
pool.

The TPU-native point: the kernel consumes the block
table DIRECTLY via scalar prefetch — the page id becomes the kv block's
index-map coordinate, so each decode step streams exactly the pages a
slot actually uses. No ``[slots, max_ctx]`` gather into HBM, no dense
attention over padding: HBM traffic per step ∝ Σ seq_lens, not
slots × max_len.

Structure:
  - grid = (slots, kv_heads, max_pages) with pages innermost; the online
    softmax running stats live in VMEM scratch across page steps.
  - block table + seq_lens are scalar-prefetched; pages past a slot's
    length are pruned (index map clamps to the last active page — a
    revisited block issues no DMA — and pl.when skips the compute).
  - GQA is native: q is [slots, kv_heads, group, d]; all q heads of a
    group share one kv page stream.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

NEG_INF = -1e30
# THE int8-KV quantization epsilon (scale = max(absmax/127, eps)) —
# one constant shared by the in-kernel quantize-on-append below and
# the XLA append paths (inference.paged.quantize_kv_rows imports it):
# a divergent eps would silently break the fused-vs-unfused
# bit-identical-pools contract
KV_QUANT_EPS = 1e-8


def _decode_kernel(bt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scratch, l_scratch, acc_scratch,
                   *, scale, page_size, max_pages, group_pad):
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    seq_len = lens_ref[s]  # inclusive position of the current token
    last_page = seq_len // page_size

    @pl.when(j <= last_page)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # [group_pad, d]
        k = k_ref[...]  # [page_size, d]
        v = v_ref[...]
        sc = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [group_pad, page_size]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1
        )
        sc = jnp.where(pos <= seq_len, sc, NEG_INF)

        m_prev = m_scratch[:, :1]
        l_prev = l_scratch[:, :1]
        m_cur = jnp.max(sc, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(j == max_pages - 1)
    def _fin():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens,
                           scale=None):
    """q: [slots, kv_heads, group, d] (one decode token per slot).

    k_pages/v_pages: [kv_heads, n_pages, page_size, d] — head-major, the
    TPU-tileable layout: the per-grid-step block is one head's one page,
    so the block's LAST TWO dims are (page_size, d) = full tiled minor
    dims. (A head-minor pool [pages, page_size, kvh, d] cannot lower:
    selecting 1 of kvh in the sublane dim is a strided DMA the Mosaic
    lowering rejects — found the first time a 32-kv-head 7B model hit
    real silicon; small models with kvh==1 never trip it.)
    block_tables: [slots, max_pages] int32; seq_lens: [slots] int32 —
    slot i attends to positions [0, seq_lens[i]] inclusive.
    Returns [slots, kv_heads, group, d].
    """
    slots, kvh, group, d = q.shape
    _, n_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5

    # pad the q-head group to the fp32 sublane tile (8)
    group_pad = max(8, -(-group // 8) * 8)
    if group_pad != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, group_pad - group), (0, 0)))

    def q_index(s, h, j, bt_ref, lens_ref):
        return (s, h, 0, 0)

    def kv_index(s, h, j, bt_ref, lens_ref):
        # clamp to the slot's last active page: pruned steps revisit the
        # previous block, so no DMA is issued for them
        last = lens_ref[s] // page_size
        return (h, bt_ref[s, jnp.minimum(j, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, kvh, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, group_pad, d), q_index),
            pl.BlockSpec((None, None, page_size, d), kv_index),
            pl.BlockSpec((None, None, page_size, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, group_pad, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((group_pad, 128), jnp.float32),
            pltpu.VMEM((group_pad, 128), jnp.float32),
            pltpu.VMEM((group_pad, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, group_pad=group_pad,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, kvh, group_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_backend.interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), q, k_pages, v_pages)
    return out[:, :, :group, :]


# ---------------------------------------------------------------------------
# Fused single-pass decode: in-kernel RoPE + KV-append + attention
# ---------------------------------------------------------------------------
def kernel_rope_rot(x, cos, sin):
    """In-kernel half-rotation (Neox/Llama convention, matching
    kernels/rope.apply_rope): x [..., d] f32, cos/sin broadcastable
    [..., d/2]. ONE definition shared by the paged and contiguous fused
    kernels so the convention cannot drift between them."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def kernel_quant_rows(x):
    """In-kernel symmetric per-row int8: x [rows, d] f32 → (int8 rows,
    f32 scales [rows, 1]). ONE definition shared by the paged and
    contiguous fused kernels, matching ``inference.paged.
    quantize_kv_rows`` exactly (absmax/127, round, clip, same eps) so
    the fused quantize-on-append and the XLA scatter paths write
    bit-identical pools."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, KV_QUANT_EPS)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def append_tile_rows(minor: int, itemsize: int) -> int:
    """Rows the fused decode kernels write back per append: one sublane
    tile of the pool dtype (8 rows of f32, 16 of bf16, 32 of int8), the
    least the chip's compiler takes as an output block, or the whole
    ``minor`` (page or chunk) where that is smaller."""
    return math.gcd(minor, 32 // itemsize)


def online_softmax_update(sc, v, m_prev, l_prev, acc_prev):
    """One streaming-softmax step shared by the fused decode kernels:
    fold scores ``sc`` [q, kblock] and values ``v`` [kblock, d] into the
    running (m, l, acc); returns the updated triple (keepdims stats)."""
    m_cur = jnp.max(sc, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(sc - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_prev * alpha + pv


def _fused_decode_kernel(bt_ref, lens_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         k_ref, v_ref, *rest,
                         scale, page_size, max_pages, group_pad, quant,
                         append_rows):
    if quant:
        (ks_ref, vs_ref, cos_ref, sin_ref, o_ref, ko_ref, vo_ref,
         kso_ref, vso_ref, q_scratch, m_scratch, l_scratch,
         acc_scratch) = rest
    else:
        (cos_ref, sin_ref, o_ref, ko_ref, vo_ref, q_scratch,
         m_scratch, l_scratch, acc_scratch) = rest
    s = pl.program_id(0)
    j = pl.program_id(2)
    seq_len = lens_ref[s]  # position of THIS token (== tokens cached)
    last_page = seq_len // page_size
    offs = seq_len % page_size

    cos = cos_ref[...].astype(jnp.float32)  # [1, d/2] row at pos_ref[s]
    sin = sin_ref[...].astype(jnp.float32)

    def rot(x):
        return kernel_rope_rot(x, cos, sin)

    # rotated new-token K — also the row appended to the pool, so the
    # token never round-trips through HBM before attention reads it.
    # Attention merges the CACHE-DTYPE-ROUNDED values (not the f32
    # intermediates): the unfused path attends to the appended row
    # as the pool stores it, and bf16/int8 pools must not flip a greedy
    # argmax between the fused and unfused engines
    k_rot = rot(kn_ref[0, 0].astype(jnp.float32))  # [1, d]
    v_raw = vn_ref[0, 0].astype(jnp.float32)
    if quant:
        # quantize-on-append in-kernel: the int8 row and its f32 scale
        # land together; attention merges the DEQUANTIZED stored values
        kq, kscl = kernel_quant_rows(k_rot)
        vq, vscl = kernel_quant_rows(v_raw)
        k_new = kq.astype(jnp.float32) * kscl
        v_new = vq.astype(jnp.float32) * vscl
    else:
        k_new = k_rot.astype(ko_ref.dtype).astype(jnp.float32)
        v_new = v_raw.astype(vo_ref.dtype).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        # RoPE q once per (s, h) into scratch (input-ref mutations don't
        # persist across grid steps in interpret mode; scratch does)
        q_scratch[:] = rot(q_ref[0, 0].astype(jnp.float32))

    @pl.when(j <= last_page)
    def _step():
        q = q_scratch[...]  # [group_pad, d] rotated f32
        is_last = j == last_page
        row = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        sel = (row == offs) & is_last
        # merge the new token into the streamed page IN VMEM: the HBM
        # page still holds stale data at `offs`; attention must see the
        # rotated k / raw v of the token being appended this step
        kf = k_ref[...].astype(jnp.float32)
        vf = v_ref[...].astype(jnp.float32)

        # the append. Mosaic refuses a one-row output block ("the last
        # two dimensions of your block shape [must be] divisible by 8
        # and 128"), so what goes back is the sublane tile of
        # `append_rows` rows that holds row `offs`, taken from the page
        # this step already has in VMEM with the new row merged in. Its
        # block index is constant over j: one DMA per (s, h). Widening
        # the other rows to f32 and back is exact, so they return
        # bit-identical.
        @pl.when(is_last)
        def _append():
            base = pl.multiple_of(offs // append_rows * append_rows,
                                  append_rows)
            tile = pl.ds(base, append_rows)
            hit = (base + jax.lax.broadcasted_iota(
                jnp.int32, (append_rows, 1), 0)) == offs

            def merged(new, ref):
                old = ref[tile, :]
                return jnp.where(hit, new.astype(jnp.float32),
                                 old.astype(jnp.float32)).astype(old.dtype)

            if quant:
                ko_ref[...] = merged(kq, k_ref)
                vo_ref[...] = merged(vq, v_ref)
                kso_ref[...] = merged(kscl, ks_ref)
                vso_ref[...] = merged(vscl, vs_ref)
            else:
                ko_ref[...] = merged(k_new, k_ref)
                vo_ref[...] = merged(v_new, v_ref)

        if quant:
            # dequantize the streamed page: per-row scales ride as a
            # [page_size, 1] block alongside the [page_size, d] page
            kf = kf * ks_ref[...]
            vf = vf * vs_ref[...]
        k = jnp.where(sel, k_new, kf)
        v = jnp.where(sel, v_new, vf)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [group_pad, page_size]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1
        )
        sc = jnp.where(pos <= seq_len, sc, NEG_INF)

        m_new, l_new, acc = online_softmax_update(
            sc, v, m_scratch[:, :1], l_scratch[:, :1], acc_scratch[:])
        acc_scratch[:] = acc
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(j == max_pages - 1)
    def _fin():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l).astype(o_ref.dtype)


def fused_paged_decode_attention(q, k_new, v_new, k_pages, v_pages,
                                 block_tables, seq_lens, positions,
                                 cos, sin, scale=None,
                                 k_scale=None, v_scale=None):
    """Single-pass decode: RoPE(q, k_new) + append (k_new, v_new) into
    each slot's current page + length-pruned online-softmax attention,
    one kernel per layer.

    q: [slots, kv_heads, group, d] UNROTATED; k_new/v_new:
    [slots, kv_heads, d] the new token's unrotated K / V per slot.
    k_pages/v_pages: [kv_heads, n_pages, page_size, d] head-major pool
    (see ``paged_decode_attention``); ALIASED into the outputs — under
    jit the caller should donate them. seq_lens: [slots] int32, tokens
    already cached (== the new token's in-slot position; slot i attends
    to [0, seq_lens[i]] inclusive of the appended token). positions:
    [slots] int32 RoPE positions (== seq_lens for the serving engine;
    kept separate so callers with custom position_ids stay correct).
    cos/sin: [max_pos, d//2] rope tables — the per-slot row is selected
    by scalar-prefetched position, so rotation costs one table-row read
    instead of a q/k materialization round-trip.

    PRECONDITION (unchecked — indices are traced): seq_lens[i] <
    max_pages * page_size (the slot has a page for the appended row;
    Pallas CLAMPS out-of-range block indices, so violating this
    silently overwrites the last allocated row) and positions[i] <
    cos.shape[0]. The serving engine guarantees both.

    INT8 POOLS: pass ``k_scale``/``v_scale`` f32
    [kv_heads, n_pages, page_size, 1] per-row dequant scales (the
    layout ``inference.paged.init_paged_pool`` builds). The kernel
    quantizes the appended row in-kernel (same absmax rule as the XLA
    append paths), writes payload + scale together, and dequantizes
    each streamed page in VMEM — attention math stays f32. Scale
    blocks mirror the pool blocks with d→1 so they tile wherever the
    pool does.

    Returns (out [slots, kv_heads, group, d], k_pages', v_pages') —
    plus (k_scale', v_scale') when quantized.
    """
    slots, kvh, group, d = q.shape
    _, n_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    quant = k_scale is not None
    if scale is None:
        scale = d ** -0.5

    group_pad = max(8, -(-group // 8) * 8)
    if group_pad != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, group_pad - group), (0, 0)))
    k_new = k_new.reshape(slots, kvh, 1, d)
    v_new = v_new.reshape(slots, kvh, 1, d)
    half = d // 2

    def q_index(s, h, j, bt_ref, lens_ref, pos_ref):
        return (s, h, 0, 0)

    def kv_index(s, h, j, bt_ref, lens_ref, pos_ref):
        last = lens_ref[s] // page_size
        return (h, bt_ref[s, jnp.minimum(j, last)], 0, 0)

    def rope_index(s, h, j, bt_ref, lens_ref, pos_ref):
        return (pos_ref[s], 0, 0)

    append_rows = append_tile_rows(page_size, k_pages.dtype.itemsize)

    def append_index(s, h, j, bt_ref, lens_ref, pos_ref):
        # the tile of the slot's current page that holds the new row —
        # constant over j, so it is written back once per (s, h)
        return (h, bt_ref[s, lens_ref[s] // page_size],
                lens_ref[s] % page_size // append_rows, 0)

    in_specs = [
        pl.BlockSpec((1, 1, group_pad, d), q_index),
        pl.BlockSpec((1, 1, 1, d), q_index),
        pl.BlockSpec((1, 1, 1, d), q_index),
        pl.BlockSpec((None, None, page_size, d), kv_index),
        pl.BlockSpec((None, None, page_size, d), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, group_pad, d), q_index),
        pl.BlockSpec((None, None, append_rows, d), append_index),
        pl.BlockSpec((None, None, append_rows, d), append_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((slots, kvh, group_pad, d), q.dtype),
        jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
        jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
    ]
    # operand order: 3 prefetch scalars, q, kn, vn, k_pages(6),
    # v_pages(7), [k_scale(8), v_scale(9),] cos, sin — pools (and
    # scale arrays) alias their outputs so the append is in-place on
    # the donated cache buffers
    aliases = {6: 1, 7: 2}
    operands = [q, k_new, v_new, k_pages, v_pages]
    if quant:
        in_specs += [
            pl.BlockSpec((None, None, page_size, 1), kv_index),
            pl.BlockSpec((None, None, page_size, 1), kv_index),
        ]
        out_specs += [
            pl.BlockSpec((None, None, append_rows, 1), append_index),
            pl.BlockSpec((None, None, append_rows, 1), append_index),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        aliases.update({8: 3, 9: 4})
        operands += [k_scale, v_scale]
    # the table rides as [max_pos, 1, d/2]: a (1, d/2) block of the 2-D
    # table has a second-minor dim of 1, which Mosaic refuses unless it
    # is the array's own
    in_specs += [
        pl.BlockSpec((None, 1, half), rope_index),
        pl.BlockSpec((None, 1, half), rope_index),
    ]
    operands += [cos.reshape(-1, 1, half), sin.reshape(-1, 1, half)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, kvh, max_pages),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((group_pad, d), jnp.float32),
            pltpu.VMEM((group_pad, 128), jnp.float32),
            pltpu.VMEM((group_pad, 128), jnp.float32),
            pltpu.VMEM((group_pad, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fused_decode_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, group_pad=group_pad, quant=quant,
        append_rows=append_rows,
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_backend.interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(positions, jnp.int32),
      *operands)
    if quant:
        out, k_pages, v_pages, k_scale, v_scale = res
        return out[:, :, :group, :], k_pages, v_pages, k_scale, v_scale
    out, k_pages, v_pages = res
    return out[:, :, :group, :], k_pages, v_pages
