"""Fused single-pass decode attention for CONTIGUOUS per-slot KV caches
(+ the dispatch gate and lax reference paths shared with the paged
variant in ``paged_attention.py``).

Parity: phi ``masked_multihead_attention`` — the reference's single
fused decode op that rotates the new token, writes it into the cache and
attends, all in one kernel. The engine's default contiguous mode
previously paid three HBM round-trips per decoder layer per token
(RoPE materializes rotated q/k, the per-slot scatter writes K/V, dense
masked SDPA then re-reads ``[slots, max_len]`` including every padding
row); this kernel does all three in one pass with LENGTH-PRUNED
streaming — per-step traffic ∝ Σ ceil(len_i/chunk)·chunk, the same
``Σ seq_lens`` scaling the paged kernel already has, instead of
``slots × max_len``.

Structure (mirrors kernels/paged_attention.py):
  - the cache rides as ``[slots, max_len, kvh*d]`` (a free reshape of
    the engine's ``[slots, max_len, kvh, d]`` layout): the per-grid-step
    block is one slot's ``chunk`` rows with minor dims
    ``(chunk, kvh*d)`` — full tiled minor dims, no head-strided DMA —
    and all kv heads stream in one fetch, with a static per-head loop
    inside the kernel;
  - grid = (slots, n_chunks), chunks innermost; chunks past a slot's
    length are pruned (index map clamps → no DMA, pl.when skips
    compute);
  - RoPE is applied in-kernel from scalar-prefetched positions (the
    cos/sin table row is the block index — one row read per slot);
  - the new token's K/V is merged into the streamed chunk in VMEM and
    the sublane tile around its row is written back through
    ``input_output_aliases`` (the chip's compiler refuses a one-row
    block), so the token never round-trips through HBM before
    attention reads it and the separate append scatter disappears from
    the decode trace.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import flags
from . import _backend
from .paged_attention import (
    NEG_INF,
    append_tile_rows,
    kernel_quant_rows,
    kernel_rope_rot,
    online_softmax_update,
)


# ---------------------------------------------------------------------------
# ptaudit contract annotation (analysis/program_audit.py imports this):
# the dtype widenings the decode-path kernels PROMISE — narrow streams
# (bf16/f16 caches, int8/int4 payloads and weight groups) stay narrow
# through HBM and widen only at these in-register sites. Any other
# narrow->wide convert inside a compiled serving program is a DQ001
# finding, because it silently re-widens the stream the bytes-per-token
# models (kernelbench) price as narrow.
# ---------------------------------------------------------------------------
AUDIT_WIDEN_ALLOW = {
    "bfloat16->float32": "attention gathers bf16 K/V rows and "
                         "accumulates logits/softmax in f32 in-VMEM "
                         "(never re-materialized wide to HBM)",
    "float16->float32": "same softmax-accumulator discipline for f16 "
                        "caches",
    "int8->float32": "in-kernel dequant: int8 KV payloads / weight "
                     "groups widen against their f32 scale rows only "
                     "at the matmul/attention input",
}


def contiguous_chunk(max_len: int) -> int:
    """Streaming granularity over the [slots, max_len] cache rows:
    gcd(max_len, 128) — i.e. the largest power-of-two divisor of
    max_len capped at 128 — keeps blocks tile-aligned without
    constraining the engine's max_len choice."""
    return math.gcd(max_len, 128)


def decode_tiles_ok(head_dim: int, minor: int, dtype=None) -> bool:
    """THE tiling rule for every Pallas decode kernel (block-table and
    fused, both cache modes — ``inference.paged._use_pallas_decode``
    shares it): d fills the lane dim, and ``minor`` (page_size or the
    contiguous chunk) respects the pool dtype's sublane tile — 16 for
    the bf16/f32 pools, 32 for int8 (the int8 min tile is (32, 128))."""
    sub = 32 if (dtype is not None
                 and jnp.dtype(dtype) == jnp.int8) else 16
    return head_dim % 128 == 0 and minor % sub == 0


def fused_decode_active(head_dim: int, minor: int, dtype=None) -> bool:
    """Gate for the fused decode kernels (PT_FLAGS_fused_decode).

    ``minor``: page_size (paged mode) or the contiguous chunk length —
    the streamed block's sublane dim; ``dtype``: the pool dtype (int8
    tightens the tile rule). auto = compiled kernel on TPU when the
    block tiles (``decode_tiles_ok``); the lax reference elsewhere.
    ``on`` forces the kernel (Pallas interpret mode off-TPU — how the
    tier-1 parity tests run it); ``off`` forces the reference path.
    """
    val = str(flags.flag("fused_decode")).lower()
    if val in ("off", "0", "false", "no"):
        return False
    if _backend.interpret():
        return val in ("on", "1", "true", "yes")
    if val in ("on", "1", "true", "yes"):
        return True
    return decode_tiles_ok(head_dim, minor, dtype)


# ---------------------------------------------------------------------------
# Pallas kernel — contiguous per-slot caches
# ---------------------------------------------------------------------------
def _fused_contig_kernel(lens_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         k_ref, v_ref, *rest,
                         scale, chunk, n_chunks, kvh, d, quant,
                         append_rows):
    if quant:
        (ks_ref, vs_ref, cos_ref, sin_ref, o_ref, ko_ref, vo_ref,
         kso_ref, vso_ref, q_scratch, m_scratch, l_scratch,
         acc_scratch) = rest
    else:
        (cos_ref, sin_ref, o_ref, ko_ref, vo_ref, q_scratch,
         m_scratch, l_scratch, acc_scratch) = rest
    s = pl.program_id(0)
    j = pl.program_id(1)
    seq_len = lens_ref[s]  # position of THIS token (== tokens cached)
    last_chunk = seq_len // chunk
    offs = seq_len % chunk

    cos = cos_ref[...].astype(jnp.float32)  # [1, d/2] row at pos_ref[s]
    sin = sin_ref[...].astype(jnp.float32)

    def rot(x):
        return kernel_rope_rot(x, cos, sin)

    # rotated new-token K for all heads, flattened to the cache row
    # layout [1, kvh*d] — the row appended to the cache.
    # Attention merges the CACHE-DTYPE-ROUNDED values — same rounding
    # the unfused path's appended row gets — so bf16/int8 caches cannot
    # flip a greedy argmax between the fused and unfused engines
    k_rot = rot(kn_ref[...].astype(jnp.float32))  # [kvh, 1, d]
    v_raw = vn_ref[...].astype(jnp.float32)
    if quant:
        # quantize-on-append in-kernel (per head over d — the same row
        # rule as inference.paged.quantize_kv_rows): int8 payload to
        # the cache row, f32 scales to the [1, kvh] scale row
        kq, kscl = kernel_quant_rows(k_rot)   # [kvh, 1, d], [kvh, 1, 1]
        vq, vscl = kernel_quant_rows(v_raw)
        k_new = (kq.astype(jnp.float32) * kscl).reshape(1, kvh * d)
        v_new = (vq.astype(jnp.float32) * vscl).reshape(1, kvh * d)
    else:
        k_new = k_rot.reshape(1, kvh * d).astype(
            ko_ref.dtype).astype(jnp.float32)
        v_new = v_raw.reshape(1, kvh * d).astype(
            vo_ref.dtype).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        q_scratch[:] = rot(q_ref[...].astype(jnp.float32))

    @pl.when(j <= last_chunk)
    def _step():
        is_last = j == last_chunk
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        sel = (row == offs) & is_last
        kf = k_ref[...].astype(jnp.float32)
        vf = v_ref[...].astype(jnp.float32)

        # the append: the sublane tile of `append_rows` rows that holds
        # row `offs` goes back with the new row merged in (Mosaic
        # refuses a one-row output block — see _fused_decode_kernel);
        # the f32 round trip of the other rows is exact, so they return
        # bit-identical
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
                ko_ref[...] = merged(kq.reshape(1, kvh * d), k_ref)
                vo_ref[...] = merged(vq.reshape(1, kvh * d), v_ref)
                kso_ref[...] = merged(kscl.reshape(1, kvh), ks_ref)
                vso_ref[...] = merged(vscl.reshape(1, kvh), vs_ref)
            else:
                ko_ref[...] = merged(k_new, k_ref)
                vo_ref[...] = merged(v_new, v_ref)

        if quant:
            # dequantize the streamed chunk: scale rows [chunk, kvh]
            # broadcast over each head's d-segment of the row layout
            kf = kf * jnp.repeat(ks_ref[...], d, axis=1)
            vf = vf * jnp.repeat(vs_ref[...], d, axis=1)
        # merge the new token into the streamed chunk in VMEM
        k_blk = jnp.where(sel, k_new, kf)
        v_blk = jnp.where(sel, v_new, vf)
        valid = (j * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1)) <= seq_len  # [1, chunk]
        for h in range(kvh):  # static unroll; all heads share the fetch
            kh = k_blk[:, h * d:(h + 1) * d]  # [chunk, d]
            vh = v_blk[:, h * d:(h + 1) * d]
            q = q_scratch[h]  # [group_pad, d] rotated f32
            sc = jax.lax.dot_general(
                q, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [group_pad, chunk]
            sc = jnp.where(valid, sc, NEG_INF)
            m_new, l_new, acc = online_softmax_update(
                sc, vh, m_scratch[h, :, :1], l_scratch[h, :, :1],
                acc_scratch[h])
            acc_scratch[h] = acc
            m_scratch[h] = jnp.broadcast_to(m_new, m_scratch.shape[1:])
            l_scratch[h] = jnp.broadcast_to(l_new, l_scratch.shape[1:])

    @pl.when(j == n_chunks - 1)
    def _fin():
        for h in range(kvh):
            l = l_scratch[h, :, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scratch[h] / l).astype(o_ref.dtype)


def fused_contiguous_decode_attention(q, k_new, v_new, ck, cv, seq_lens,
                                      positions, cos, sin, scale=None,
                                      k_scale=None, v_scale=None):
    """Single-pass decode over the engine's contiguous per-slot caches:
    RoPE(q, k_new) + write (k_new, v_new) at each slot's current length
    + length-pruned online-softmax attention, one kernel per layer.

    q: [slots, kv_heads, group, d] UNROTATED; k_new/v_new:
    [slots, kv_heads, d]. ck/cv: [slots, max_len, kv_heads, d] — ALIASED
    into the outputs (donate under jit). seq_lens: [slots] int32 tokens
    already cached; slot i attends to [0, seq_lens[i]] inclusive of the
    appended token. positions: [slots] int32 RoPE positions. cos/sin:
    [max_pos, d//2].

    PRECONDITION (unchecked — indices are traced): seq_lens[i] <
    max_len (the cache has room for the appended row; Pallas CLAMPS
    out-of-range block indices, so violating this silently overwrites
    the last cached row) and positions[i] < cos.shape[0]. The serving
    engine guarantees both (add_request length check + _maybe_finish).

    INT8 CACHES: pass ``k_scale``/``v_scale`` f32
    [slots, max_len, kvh] per-row dequant scales (the layout
    ``QuantizedKV`` carries). The kernel quantizes the appended row
    per head in-kernel (same absmax rule as the XLA scatter paths),
    writes payload + scale rows together, and dequantizes each
    streamed chunk in VMEM. Scale blocks are (chunk, kvh) — sublane
    matches the cache blocks, lane is the full kvh dim.

    Returns (out [slots, kv_heads, group, d], ck', cv') — plus
    (k_scale', v_scale') when quantized.
    """
    slots, kvh, group, d = q.shape
    max_len = ck.shape[1]
    chunk = contiguous_chunk(max_len)
    n_chunks = max_len // chunk
    quant = k_scale is not None
    if scale is None:
        scale = d ** -0.5

    group_pad = max(8, -(-group // 8) * 8)
    if group_pad != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, group_pad - group), (0, 0)))
    k_new = k_new.reshape(slots, kvh, 1, d)
    v_new = v_new.reshape(slots, kvh, 1, d)
    # free layout view: one streamed block is (chunk, kvh*d) — full
    # tiled minor dims; a head-minor 4D block would DMA sublane-strided
    ck2 = ck.reshape(slots, max_len, kvh * d)
    cv2 = cv.reshape(slots, max_len, kvh * d)
    half = d // 2

    def q_index(s, j, lens_ref, pos_ref):
        return (s, 0, 0, 0)

    def kv_index(s, j, lens_ref, pos_ref):
        # clamp to the slot's last active chunk: pruned steps revisit
        # the previous block, so no DMA is issued for them
        return (s, jnp.minimum(j, lens_ref[s] // chunk), 0)

    def rope_index(s, j, lens_ref, pos_ref):
        return (pos_ref[s], 0, 0)

    append_rows = append_tile_rows(chunk, ck.dtype.itemsize)

    def append_index(s, j, lens_ref, pos_ref):
        # the tile that holds the new token's row, constant in j
        return (s, lens_ref[s] // append_rows, 0)

    in_specs = [
        pl.BlockSpec((None, kvh, group_pad, d),
                     lambda s, j, l, p: (s, 0, 0, 0)),
        pl.BlockSpec((None, kvh, 1, d),
                     lambda s, j, l, p: (s, 0, 0, 0)),
        pl.BlockSpec((None, kvh, 1, d),
                     lambda s, j, l, p: (s, 0, 0, 0)),
        pl.BlockSpec((None, chunk, kvh * d), kv_index),
        pl.BlockSpec((None, chunk, kvh * d), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, kvh, group_pad, d), q_index),
        pl.BlockSpec((None, append_rows, kvh * d), append_index),
        pl.BlockSpec((None, append_rows, kvh * d), append_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((slots, kvh, group_pad, d), q.dtype),
        jax.ShapeDtypeStruct(ck2.shape, ck2.dtype),
        jax.ShapeDtypeStruct(cv2.shape, cv2.dtype),
    ]
    # operand order: 2 prefetch scalars, q, kn, vn, ck(5), cv(6),
    # [ks(7), vs(8),] cos, sin — caches (and scale arrays) alias
    # their outputs (in-place append)
    aliases = {5: 1, 6: 2}
    operands = [q, k_new, v_new, ck2, cv2]
    if quant:
        in_specs += [
            pl.BlockSpec((None, chunk, kvh), kv_index),
            pl.BlockSpec((None, chunk, kvh), kv_index),
        ]
        out_specs += [
            pl.BlockSpec((None, append_rows, kvh), append_index),
            pl.BlockSpec((None, append_rows, kvh), append_index),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        aliases.update({7: 3, 8: 4})
        operands += [k_scale, v_scale]
    # [max_pos, 1, d/2]: see fused_paged_decode_attention
    in_specs += [
        pl.BlockSpec((None, 1, half), rope_index),
        pl.BlockSpec((None, 1, half), rope_index),
    ]
    operands += [cos.reshape(-1, 1, half), sin.reshape(-1, 1, half)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, n_chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kvh, group_pad, d), jnp.float32),
            pltpu.VMEM((kvh, group_pad, 128), jnp.float32),
            pltpu.VMEM((kvh, group_pad, 128), jnp.float32),
            pltpu.VMEM((kvh, group_pad, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _fused_contig_kernel, scale=scale, chunk=chunk,
        n_chunks=n_chunks, kvh=kvh, d=d, quant=quant,
        append_rows=append_rows,
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_backend.interpret(),
    )(jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(positions, jnp.int32),
      *operands)
    if quant:
        out, ck2, cv2, k_scale, v_scale = res
        return (out[:, :, :group, :],
                ck2.reshape(slots, max_len, kvh, d),
                cv2.reshape(slots, max_len, kvh, d),
                k_scale, v_scale)
    out, ck2, cv2 = res
    return (out[:, :, :group, :],
            ck2.reshape(slots, max_len, kvh, d),
            cv2.reshape(slots, max_len, kvh, d))


# ---------------------------------------------------------------------------
# lax reference paths (numeric source of truth for parity tests)
# ---------------------------------------------------------------------------
def _rope_rotate(x, positions, cos, sin):
    """x: [slots, heads, d] (one token per slot) → rotated via the
    canonical ``kernels/rope.apply_rope`` (so the oracle can never
    drift from the model path's rope convention)."""
    from .rope import apply_rope

    x4 = x[:, None]  # [slots, 1, heads, d]
    out, _ = apply_rope(x4, x4, cos, sin, positions[:, None])
    return out[:, 0]


def fused_paged_decode_reference(q, k_new, v_new, k_pages, v_pages,
                                 block_tables, seq_lens, positions,
                                 cos, sin, scale=None,
                                 k_scale=None, v_scale=None):
    """Unfused reference for ``fused_paged_decode_attention``: rope →
    append_kv scatter → dense gathered attention (the pre-fusion decode
    path, kept as the parity oracle). int8 pools (``k_scale`` set) ride
    the same path: ``append_kv`` quantizes-on-append, ``gather_kv``
    dequantizes, so this stays the numeric oracle for the quantized
    kernel too."""
    from ..inference.paged import (
        PagedLayerCache,
        PagedState,
        append_kv,
        dense_paged_attention,
    )

    slots, kvh, group, d = q.shape
    qr = _rope_rotate(q.reshape(slots, kvh * group, d), positions,
                      cos, sin).reshape(slots, kvh, group, d)
    kr = _rope_rotate(k_new, positions, cos, sin)
    cache = PagedLayerCache(k_pages, v_pages, k_scale, v_scale)
    state = PagedState(jnp.asarray(block_tables, jnp.int32),
                       jnp.asarray(seq_lens, jnp.int32))
    cache = append_kv(cache, state, kr[:, None], v_new[:, None])
    out = dense_paged_attention(
        qr.reshape(slots, 1, kvh * group, d), cache, state, scale=scale)
    out = out[:, 0].reshape(slots, kvh, group, d)
    if k_scale is not None:
        return (out, cache.k_pages, cache.v_pages,
                cache.k_scale, cache.v_scale)
    return out, cache.k_pages, cache.v_pages


def fused_contiguous_decode_reference(q, k_new, v_new, ck, cv, seq_lens,
                                      positions, cos, sin, scale=None,
                                      k_scale=None, v_scale=None):
    """Unfused reference for ``fused_contiguous_decode_attention``:
    rope → per-slot scatter → dense masked attention over the full
    [slots, max_len] cache (the pre-fusion contiguous decode path).
    int8 caches (``k_scale`` set): the appended row is quantized with
    the shared absmax rule and attention reads the dequantized cache."""
    from ..inference.paged import quantize_kv_rows

    slots, kvh, group, d = q.shape
    max_len = ck.shape[1]
    if scale is None:
        scale = d ** -0.5
    qr = _rope_rotate(q.reshape(slots, kvh * group, d), positions,
                      cos, sin).reshape(slots, kvh, group, d)
    kr = _rope_rotate(k_new, positions, cos, sin)
    lens = jnp.asarray(seq_lens, jnp.int32)
    quant = k_scale is not None
    if quant:
        kq, ks = quantize_kv_rows(kr)      # [slots, kvh, d] / [s, kvh]
        vq, vs = quantize_kv_rows(v_new)
        ck = ck.at[jnp.arange(slots), lens].set(kq)
        cv = cv.at[jnp.arange(slots), lens].set(vq)
        k_scale = k_scale.at[jnp.arange(slots), lens].set(ks)
        v_scale = v_scale.at[jnp.arange(slots), lens].set(vs)
        kf = ck.astype(jnp.float32) * k_scale[..., None]
        vf = cv.astype(jnp.float32) * v_scale[..., None]
    else:
        ck = ck.at[jnp.arange(slots), lens].set(kr.astype(ck.dtype))
        cv = cv.at[jnp.arange(slots), lens].set(v_new.astype(cv.dtype))
        kf, vf = ck, cv
    k = jnp.repeat(kf.astype(jnp.float32), group, axis=2)
    v = jnp.repeat(vf.astype(jnp.float32), group, axis=2)
    qf = qr.reshape(slots, kvh * group, 1, d).astype(jnp.float32) * scale
    s = jnp.einsum("shqd,skhd->shqk", qf, k)
    mask = jnp.arange(max_len)[None, :] <= lens[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shqk,skhd->shqd", p, v)
    out = out[:, :, 0].reshape(slots, kvh, group, d).astype(q.dtype)
    if quant:
        return out, ck, cv, k_scale, v_scale
    return out, ck, cv
