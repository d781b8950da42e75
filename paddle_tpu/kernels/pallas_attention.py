"""Pallas TPU flash-attention kernel (forward + custom-VJP backward).

Parity: the reference's flash-attn integration (phi flash_attn kernels
wrapping libflashattn.so CUDA kernels, paddle/phi/kernels/gpu/
flash_attn_kernel.cu, incl. the flash_attn_varlen entry point). This is
the TPU-native equivalent: online-softmax tiling in VMEM, fp32 running
statistics, never materializing the [sq, sk] score matrix in HBM.

Design notes (per /opt/skills/guides/pallas_guide.md):
  - forward grid = (batch*kv_heads, q_per_kv, q_blocks, k_blocks); k is
    the innermost (sequential) dimension so the running max/denominator
    live in VMEM scratch across k-steps.
  - GQA is native: q is viewed as [b*hk, rep, sq, d] and k/v as
    [b*hk, sk, d]; the kv block index map ignores the rep dimension, so
    kv is NEVER materialized rep times in HBM (no jnp.repeat).
  - causal masking prunes fully-masked k-blocks: the kv index map clamps
    the block index at the diagonal (a revisited block issues no DMA) and
    the kernel body is skipped under pl.when, so causal runs ~half the
    FLOPs and ~half the kv HBM traffic. The mask itself is applied only
    in diagonal-straddling blocks.
  - backward is two passes (flash-v2 style): a dq kernel with k innermost
    accumulating dq in VMEM scratch, and a dk/dv kernel with (rep, q)
    innermost accumulating dk/dv in VMEM scratch — no [bh, n_kb, sq, d]
    HBM partials anywhere; every gradient's HBM footprint equals its
    final size. The dk/dv pass also performs the GQA head-group reduction
    in-register (sum over rep lands in the same scratch accumulator).
  - varlen/packed sequences via segment ids (parity with
    flash_attn_varlen): tokens attend only within equal segment id;
    padding can be given a sentinel segment.
  - blocks are MXU-aligned; all matmuls request fp32 accumulation via
    preferred_element_type; per-row stats are carried lane-broadcast
    ([q_block, 128]) to keep Mosaic layouts trivial.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# v5e-swept defaults (876M bench shape, b4 x s2048 x h24 x d128, causal):
# 256/256 ran fwd 5.36ms / fwd+bwd 13.9ms; 512/1024 2.16/6.49;
# 1024/1024 1.97/6.20 — 2.2x over 256-blocks (grid-step overhead
# dominates small tiles; each 256x256 tile is ~0.2us of MXU work) and
# ahead of the jax-bundled TPU flash kernel's 1.31/6.95 on fwd+bwd.
# 2048-size blocks fail to compile (VMEM). Shorter sequences clamp in
# _fold, so the large default is safe for every caller.
DEFAULT_Q_BLOCK = 1024
DEFAULT_K_BLOCK = 1024
NEG_INF = -1e30
LANES = 128


def _interpret() -> bool:
    # run the kernel in interpreter mode off-TPU (CPU CI parity tests)
    return jax.default_backend() != "tpu"


def _params(*parallel_then_arbitrary: str):
    return pltpu.CompilerParams(dimension_semantics=parallel_then_arbitrary)


def _causal_j_max(i: int, q_block: int, k_block: int):
    """Last kv block index with any unmasked element for q block i."""
    return ((i + 1) * q_block - 1) // k_block


def _causal_i_min(j: int, q_block: int, k_block: int):
    """First q block index with any unmasked element for kv block j."""
    return (j * k_block) // q_block


def _window_j_min(i: int, q_block: int, k_block: int, window: int):
    """First kv block with any in-window element for q block i
    (sliding window: only keys with q_pos − k_pos < window count; the
    earliest relevant k_pos for this q block is i·q_block − window + 1).
    """
    lo = i * q_block - window + 1
    return jnp.maximum(lo, 0) // k_block


def _window_i_max(j: int, q_block: int, k_block: int, window: int):
    """Last q block with any in-window element for kv block j (largest
    relevant q_pos is (j+1)·k_block − 1 + window − 1)."""
    return ((j + 1) * k_block - 1 + window - 1) // q_block


def _block_mask(s, qb_idx, kb_idx, q_block, k_block, causal, q_seg, k_seg,
                window=0):
    """Apply causal/sliding-window/segment masking to a
    [q_block, k_block] score tile.

    Only called where it can matter: causal masking only on
    diagonal-straddling blocks (callers prune/skip fully-masked blocks).
    ``window`` > 0 (Mistral-style local attention, parity: flash_attn
    window_size) additionally masks keys more than window−1 positions
    behind the query.
    """
    mask = None
    if causal or window:
        q_pos = qb_idx * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, k_block), 0
        )
        k_pos = kb_idx * k_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, k_block), 1
        )
        mask = q_pos >= k_pos
        if window:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    if q_seg is not None:
        seg = q_seg == k_seg  # [q_block, 1] == [1, k_block] -> broadcast
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, sm_scale, causal, q_block, k_block, n_kb,
                with_lse, with_segments, window):
    if with_segments:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, *out_refs = refs
    else:
        q_ref, k_ref, v_ref, *out_refs = refs
        qseg_ref = kseg_ref = None
    if with_lse:
        o_ref, lse_ref, m_scratch, l_scratch, acc_scratch = out_refs
    else:
        o_ref, m_scratch, l_scratch, acc_scratch = out_refs
        lse_ref = None

    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _step():
        q = q_ref[0, 0]  # [q_block, d]
        k = k_ref[0]  # [k_block, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        q_seg = qseg_ref[0][:, :1] if qseg_ref is not None else None
        k_seg = kseg_ref[...][:1, :] if kseg_ref is not None else None
        if causal or window or q_seg is not None:
            s = _block_mask(s, i, j, q_block, k_block, causal, q_seg,
                            k_seg, window)

        m_prev = m_scratch[:, :1]  # [q_block, 1]
        l_prev = l_scratch[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [q_block, k_block] fp32
        alpha = jnp.exp(m_prev - m_new)  # [q_block, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        v = v_ref[0]  # [k_block, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    # pruned iterations (causal: fully above the diagonal; window:
    # fully behind the window) do no work; the kv index map clamps their
    # block index so they issue no DMA either.
    if causal and window:
        pl.when(jnp.logical_and(
            j <= _causal_j_max(i, q_block, k_block),
            j >= _window_j_min(i, q_block, k_block, window)))(_step)
    elif causal:
        pl.when(j <= _causal_j_max(i, q_block, k_block))(_step)
    else:
        _step()

    @pl.when(j == n_kb - 1)
    def _finalize():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_scratch[:, :1] + jnp.log(l)  # [q_block, 1]
            lse_ref[0, 0] = jnp.broadcast_to(lse, (q_block, LANES))


def _mha_fwd_impl(q, k, v, qseg, kseg, sm_scale, causal, q_block, k_block,
                  return_lse=False, window=0):
    """q: [g, rep, sq, d]; k, v: [g, sk, d]; g = batch * kv_heads.

    qseg: [g, sq, LANES] int32 or None; kseg: [g, sk] int32 or None.
    """
    g, rep, sq, d = q.shape
    sk = k.shape[1]
    n_qb = sq // q_block
    n_kb = sk // k_block

    grid = (g, rep, n_qb, n_kb)

    def kv_index(b, r, i, j):
        if causal:
            j = jnp.minimum(j, _causal_j_max(i, q_block, k_block))
        if window:
            j = jnp.maximum(j, _window_j_min(i, q_block, k_block, window))
        return (b, j, 0)

    q_spec = pl.BlockSpec((1, 1, q_block, d), lambda b, r, i, j: (b, r, i, 0))
    k_spec = pl.BlockSpec((1, k_block, d), kv_index)
    o_spec = q_spec
    in_specs = [q_spec, k_spec, k_spec]
    inputs = [q, k, v]
    if qseg is not None:
        in_specs.append(pl.BlockSpec((1, q_block, LANES),
                                     lambda b, r, i, j: (b, i, 0)))
        in_specs.append(pl.BlockSpec(
            (1, k_block),
            (lambda b, r, i, j: (b, kv_index(b, r, i, j)[1]))))
        inputs += [qseg, kseg]
    scratch = [
        pltpu.VMEM((q_block, LANES), jnp.float32),
        pltpu.VMEM((q_block, LANES), jnp.float32),
        pltpu.VMEM((q_block, d), jnp.float32),
    ]
    flops = 4 * g * rep * sq * sk * d // (2 if causal else 1)
    cost = pl.CostEstimate(
        flops=flops,
        bytes_accessed=(q.size + 2 * g * sk * d + q.size) * 2,
        transcendentals=g * rep * sq * sk // (2 if causal else 1),
    )
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, q_block=q_block,
        k_block=k_block, n_kb=n_kb, with_lse=return_lse,
        with_segments=qseg is not None, window=window,
    )
    params = _params("parallel", "parallel", "parallel", "arbitrary")
    if not return_lse:
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((g, rep, sq, d), q.dtype),
            scratch_shapes=scratch,
            cost_estimate=cost,
            compiler_params=params,
            interpret=_interpret(),
        )(*inputs)
    lse_spec = pl.BlockSpec((1, 1, q_block, LANES),
                            lambda b, r, i, j: (b, r, i, 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(o_spec, lse_spec),
        out_shape=(
            jax.ShapeDtypeStruct((g, rep, sq, d), q.dtype),
            jax.ShapeDtypeStruct((g, rep, sq, LANES), jnp.float32),
        ),
        scratch_shapes=scratch,
        cost_estimate=cost,
        compiler_params=params,
        interpret=_interpret(),
    )(*inputs)
    return o, lse[:, :, :, 0]


# ---------------------------------------------------------------------------
# backward: dq pass (grid k-innermost, dq accumulates in VMEM scratch)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, sm_scale, causal, q_block, k_block, n_kb,
                   with_segments, window):
    if with_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dq_ref, dq_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         dq_scratch) = refs
        qseg_ref = kseg_ref = None

    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    def _step():
        q = q_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        # matmul operands stay in the INPUT dtype (bf16 in training) with
        # f32 accumulation — flash-v2 precision. f32 operands would run
        # the MXU at half rate on v5e/v5p.
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        q_seg = qseg_ref[0][:, :1] if qseg_ref is not None else None
        k_seg = kseg_ref[...][:1, :] if kseg_ref is not None else None

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal or window or q_seg is not None:
            s = _block_mask(s, i, j, q_block, k_block, causal, q_seg,
                            k_seg, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_scratch[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal and window:
        pl.when(jnp.logical_and(
            j <= _causal_j_max(i, q_block, k_block),
            j >= _window_j_min(i, q_block, k_block, window)))(_step)
    elif causal:
        pl.when(j <= _causal_j_max(i, q_block, k_block))(_step)
    else:
        _step()

    @pl.when(j == n_kb - 1)
    def _fin():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv pass (grid (rep, q)-innermost, dk/dv accumulate in VMEM;
# the GQA group-sum over rep happens in the same accumulator)
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(*refs, sm_scale, causal, q_block, k_block, n_qb, rep,
                    with_segments, window):
    if with_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref, dk_scratch, dv_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_scratch, dv_scratch) = refs
        qseg_ref = kseg_ref = None

    j = pl.program_id(1)
    r = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_and(r == 0, i == 0))
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def _step():
        q = q_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        # input-dtype matmul operands, f32 accumulation (see dq kernel)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        q_seg = qseg_ref[0][:, :1] if qseg_ref is not None else None
        k_seg = kseg_ref[...][:1, :] if kseg_ref is not None else None

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal or window or q_seg is not None:
            s = _block_mask(s, i, j, q_block, k_block, causal, q_seg,
                            k_seg, window)
        p = jnp.exp(s - lse)  # [q_block, k_block] f32
        # dv += p^T do
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dk += ds^T q
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal and window:
        pl.when(jnp.logical_and(
            i >= _causal_i_min(j, q_block, k_block),
            i <= _window_i_max(j, q_block, k_block, window)))(_step)
    elif causal:
        pl.when(i >= _causal_i_min(j, q_block, k_block))(_step)
    else:
        _step()

    @pl.when(jnp.logical_and(r == rep - 1, i == n_qb - 1))
    def _fin():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# backward: FUSED single pass (flash-v2 backward proper).
#
# The two-pass layout above runs 7 tile-matmuls (s and dp are computed
# twice) and the full exp/mask/ds VPU chain twice — and the round-4
# profile showed the backward VPU-bound at ~31% of roofline. This kernel
# computes s/p/dp/ds ONCE per (j, i) tile and emits all three gradients:
# dk/dv accumulate in VMEM scratch exactly as before (j is the outer
# grid dim), while dq — whose natural accumulation order is transposed —
# is written as per-j f32 PARTIALS [g, n_kb, rep, sq, d] that one XLA
# reduction folds afterwards. 5 tile-matmuls, one VPU chain; extra HBM
# is n_kb x sizeof(dq) for the partials, so the fused path is gated to
# small n_kb (large k_block keeps n_kb = seq/1024) and falls back to the
# two-pass kernels beyond it. Races: every partial block is written by
# exactly one grid step; fully-masked steps zero-fill theirs.
# ---------------------------------------------------------------------------
_FUSED_BWD_MAX_KB = 4


def _bwd_fused_kernel(*refs, sm_scale, causal, q_block, k_block, n_qb, rep,
                      with_segments, window):
    if with_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dqp_ref, dk_ref, dv_ref, dk_scratch, dv_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dqp_ref,
         dk_ref, dv_ref, dk_scratch, dv_scratch) = refs
        qseg_ref = kseg_ref = None

    j = pl.program_id(1)
    r = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_and(r == 0, i == 0))
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def _step():
        q = q_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        q_seg = qseg_ref[0][:, :1] if qseg_ref is not None else None
        k_seg = kseg_ref[...][:1, :] if kseg_ref is not None else None

        # input-dtype matmul operands, f32 accumulation (flash-v2)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal or window or q_seg is not None:
            s = _block_mask(s, i, j, q_block, k_block, causal, q_seg,
                            k_seg, window)
        p = jnp.exp(s - lse)  # computed ONCE for all three grads
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dqp_ref[0, 0, 0] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dqp_ref.dtype)

    def _skip():
        # fully-masked tile: its dq partial block must still be defined
        dqp_ref[0, 0, 0] = jnp.zeros_like(dqp_ref[0, 0, 0])

    if causal and window:
        live = jnp.logical_and(
            i >= _causal_i_min(j, q_block, k_block),
            i <= _window_i_max(j, q_block, k_block, window))
        pl.when(live)(_step)
        pl.when(jnp.logical_not(live))(_skip)
    elif causal:
        live = i >= _causal_i_min(j, q_block, k_block)
        pl.when(live)(_step)
        pl.when(jnp.logical_not(live))(_skip)
    else:
        _step()

    @pl.when(jnp.logical_and(r == rep - 1, i == n_qb - 1))
    def _fin():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _mha_bwd_impl(q, k, v, o, do, lse, qseg, kseg, sm_scale, causal,
                  q_block, k_block, dlse=None, window=0):
    g, rep, sq, d = q.shape
    sk = k.shape[1]
    n_qb = sq // q_block
    n_kb = sk // k_block
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # lse cotangent folds into delta: ds = p*(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)
    # lane-broadcast the per-row vectors to a 128 minor dim (TPU tiling)
    lse_b = jnp.broadcast_to(lse[..., None], (g, rep, sq, LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (g, rep, sq, LANES))

    q_spec = pl.BlockSpec((1, 1, q_block, d), lambda b, r, i, j: (b, r, i, 0))
    row_spec = pl.BlockSpec((1, 1, q_block, LANES),
                            lambda b, r, i, j: (b, r, i, 0))

    def kv_index(b, r, i, j):
        if causal:
            j = jnp.minimum(j, _causal_j_max(i, q_block, k_block))
        if window:
            j = jnp.maximum(j, _window_j_min(i, q_block, k_block, window))
        return (b, j, 0)

    k_spec = pl.BlockSpec((1, k_block, d), kv_index)
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    inputs = [q, k, v, do, lse_b, delta_b]
    if qseg is not None:
        in_specs.append(pl.BlockSpec((1, q_block, LANES),
                                     lambda b, r, i, j: (b, i, 0)))
        in_specs.append(pl.BlockSpec(
            (1, k_block), lambda b, r, i, j: (b, kv_index(b, r, i, j)[1])))
        inputs += [qseg, kseg]

    fused = n_kb <= _FUSED_BWD_MAX_KB
    if not fused:
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                q_block=q_block, k_block=k_block, n_kb=n_kb,
                with_segments=qseg is not None, window=window,
            ),
            grid=(g, rep, n_qb, n_kb),
            in_specs=in_specs,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((g, rep, sq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((q_block, d), jnp.float32)],
            cost_estimate=pl.CostEstimate(
                flops=6 * g * rep * sq * sk * d // (2 if causal else 1),
                bytes_accessed=4 * g * rep * sq * d * 2 + 2 * g * sk * d * 2,
                transcendentals=g * rep * sq * sk // (2 if causal else 1),
            ),
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=_interpret(),
        )(*inputs)

    # dk/dv pass (fused: + dq partials): grid reordered (g, kb, rep, qb)
    def q_index2(b, j, r, i):
        if causal:
            i = jnp.maximum(i, _causal_i_min(j, q_block, k_block))
        if window:
            i = jnp.minimum(i, _window_i_max(j, q_block, k_block, window))
        return (b, r, i, 0)

    q_spec2 = pl.BlockSpec((1, 1, q_block, d), q_index2)
    row_spec2 = pl.BlockSpec(
        (1, 1, q_block, LANES),
        lambda b, j, r, i: q_index2(b, j, r, i))
    kv_spec2 = pl.BlockSpec((1, k_block, d), lambda b, j, r, i: (b, j, 0))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    if qseg is not None:
        in_specs2.append(pl.BlockSpec(
            (1, q_block, LANES),
            lambda b, j, r, i: (b, q_index2(b, j, r, i)[2], 0)))
        in_specs2.append(pl.BlockSpec((1, k_block),
                                      lambda b, j, r, i: (b, j)))

    if fused:
        dqp_spec = pl.BlockSpec(
            (1, 1, 1, q_block, d), lambda b, j, r, i: (b, j, r, i, 0))
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
                q_block=q_block, k_block=k_block, n_qb=n_qb, rep=rep,
                with_segments=qseg is not None, window=window,
            ),
            grid=(g, n_kb, rep, n_qb),
            in_specs=in_specs2,
            out_specs=(dqp_spec, kv_spec2, kv_spec2),
            out_shape=(
                jax.ShapeDtypeStruct((g, n_kb, rep, sq, d), jnp.float32),
                jax.ShapeDtypeStruct((g, sk, d), q.dtype),
                jax.ShapeDtypeStruct((g, sk, d), q.dtype),
            ),
            scratch_shapes=[
                pltpu.VMEM((k_block, d), jnp.float32),
                pltpu.VMEM((k_block, d), jnp.float32),
            ],
            cost_estimate=pl.CostEstimate(
                flops=10 * g * rep * sq * sk * d // (2 if causal else 1),
                bytes_accessed=(4 * g * rep * sq * d * 2
                                + 2 * g * sk * d * 2
                                + 4 * g * n_kb * rep * sq * d),
                transcendentals=g * rep * sq * sk
                // (2 if causal else 1),
            ),
            compiler_params=_params("parallel", "parallel", "arbitrary",
                                    "arbitrary"),
            interpret=_interpret(),
        )(*inputs)
        dq = dq_part.sum(axis=1).astype(q.dtype)
        return dq, dk, dv

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            q_block=q_block, k_block=k_block, n_qb=n_qb, rep=rep,
            with_segments=qseg is not None, window=window,
        ),
        grid=(g, n_kb, rep, n_qb),
        in_specs=in_specs2,
        out_specs=(kv_spec2, kv_spec2),
        out_shape=(
            jax.ShapeDtypeStruct((g, sk, d), q.dtype),
            jax.ShapeDtypeStruct((g, sk, d), q.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((k_block, d), jnp.float32),
            pltpu.VMEM((k_block, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=8 * g * rep * sq * sk * d // (2 if causal else 1),
            bytes_accessed=4 * g * rep * sq * d * 2 + 2 * g * sk * d * 2,
            transcendentals=g * rep * sq * sk // (2 if causal else 1),
        ),
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=_interpret(),
    )(*inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP over the folded [g, rep, s, d] layout
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _mha_folded(q, k, v, qseg, kseg, sm_scale, causal, q_block, k_block,
                window):
    return _mha_fwd_impl(q, k, v, qseg, kseg, sm_scale, causal, q_block,
                         k_block, window=window)


def _mha_folded_fwd(q, k, v, qseg, kseg, sm_scale, causal, q_block, k_block,
                    window):
    o, lse = _mha_fwd_impl(q, k, v, qseg, kseg, sm_scale, causal, q_block,
                           k_block, return_lse=True, window=window)
    return o, (q, k, v, o, lse, qseg, kseg)


def _mha_folded_bwd(sm_scale, causal, q_block, k_block, window, res, do):
    q, k, v, o, lse, qseg, kseg = res
    dq, dk, dv = _mha_bwd_impl(q, k, v, o, do, lse, qseg, kseg, sm_scale,
                               causal, q_block, k_block, window=window)
    return dq, dk, dv, None, None


_mha_folded.defvjp(_mha_folded_fwd, _mha_folded_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _mha_lse_folded(q, k, v, qseg, kseg, sm_scale, causal, q_block, k_block,
                    window):
    """Like _mha_folded but also returns logsumexp — the merge statistic
    ring/context-parallel attention needs to combine per-block results."""
    return _mha_fwd_impl(q, k, v, qseg, kseg, sm_scale, causal, q_block,
                         k_block, return_lse=True, window=window)


def _mha_lse_folded_fwd(q, k, v, qseg, kseg, sm_scale, causal, q_block,
                        k_block, window):
    o, lse = _mha_fwd_impl(q, k, v, qseg, kseg, sm_scale, causal, q_block,
                           k_block, return_lse=True, window=window)
    return (o, lse), (q, k, v, o, lse, qseg, kseg)


def _mha_lse_folded_bwd(sm_scale, causal, q_block, k_block, window, res,
                        cts):
    q, k, v, o, lse, qseg, kseg = res
    do, dlse = cts
    dq, dk, dv = _mha_bwd_impl(q, k, v, o, do, lse, qseg, kseg, sm_scale,
                               causal, q_block, k_block, dlse=dlse,
                               window=window)
    return dq, dk, dv, None, None


_mha_lse_folded.defvjp(_mha_lse_folded_fwd, _mha_lse_folded_bwd)


SegmentIds = Tuple[jax.Array, jax.Array]


def _fold(q, k, v, segment_ids, q_block, k_block):
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hq % hk:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hk}")
    rep = hq // hk
    # Unaligned head_dim (64/96 in GPT/ViT configs): zero-pad to the lane
    # width. Exact — padded dims contribute 0 to q·k scores and 0 to the
    # padded output columns, which the caller slices off. sm_scale is
    # computed from the TRUE d by the caller before padding. Cheaper than
    # falling back to dense XLA attention, which materializes [sq, sk].
    if d % LANES:
        d_pad = ((d + LANES - 1) // LANES) * LANES
        pad = [(0, 0)] * 3 + [(0, d_pad - d)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        d = d_pad
    # choose blocks that tile the sequence exactly: prefer the requested
    # block, else halve until one divides (any 128-multiple seq len
    # divides at 128)
    def _fit(blk, sl):
        blk = min(blk, sl)
        while blk > 128 and sl % blk:
            blk //= 2
        if sl % blk:
            # requested block shares no power-of-two divisor with the
            # seq (e.g. 768 vs 2048) — fall back to the universal 128
            blk = 128
        return blk

    qb = _fit(q_block, sq)
    kb = _fit(k_block, sk)
    if sq % qb or sk % kb:
        raise ValueError(
            f"seq lens ({sq}, {sk}) must be multiples of 128")

    # [b, s, h, d] -> q: [b*hk, rep, sq, d]; kv: [b*hk, sk, d]
    qf = q.transpose(0, 2, 1, 3).reshape(b, hk, rep, sq, d)
    qf = qf.reshape(b * hk, rep, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)

    qseg = kseg = None
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            q_ids, kv_ids = segment_ids
        else:
            q_ids = kv_ids = segment_ids
        q_ids = jnp.asarray(q_ids, jnp.int32)
        kv_ids = jnp.asarray(kv_ids, jnp.int32)
        # replicate per kv-head group: [b, s] -> [b*hk, ...]
        qseg = jnp.broadcast_to(q_ids[:, None, :, None],
                                (b, hk, sq, LANES)).reshape(b * hk, sq, LANES)
        kseg = jnp.broadcast_to(kv_ids[:, None, :],
                                (b, hk, sk)).reshape(b * hk, sk)
    return qf, kf, vf, qseg, kseg, qb, kb


def mha(q, k, v, causal: bool = False, sm_scale: Optional[float] = None,
        q_block: int = DEFAULT_Q_BLOCK, k_block: int = DEFAULT_K_BLOCK,
        segment_ids: Optional[Union[jax.Array, SegmentIds]] = None,
        window: int = 0):
    """Flash attention over [batch, seq, heads, head_dim].

    GQA (kv_heads < q_heads) is handled inside the kernel's index maps —
    kv is never replicated in HBM. ``segment_ids`` enables varlen/packed
    attention (parity: flash_attn_varlen): either one [b, s] int array
    (self-attention) or a (q_ids [b, sq], kv_ids [b, sk]) pair; tokens
    attend only where ids match.
    """
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    if window and not causal:
        raise ValueError("sliding window requires causal=True")
    qf, kf, vf, qseg, kseg, qb, kb = _fold(q, k, v, segment_ids,
                                           q_block, k_block)
    of = _mha_folded(qf, kf, vf, qseg, kseg, sm_scale, causal, qb, kb,
                     window)
    of = of.reshape(b, hq, sq, of.shape[-1]).transpose(0, 2, 1, 3)
    return of[..., :d]  # drop lane padding for unaligned head_dim


def mha_with_lse(q, k, v, causal: bool = False,
                 sm_scale: Optional[float] = None,
                 q_block: int = DEFAULT_Q_BLOCK,
                 k_block: int = DEFAULT_K_BLOCK,
                 segment_ids: Optional[Union[jax.Array, SegmentIds]] = None,
                 window: int = 0):
    """Flash attention that also returns logsumexp [b, heads, sq] — the
    statistic ring/context-parallel callers need to merge per-block
    partial results (fully differentiable, incl. the lse output)."""
    b, sq, hq, d = q.shape
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    if window and not causal:
        raise ValueError("sliding window requires causal=True")
    qf, kf, vf, qseg, kseg, qb, kb = _fold(q, k, v, segment_ids,
                                           q_block, k_block)
    of, lse = _mha_lse_folded(qf, kf, vf, qseg, kseg, sm_scale, causal,
                              qb, kb, window)
    o = of.reshape(b, hq, sq, of.shape[-1]).transpose(0, 2, 1, 3)
    return o[..., :d], lse.reshape(b, hq, sq)
