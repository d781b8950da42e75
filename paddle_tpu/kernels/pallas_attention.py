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
  - causal pruning works at two grains. Grid tiles wholly above the
    diagonal (or behind the sliding window) are skipped: the index maps
    clamp their block (a revisited block issues no DMA) and the body
    sits under pl.when. A square tile the diagonal or the window's edge
    crosses is worked as a statically unrolled list of 128-row strips,
    each against the one run of k columns it can see (_tile_strips):
    what lies above the diagonal inside the tile is not computed, and
    the iota mask is built only over the 128 columns the diagonal
    crosses. causal_live_share() is the area computed over the square:
    at s = 2048 in 1024-blocks 0.53 where the triangle is 0.50 and
    whole tiles were 0.75; 0.52 at s = 4096, 0.51 at 8192. Tiles with
    nothing to prune run whole, as one pair of large matmuls; so do
    non-causal calls, non-square blocks and blocks of 128-256.
  - backward is ONE fused kernel at every shape (_bwd_kernel): s/p/dp/ds
    once per strip for all three gradients; dq accumulates in float32
    VMEM scratch over the k blocks, dk/dv over (rep, q blocks), which
    also sums the GQA group. Its grid streams q blocks innermost and
    keeps the group's whole dq while that fits _WHOLE_DQ_VMEM_BUDGET,
    else streams k blocks innermost and keeps the group's whole dk/dv,
    whose size does not grow with the GQA group. No gradient has HBM
    partials; every gradient's HBM footprint equals its final size.
    delta = rowsum(o · dO) is computed in the kernel from the o and dO
    blocks.
  - varlen/packed sequences via segment ids (parity with
    flash_attn_varlen): tokens attend only within equal segment id;
    padding can be given a sentinel segment.
  - blocks are MXU-aligned; all matmuls request fp32 accumulation via
    preferred_element_type; per-row stats are carried lane-broadcast
    ([q_block, 128]) to keep Mosaic layouts trivial. lse is held from
    forward to backward compact ([g, rep, sq]) and broadcast again there.

Readings on one TPU v5e (PR 30's chip runs, PERF.md section 6: the
kernels alone at the benchmark cell's shape, b 2, s 2048, 32 heads over
8, d 128, bf16, causal; ms a call, forward / backward; the required
work at the chip's 197 TFLOP/s is 0.35 / 0.87):
  before PR 30: whole 1024-tiles, a mask on every tile   0.86 / 1.47
      (and 0.18 more summing float32 dq partials from HBM)
  whole tiles, a static mask on the diagonal tiles only  0.76 / 1.44
  grid blocks of 512 and of 256 instead (wall time of forward and of
      forward + backward, 1.14 and 3.43 at 1024): 1.76, 4.36; 3.14, 8.66
  4 to 8 strips a tile, written strip by strip           0.87-0.95 / 1.20-1.33
  8 strips of 128 rows, every score matmul first (forward), the next
      strip's matmuls issued ahead (backward): this file 0.68 / 1.09
  16 strips of 64 rows, same orders                      0.81-0.84 / 1.33-1.54
Mosaic keeps to program order, so HOW the strips are written decides
whether a strip's VPU work runs under its neighbours' matmuls; under
128 rows a strip loses more at the MXU than it prunes.

The backward at the four train cells' shapes (PR 41's chip runs, PERF.md
section 6: bf16, d 128, causal, blocks 1024; ms a call, before PR 41 /
this file, and the grid order not taken):
  g 8, rep 4, s 8192 (LFM2): two calls 12.14 / k innermost 8.86
  g 2, rep 16, s 8192 (Nemotron): two calls 12.07 / k innermost 8.79
  g 16, rep 4, s 2048 (Mistral): 1.25 / q innermost 1.25; k 1.32
  g 10, rep 2, s 8192, window 512 (Phi-4-mini-flash): 1.53 / 1.53; k 1.49
  the same, full and cross-attention: 5.30 / 5.30; k innermost 5.55
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

# Blocks are the GRID tiles: few grid steps and large DMAs (2048-blocks
# do not fit VMEM). The causal triangle is cut finer than that inside
# the kernels, see _tile_strips.
DEFAULT_Q_BLOCK = 1024
DEFAULT_K_BLOCK = 1024
NEG_INF = -1e30
LANES = 128
# rows of the strips a square causal grid tile is worked in, and the
# width of the column ranges its mask is built over
_STRIP = LANES
# what Mosaic grants a kernel on a v5e without being asked: every
# kernel's per-tile blocks and temporaries fit it at the default blocks
# (tests/test_chip_compile.py holds that); the backward asks for its
# whole-length accumulator on top
_TILE_VMEM_BYTES = 16 << 20
# the backward streams q blocks innermost while one kv group's whole dq
# fits this (s = 4096 at rep 4, s = 8192 at rep 2; d 128, bf16), and k
# blocks innermost beyond it (see _bwd_kernel)
_WHOLE_DQ_VMEM_BUDGET = 16 << 20


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


def _strip_rows(q_block: int, k_block: int, causal: bool = True) -> int:
    """Rows of the strips a causal grid tile is worked in, 0 where tiles
    are worked whole: non-causal calls have no diagonal, non-square
    tiles meet it at an offset only the grid step knows, and under four
    strips a block (128-256) is already as fine as the pruning gets."""
    if causal and q_block == k_block and q_block >= 4 * _STRIP:
        return _STRIP
    return 0


def _tile_strips(delta: int, blk: int, rows: int, window: int):
    """Static work list of the square causal grid tile ``delta`` block
    rows below the diagonal: ``((r0, r1, c0, c1, masks), ...)``, one
    entry for each strip of ``rows`` q rows (0: the tile whole) with the
    one run of k columns [c0, c1) it has to visit: the live columns of a
    row are a band, so a strip's are contiguous, and what lies wholly
    above the diagonal or behind the window is not computed at all.
    ``masks`` lists the column ranges of that run the diagonal or the
    window's edge crosses, as (ca, cb, off) relative to the run, ``off``
    = q_pos − k_pos at the range's corner; the columns between them take
    no mask. A tile with nothing to prune comes back as one whole-tile
    strip (one pair of large matmuls), a dead tile as ()."""
    h = rows or blk  # strips and mask ranges are h x h squares
    out = []
    for r0 in range(0, blk, h):
        run, masks = [], []
        for ca in range(0, blk, h):
            off = delta * blk + r0 - ca
            lo, hi = off - (h - 1), off + (h - 1)
            if hi < 0 or (window and lo >= window):
                continue
            run.append(ca)
            if lo < 0 or (window and hi >= window):
                masks.append((ca, ca + h, off))
        if run:
            c0 = run[0]
            out.append((r0, r0 + h, c0, run[-1] + h, tuple(
                (ca - c0, cb - c0, off) for ca, cb, off in masks)))
    if len(out) == blk // h and all(
            (c0, c1, m) == (0, blk, ()) for _, _, c0, c1, m in out):
        return ((0, blk, 0, blk, ()),)
    return tuple(out)


def causal_live_share(sq: int, sk: int, q_block: int, k_block: int,
                      rows: int, causal: bool = True, window: int = 0):
    """Area of the score square the kernels compute, over sq·sk, when
    square causal tiles are worked in strips of ``rows`` (0: whole grid
    tiles). A trace-time constant; the causal triangle itself is
    (sq + 1) / 2sq. s = 2048 in 1024-blocks: 0.75 whole (3 of 4 tiles),
    0.53125 in 128-row strips, against 0.50024 required."""
    if not causal:
        return 1.0
    live = 0
    for i in range(sq // q_block):
        for j in range(sk // k_block):
            if q_block == k_block:
                live += sum((r1 - r0) * (c1 - c0)
                            for r0, r1, c0, c1, _ in _tile_strips(
                                i - j, q_block, rows, window))
                continue
            lo = i * q_block - (j + 1) * k_block + 1
            hi = (i + 1) * q_block - 1 - j * k_block
            if hi >= 0 and not (window and lo >= window):
                live += q_block * k_block
    return live / (sq * sk)


def _tile_cases(i, j, n_qb, n_kb, q_block, k_block, causal, window):
    """The ways grid tile (i, j) is worked, as (condition, strips): the
    condition is on the program ids (None: always), the strips are
    _tile_strips' static work list. Square causal tiles differ only by
    i − j, so each distinct work list is unrolled once; steps no
    condition admits (above the diagonal, behind the window) do nothing,
    and the index maps clamp their block so they issue no DMA either.
    A non-square causal tile is worked whole under a mask whose offset
    is the step's own."""
    if not causal:
        return [(None, ((0, q_block, 0, k_block, ()),))]
    if q_block != k_block:
        live = j <= _causal_j_max(i, q_block, k_block)
        if window:
            live = jnp.logical_and(
                live, j >= _window_j_min(i, q_block, k_block, window))
        off = i * q_block - j * k_block
        return [(live, ((0, q_block, 0, k_block, ((0, k_block, off),)),))]
    rows = _strip_rows(q_block, k_block)
    runs = []  # [first delta, last delta, strips]
    for delta in range(1 - n_kb, n_qb):
        strips = _tile_strips(delta, q_block, rows, window)
        if runs and runs[-1][1] == delta - 1 and runs[-1][2] == strips:
            runs[-1][1] = delta
        elif strips:
            runs.append([delta, delta, strips])
    return [(jnp.logical_and(i - j >= lo, i - j <= hi), strips)
            for lo, hi, strips in runs]


def _for_tile_cases(cases, work):
    for cond, strips in cases:
        if cond is None:
            work(strips)
        else:
            pl.when(cond)(functools.partial(work, strips))


def _clamp_j(i, j, q_block, k_block, causal, window):
    """kv block the index maps fetch at step (i, j): pruned steps
    revisit a live block, which issues no DMA."""
    if causal:
        j = jnp.minimum(j, _causal_j_max(i, q_block, k_block))
    if window:
        j = jnp.maximum(j, _window_j_min(i, q_block, k_block, window))
    return j


def _clamp_i(i, j, q_block, k_block, causal, window):
    """q block fetched at step (i, j) where q is the streamed side."""
    if causal:
        i = jnp.maximum(i, _causal_i_min(j, q_block, k_block))
    if window:
        i = jnp.minimum(i, _window_i_max(j, q_block, k_block, window))
    return i


def _scores(q, k, sm_scale, masks, window, q_seg, k_seg):
    """Scaled q·kᵀ for one strip's run, float32, with what is masked set
    to NEG_INF. ``masks`` are the column ranges the positional mask
    touches (_tile_strips); the others pass through. ``window`` > 0
    (Mistral-style local attention, parity: flash_attn window_size) also
    masks keys more than window−1 positions behind the query; segment
    ids mask the whole run."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale

    def masked(part, off):
        diff = (off + jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, part.shape, 1))
        keep = diff >= 0
        if window:
            keep = jnp.logical_and(keep, diff < window)
        return jnp.where(keep, part, NEG_INF)

    if masks:
        parts, at = [], 0
        for ca, cb, off in masks:
            if ca > at:
                parts.append(s[:, at:ca])
            parts.append(masked(s[:, ca:cb], off))
            at = cb
        if at < s.shape[1]:
            parts.append(s[:, at:])
        s = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if q_seg is not None:
        # [rows, 1] == [1, cols] -> broadcast
        s = jnp.where(q_seg == k_seg, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, sm_scale, causal, q_block, k_block, n_qb, n_kb,
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

    def _tile(strips):
        # All the tile's score matmuls first, then strip by strip one
        # online-softmax update and its p·v. Mosaic keeps to program
        # order: written strip by strip the three stages run one after
        # the other and a strip costs more than it saves; in this order
        # a strip's VPU work runs under its neighbours' matmuls.
        scores = [
            _scores(q_ref[0, 0, r0:r1, :], k_ref[0, c0:c1, :], sm_scale,
                    masks, window,
                    qseg_ref[0, r0:r1, :1] if with_segments else None,
                    kseg_ref[:, c0:c1] if with_segments else None)
            for r0, r1, c0, c1, masks in strips]
        for (r0, r1, c0, c1, _), s in zip(strips, scores):
            m_prev = m_scratch[r0:r1, :1]  # [rows, 1]
            l_prev = l_scratch[r0:r1, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)  # [rows, cols] fp32
            alpha = jnp.exp(m_prev - m_new)  # [rows, 1]
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

            v = v_ref[0, c0:c1, :]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scratch[r0:r1] = acc_scratch[r0:r1] * alpha + pv
            m_scratch[r0:r1] = jnp.broadcast_to(m_new, (r1 - r0, LANES))
            l_scratch[r0:r1] = jnp.broadcast_to(l_new, (r1 - r0, LANES))

    _for_tile_cases(
        _tile_cases(i, j, n_qb, n_kb, q_block, k_block, causal, window),
        _tile)

    @pl.when(j == n_kb - 1)
    def _finalize():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_scratch[:, :1] + jnp.log(l)  # [q_block, 1]
            lse_ref[0, 0] = jnp.broadcast_to(lse, (q_block, LANES))


# The two calls below are jitted INLINE: every layer of a model makes the
# same call, and Pallas traces a kernel's body anew at each pallas_call
# (0.3 s a layer for the unrolled strips, forward and backward). jit's
# cache traces it once; ``inline`` leaves no jit in the name stack, so
# the kernels' events keep the names a bare call gives them. Trace-time
# module constants are baked into the cached trace: a test that patches
# one clears JAX's caches.
_STATIC = ("sm_scale", "causal", "q_block", "k_block", "window")


@functools.partial(jax.jit, inline=True,
                   static_argnames=_STATIC + ("return_lse",))
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
        return (b, _clamp_j(i, j, q_block, k_block, causal, window), 0)

    q_spec = pl.BlockSpec((1, 1, q_block, d), lambda b, r, i, j: (b, r, i, 0))
    k_spec = pl.BlockSpec((1, k_block, d), kv_index)
    o_spec = q_spec
    in_specs = [q_spec, k_spec, k_spec]
    inputs = [q, k, v]
    if qseg is not None:
        in_specs.append(pl.BlockSpec((1, q_block, LANES),
                                     lambda b, r, i, j: (b, i, 0)))
        in_specs.append(pl.BlockSpec(
            (1, k_block), lambda b, r, i, j: kv_index(b, r, i, j)[:2]))
        inputs += [qseg, kseg]
    scratch = [
        pltpu.VMEM((q_block, LANES), jnp.float32),
        pltpu.VMEM((q_block, LANES), jnp.float32),
        pltpu.VMEM((q_block, d), jnp.float32),
    ]
    live = g * rep * sq * sk * causal_live_share(
        sq, sk, q_block, k_block, _strip_rows(q_block, k_block, causal),
        causal, window)
    cost = pl.CostEstimate(
        flops=int(4 * live * d),
        bytes_accessed=(q.size + 2 * g * sk * d + q.size) * 2,
        transcendentals=int(live),
    )
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, q_block=q_block,
        k_block=k_block, n_qb=n_qb, n_kb=n_kb, with_lse=return_lse,
        with_segments=qseg is not None, window=window,
    )
    params = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))
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
            interpret=_backend.interpret(),
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
        interpret=_backend.interpret(),
    )(*inputs)
    return o, lse[:, :, :, 0]


# ---------------------------------------------------------------------------
# backward: ONE kernel (flash-v2 backward proper). s/p/dp/ds are computed
# once per strip and feed all three gradients: 5 matmuls and one VPU
# chain. dk/dv accumulate over (rep, q blocks), which also sums the GQA
# group, and dq over the k blocks, in float32 VMEM scratch, each written
# once in the input dtype. One of the two accumulators must live across
# the other's whole length; the grid order decides which:
#   q innermost, grid (g, kb, rep, qb): the group's whole dq,
#     rep·sq·d·(4 + 2·itemsize) bytes with its output block, while that
#     fits _WHOLE_DQ_VMEM_BUDGET; 5% faster a call than k innermost at
#     the Mistral and Phi-4-mini-flash cells' shapes (PR 41's chip runs);
#   k innermost, grid (g, rep, qb, kb), beyond it: the group's whole
#     dk/dv, 2·sk·d·(4 + 2·itemsize) bytes, 16 MiB at sk 8192, d 128,
#     bf16 whatever rep (a v5e core's 128 MiB holds sk to about 48k).
# Both sum dk/dv rep-major over the q blocks and dq over ascending k
# blocks, so their gradients are bitwise equal; keep those orders: the
# Nemotron cell's routers follow every rounding upstream of them
# (ROADMAP G1). Every gradient's HBM footprint equals its final size.
# delta = rowsum(o · dO) (less the lse cotangent, where a caller
# differentiates lse) is computed in the kernel from the o and dO blocks.
# ---------------------------------------------------------------------------
def _bwd_kernel(*refs, sm_scale, causal, q_block, k_block, n_qb, n_kb, rep,
                with_dlse, with_segments, window, q_inner):
    refs = iter(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = (
        next(refs) for _ in range(6))
    dlse_ref = next(refs) if with_dlse else None
    qseg_ref = next(refs) if with_segments else None
    kseg_ref = next(refs) if with_segments else None
    dq_ref, dk_ref, dv_ref, dq_scratch, dk_scratch, dv_scratch = refs

    if q_inner:
        j, r, i = (pl.program_id(a) for a in (1, 2, 3))
    else:
        r, i, j = (pl.program_id(a) for a in (1, 2, 3))
    # where the whole-length accumulator keeps this step's block
    slot = r * n_qb + i if q_inner else 0
    kv_at = () if q_inner else (j,)

    def dkv_step(r_, i_, j_):
        # the step that opens / closes the group's dk/dv accumulation
        at = jnp.logical_and(r == r_, i == i_)
        return at if q_inner else jnp.logical_and(at, j == j_)

    @pl.when(dkv_step(0, 0, 0))
    def _init_dkv():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    @pl.when(j == 0)
    def _init_dq():
        dq_scratch[slot] = jnp.zeros(dq_scratch.shape[1:], dq_scratch.dtype)

    def _products(strip):
        # the two matmuls of a strip that wait for no VPU work. Operands
        # stay in the INPUT dtype (bf16 in training) with f32
        # accumulation — flash-v2 precision. f32 operands would run the
        # MXU at half rate on v5e/v5p.
        r0, r1, c0, c1, masks = strip
        s = _scores(q_ref[0, 0, r0:r1, :], k_ref[0, c0:c1, :], sm_scale,
                    masks, window,
                    qseg_ref[0, r0:r1, :1] if with_segments else None,
                    kseg_ref[:, c0:c1] if with_segments else None)
        dp = jax.lax.dot_general(
            do_ref[0, 0, r0:r1, :], v_ref[0, c0:c1, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return s, dp

    def _tile(strips):
        # software-pipelined by hand (Mosaic keeps to program order): the
        # next strip's s and dp matmuls are issued before this strip's
        # exp/ds chain, which then runs under them
        ahead = _products(strips[0])
        for t, (r0, r1, c0, c1, _) in enumerate(strips):
            s, dp = ahead
            if t + 1 < len(strips):
                ahead = _products(strips[t + 1])
            q = q_ref[0, 0, r0:r1, :]
            do = do_ref[0, 0, r0:r1, :]
            lse = lse_ref[0, 0, r0:r1, :1]
            delta = jnp.sum(
                o_ref[0, 0, r0:r1, :].astype(jnp.float32)
                * do.astype(jnp.float32), axis=1, keepdims=True)
            if with_dlse:
                # lse cotangent folds into delta:
                # ds = p*(dp - (delta - dlse))
                delta = delta - dlse_ref[0, 0, r0:r1, :1]
            p = jnp.exp(s - lse)  # [rows, cols] f32, ONCE for all grads
            ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
            # dv += p^T do
            dv_scratch[(*kv_at, slice(c0, c1))] += jax.lax.dot_general(
                p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # dk += ds^T q
            dk_scratch[(*kv_at, slice(c0, c1))] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dq_scratch[slot, r0:r1] += jax.lax.dot_general(
                ds, k_ref[0, c0:c1, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _for_tile_cases(
        _tile_cases(i, j, n_qb, n_kb, q_block, k_block, causal, window),
        _tile)

    @pl.when(j == n_kb - 1)
    def _fin_dq():
        dq_ref[0, slot] = dq_scratch[slot].astype(dq_ref.dtype)

    @pl.when(dkv_step(rep - 1, n_qb - 1, n_kb - 1))
    def _fin_dkv():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=_STATIC)
def _mha_bwd_impl(q, k, v, o, do, lse, qseg, kseg, sm_scale, causal,
                  q_block, k_block, dlse=None, window=0):
    g, rep, sq, d = q.shape
    sk = k.shape[1]
    n_qb = sq // q_block
    n_kb = sk // k_block
    # the per-row vectors go in lane-broadcast to a 128 minor dim (TPU
    # tiling); only here, never as a residual held since the forward
    rows = [jnp.broadcast_to(x.astype(jnp.float32)[..., None],
                             (g, rep, sq, LANES))
            for x in (lse, dlse) if x is not None]
    live = g * rep * sq * sk * causal_live_share(
        sq, sk, q_block, k_block, _strip_rows(q_block, k_block, causal),
        causal, window)
    # the whole-length accumulators' float32 scratch and output blocks
    dq_vmem = rep * sq * d * (4 + 2 * q.dtype.itemsize)
    dkv_vmem = 2 * sk * d * (4 + 2 * q.dtype.itemsize)
    q_inner = dq_vmem <= _WHOLE_DQ_VMEM_BUDGET

    def step(*ids):  # grid ids -> (b, r, i, j)
        if q_inner:
            return ids[0], ids[2], ids[3], ids[1]
        return ids

    def q_index(*ids):
        b, r, i, j = step(*ids)
        if q_inner:
            i = _clamp_i(i, j, q_block, k_block, causal, window)
        return (b, r, i, 0)

    def kv_index(*ids):
        b, r, i, j = step(*ids)
        if not q_inner:
            j = _clamp_j(i, j, q_block, k_block, causal, window)
        return (b, j, 0)

    q_spec = pl.BlockSpec((1, 1, q_block, d), q_index)
    row_spec = pl.BlockSpec((1, 1, q_block, LANES), q_index)
    kv_spec = pl.BlockSpec((1, k_block, d), kv_index)
    in_specs = ([q_spec, kv_spec, kv_spec, q_spec, q_spec]
                + [row_spec] * len(rows))
    inputs = [q, k, v, o, do] + rows
    if qseg is not None:
        in_specs.append(pl.BlockSpec(
            (1, q_block, LANES), lambda *ids: (ids[0], q_index(*ids)[2], 0)))
        in_specs.append(pl.BlockSpec(
            (1, k_block), lambda *ids: kv_index(*ids)[:2]))
        inputs += [qseg, kseg]

    slots = rep * n_qb if q_inner else 1
    dq_spec = pl.BlockSpec(
        (1, slots, q_block, d),
        (lambda b, j, r, i: (b, 0, 0, 0)) if q_inner else
        (lambda b, r, i, j: (b, r * n_qb + i, 0, 0)))
    if q_inner:
        dkv_spec, dkv_shape, dkv_scratch = kv_spec, (g, sk, d), (k_block, d)
    else:  # one block of the group's whole kv length
        dkv_spec = pl.BlockSpec((1, n_kb, k_block, d),
                                lambda b, r, i, j: (b, 0, 0, 0))
        dkv_shape, dkv_scratch = (g, n_kb, k_block, d), (n_kb, k_block, d)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal,
            q_block=q_block, k_block=k_block, n_qb=n_qb, n_kb=n_kb,
            rep=rep, with_dlse=dlse is not None,
            with_segments=qseg is not None, window=window, q_inner=q_inner,
        ),
        grid=(g, n_kb, rep, n_qb) if q_inner else (g, rep, n_qb, n_kb),
        in_specs=in_specs,
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((g, rep * n_qb, q_block, d), q.dtype)]
        + [jax.ShapeDtypeStruct(dkv_shape, q.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((slots, q_block, d), jnp.float32)]
        + [pltpu.VMEM(dkv_scratch, jnp.float32)] * 2,
        cost_estimate=pl.CostEstimate(
            flops=int(2 * 5 * live * d),
            bytes_accessed=(5 * q.size + 4 * g * sk * d) * q.dtype.itemsize,
            transcendentals=int(live),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_TILE_VMEM_BYTES + (
                dq_vmem if q_inner else dkv_vmem),
        ),
        interpret=_backend.interpret(),
    )(*inputs)
    return (dq.reshape(g, rep, sq, d), dk.reshape(g, sk, d),
            dv.reshape(g, sk, d))


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
