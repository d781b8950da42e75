"""Pallas weight-only quantized matmul (parity: phi ``weight_only_linear``,
paddle/phi/kernels/fusion/ weight-only int8/int4 GEMM via CUTLASS).

TPU-native design: the weight stays int8 (or int4 packed two-per-byte) in
HBM and is dequantized *inside the kernel* after the block is DMA'd to
VMEM — so HBM traffic is halved (int8) or quartered (int4) versus bf16.
That bandwidth saving is the entire value of weight-only quantization on
a decode-bound workload; the MXU still computes in bf16/f32, matching the
reference's approach (dequant-to-half + tensor-core GEMM) rather than
true int8 arithmetic.

Group-wise scales: ``scale[g, n]`` covers rows ``[g*group_size, (g+1)*
group_size)`` of the ``[k, n]`` weight. ``k_block`` must be a multiple of
``group_size`` (or group_size >= k_block and divisible) so each kernel
block sees whole groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from . import _backend


def quantize_weight_int8_grouped(w: jax.Array, group_size: int = 128):
    """Symmetric group-wise int8 along the in (k) axis.

    w: [k, n] → (q int8 [k, n], scale f32 [k // group_size, n]).
    """
    k, n = w.shape
    if k % group_size:
        raise ValueError(f"k={k} not divisible by group_size={group_size}")
    wf = w.astype(jnp.float32).reshape(k // group_size, group_size, n)
    amax = jnp.max(jnp.abs(wf), axis=1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q.reshape(k, n), scale[:, 0, :]


def _largest_group(k: int, group_size: int) -> int:
    """Largest divisor of ``k`` that is <= group_size (>= 1) — the
    suggestion the int4 error message offers."""
    g = min(group_size, k)
    while g > 1 and k % g:
        g -= 1
    return g


def quantize_weight_int4_grouped(w: jax.Array, group_size: int = 128):
    """Symmetric group-wise int4, packed two values per int8 byte along k.

    w: [k, n] → (packed int8 [k // 2, n], scale f32 [k // group_size, n]).
    Row 2i lives in the low nibble of packed row i, row 2i+1 in the high
    nibble (the order ``_unpack_int4`` inverts — pinned by test).
    """
    k, n = w.shape
    if k % 2:
        raise ValueError(
            f"int4 packing stores two rows per byte, so the in (k) "
            f"dimension must be even; got k={k}. Pad the weight with "
            f"one zero row (scales are per-group, a zero row is "
            f"exact) or keep this layer at int8.")
    if k % group_size:
        raise ValueError(
            f"k={k} is not divisible by group_size={group_size}: "
            f"group-wise scales cover whole [group_size, n] row "
            f"blocks. Pick a group_size that divides k (e.g. "
            f"group_size={_largest_group(k, group_size)}), or pass "
            f"group_size=k for one degenerate whole-column group — "
            f"WeightOnlyLinear does that fallback automatically.")
    wf = w.astype(jnp.float32).reshape(k // group_size, group_size, n)
    amax = jnp.max(jnp.abs(wf), axis=1, keepdims=True)
    scale = jnp.maximum(amax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int8).reshape(k, n)
    lo = q[0::2] & 0xF
    hi = (q[1::2] & 0xF) << 4
    return (lo | hi).astype(jnp.int8), scale[:, 0, :]


def _unpack_int4(packed: jax.Array) -> jax.Array:
    """[k//2, n] packed → [k, n] int32 in [-8, 7] (sign-extended nibbles).

    Mosaic-friendly formulation: no row interleave (stack/reshape of the
    sublane dim doesn't lower) — duplicate each packed row, then select
    the low/high nibble by row parity with a broadcast iota.
    """
    kk, n = packed.shape
    rep = jnp.repeat(packed.astype(jnp.int32), 2, axis=0)  # [k, n]
    parity = jax.lax.broadcasted_iota(jnp.int32, (2 * kk, n), 0) % 2
    nib = (rep >> (parity * 4)) & 0xF
    return (nib ^ 8) - 8


def _dequant_block(wq, scale_blk, group_size, k_block, out_dtype):
    """wq [k_block, n_block] int8 + scale [k_block//group_size, n_block]
    → dequantized [k_block, n_block] in out_dtype."""
    groups = k_block // group_size
    w = wq.astype(jnp.float32).reshape(groups, group_size, -1)
    w = w * scale_blk.astype(jnp.float32)[:, None, :]
    return w.reshape(k_block, -1).astype(out_dtype)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, group_size, k_block,
            n_k_blocks, is_int4):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    wq = w_ref[...]
    if is_int4:
        wq = _unpack_int4(wq)
    w = _dequant_block(wq, s_ref[0], group_size, k_block, x_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "weight_dtype", "m_block", "n_block",
                     "k_block"))
def weight_only_matmul_pallas(x, qweight, scale, *, group_size=128,
                              weight_dtype="int8", m_block=256, n_block=256,
                              k_block=256):
    """y = x @ dequant(qweight). x [m, k]; qweight int8 [k, n] (int8) or
    [k//2, n] (int4 packed); scale [k//group_size, n]."""
    is_int4 = weight_dtype == "int4"
    m, k = x.shape
    n = qweight.shape[1]
    if is_int4 and qweight.shape[0] * 2 != k:
        raise ValueError("packed int4 weight must have k/2 rows")
    if not is_int4 and qweight.shape[0] != k:
        raise ValueError("int8 weight must have k rows")
    m_block = min(m_block, m)
    n_block = min(n_block, n)
    k_block = min(k_block, k)
    if m % m_block or n % n_block or k % k_block:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) not divisible by blocks "
            f"({m_block},{k_block},{n_block})")
    if k_block % group_size:
        raise ValueError(
            f"k_block={k_block} must be a multiple of group_size={group_size}")
    grid = (m // m_block, n // n_block, k // k_block)
    kern = functools.partial(
        _kernel, group_size=group_size, k_block=k_block,
        n_k_blocks=grid[2], is_int4=is_int4)
    wrows = k_block // 2 if is_int4 else k_block
    # scale goes in as [n_k_blocks, groups_per_k_block, n]: Mosaic needs
    # the last-two block dims divisible by (8, 128) OR equal to the full
    # array dims; groups_per_k_block is tiny, so make it a full dim.
    gpb = k_block // group_size
    scale3 = scale.reshape(grid[2], gpb, n)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_block, k_block), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((wrows, n_block), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((1, gpb, n_block), lambda i, j, kb: (kb, 0, j)),
        ],
        out_specs=pl.BlockSpec((m_block, n_block), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_block, n_block), jnp.float32)],
        interpret=_backend.interpret(),
    )(x, qweight, scale3)


def weight_only_matmul_xla(x, qweight, scale, *, group_size=128,
                           weight_dtype="int8"):
    """Reference XLA path (also the small-shape fallback): dequantize then
    matmul; XLA fuses the scale multiply into the dot's operand."""
    if weight_dtype == "int4":
        qweight = _unpack_int4(qweight)
    k, n = qweight.shape
    w = qweight.astype(jnp.float32).reshape(k // group_size, group_size, n)
    w = (w * scale.astype(jnp.float32)[:, None, :]).reshape(k, n)
    return jnp.matmul(x, w.astype(x.dtype))
