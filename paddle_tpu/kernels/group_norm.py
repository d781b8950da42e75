"""Fused Pallas GroupNorm(+SiLU) for channels-last (NHWC) activations.

Parity: the reference's fused group_norm kernels
(paddle/phi/kernels/fusion/gpu/fused_groupnorm — GroupNormNHWC
forward/backward used by the SD-UNet path in ppdiffusers).

Why a kernel when XLA can fuse elementwise chains: GroupNorm is a
CASCADED reduction — per-(sample, group) moments over (H·W·C/G)
elements, then a normalize+affine(+SiLU) elementwise pass over the same
tensor. XLA compiles this as separate reduce and map fusions with the
activation streamed from HBM once per pass (2-3 reads + 1 write), and
under NCHW it additionally brackets the chain with relayout copies (the
round-5 SD-UNet capture: 40% of device time in {1,0,3,2}<->{0,1,3,2}
copies, 9.0% MFU). This kernel reads the activation from HBM ONCE,
keeps the (sample, group-block) tile VMEM-resident, computes moments +
normalize + affine + optional SiLU in one grid step, and writes once —
the RedFuser-style cascaded-reduction fusion, with the group-channel
reductions expressed as tiny one-hot matmuls so no lane-crossing
reshape is needed.

Moments use the numerically-stable two-pass form (mean first, then
centered second moment) — both passes run over the VMEM-resident tile,
so HBM sees a single pass; a streaming Welford merge is unnecessary at
these tile sizes and would cost extra VPU work.

Backward is a second fused kernel over the same tiling: recomputes
x̂ from saved per-group (mean, rstd), applies the SiLU cotangent chain
when the activation was fused, and emits dx in one read of (x, dy) +
one write, with per-(sample, block) dγ/dβ partials reduced outside (an
[n, c] array — negligible next to the activations).

Grid: ``(n, c // c_block)`` where ``c_block`` is a group-aligned
channel slab chosen to fit the VMEM budget; every group lies wholly
inside one slab, so each grid step owns its statistics. Tensors whose
per-sample slab exceeds the budget fall back to the lax reference
(``supports_fused`` returns False) — same numerics, still
transpose-free under the NHWC layout policy.

Interpreter mode (non-TPU backends) runs the same kernels via
``interpret=True``; ``group_norm_reference`` is the numeric source of
truth the tests compare against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _backend


# VMEM the fused path may assume per grid step: the backward holds
# x, dy, dx slabs in f32 plus the bf16 originals (~5 f32-slab
# equivalents); keep comfortably under the ~16 MB/core budget so the
# pipelined double-buffering still fits.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_F32_SLABS = 5  # worst-case resident f32 copies of one (hw, c_block) slab


def _pick_c_block(hw: int, c: int, cg: int):
    """Largest group-aligned channel slab that fits the VMEM budget.

    Doubles from one group's channels up (so every slab holds whole
    groups and ``c % c_block == 0``); None when even a single group's
    slab blows the budget."""
    if hw * cg * 4 * _F32_SLABS > VMEM_BUDGET_BYTES:
        return None
    blk = cg
    while (blk * 2 <= c and c % (blk * 2) == 0
           and hw * blk * 2 * 4 * _F32_SLABS <= VMEM_BUDGET_BYTES):
        blk *= 2
    return blk


def supports_fused(shape, num_groups: int) -> bool:
    """True when the fused kernel handles this NHWC shape in-budget."""
    if len(shape) != 4:
        return False
    n, h, w, c = shape
    if c % num_groups:
        return False
    return _pick_c_block(h * w, c, c // num_groups) is not None


def _group_matrix(c_block: int, groups_per_block: int):
    """[c_block, g_blk] one-hot group membership: matmul with it sums
    per-channel partials into per-group totals (and its transpose
    broadcasts per-group stats back per-channel) — no lane-crossing
    reshapes inside the kernel."""
    cg = c_block // groups_per_block
    ch = jnp.arange(c_block)[:, None]
    gr = jnp.arange(groups_per_block)[None, :]
    return (ch // cg == gr).astype(jnp.float32)


def _silu_grad(z, sig):
    # d silu(z)/dz with sig = sigmoid(z)
    return sig * (1.0 + z * (1.0 - sig))


def _gn_fwd_kernel(x_ref, gamma_ref, beta_ref, gmat_ref,
                   y_ref, mean_ref, rstd_ref, *, eps, act, inv_n):
    x = x_ref[0].astype(jnp.float32)          # [hw, c_blk]
    gmat = gmat_ref[...]                      # [c_blk, g_blk]
    # stable two-pass moments over the VMEM-resident slab
    mean_g = (jnp.sum(x, axis=0, keepdims=True) @ gmat) * inv_n  # [1, g_blk]
    mean_c = mean_g @ gmat.T                  # [1, c_blk]
    d = x - mean_c
    var_g = (jnp.sum(d * d, axis=0, keepdims=True) @ gmat) * inv_n
    rstd_g = jax.lax.rsqrt(var_g + eps)
    xhat = d * (rstd_g @ gmat.T)
    y = xhat * gamma_ref[...] + beta_ref[...]
    if act == "silu":
        y = y * jax.nn.sigmoid(y)
    y_ref[0] = y.astype(y_ref.dtype)
    mean_ref[...] = mean_g
    rstd_ref[...] = rstd_g


def _gn_bwd_kernel(x_ref, dy_ref, gamma_ref, beta_ref, gmat_ref,
                   mean_ref, rstd_ref,
                   dx_ref, dgamma_ref, dbeta_ref, *, act, inv_n):
    x = x_ref[0].astype(jnp.float32)          # [hw, c_blk]
    dy = dy_ref[0].astype(jnp.float32)
    gmat = gmat_ref[...]
    gamma = gamma_ref[...]                    # [1, c_blk]
    rstd_c = rstd_ref[...] @ gmat.T
    xhat = (x - mean_ref[...] @ gmat.T) * rstd_c
    dz = dy
    if act == "silu":
        z = xhat * gamma + beta_ref[...]
        sig = jax.nn.sigmoid(z)
        dz = dy * _silu_grad(z, sig)
    dgamma_ref[...] = jnp.sum(dz * xhat, axis=0, keepdims=True)[None]
    dbeta_ref[...] = jnp.sum(dz, axis=0, keepdims=True)[None]
    dxhat = dz * gamma
    m1 = (jnp.sum(dxhat, axis=0, keepdims=True) @ gmat) * inv_n
    m2 = (jnp.sum(dxhat * xhat, axis=0, keepdims=True) @ gmat) * inv_n
    dx = rstd_c * (dxhat - m1 @ gmat.T - xhat * (m2 @ gmat.T))
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _gn_fwd_pallas(x3, gamma, beta, num_groups, eps, act):
    """x3: [n, hw, c]. Returns (y [n, hw, c], mean [n, g], rstd [n, g])."""
    n, hw, c = x3.shape
    g = num_groups
    cg = c // g
    c_blk = _pick_c_block(hw, c, cg)
    g_blk = c_blk // cg
    gmat = _group_matrix(c_blk, g_blk)
    grid = (n, c // c_blk)
    kernel = functools.partial(_gn_fwd_kernel, eps=eps, act=act,
                               inv_n=1.0 / (hw * cg))
    f32 = jnp.float32
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((1, hw, c_blk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, c_blk), lambda i, j: (0, j)),
            pl.BlockSpec((1, c_blk), lambda i, j: (0, j)),
            pl.BlockSpec((c_blk, g_blk), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, hw, c_blk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, g_blk), lambda i, j: (i, j)),
            pl.BlockSpec((1, g_blk), lambda i, j: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, hw, c), x3.dtype),
            jax.ShapeDtypeStruct((n, g), f32),
            jax.ShapeDtypeStruct((n, g), f32),
        ),
        interpret=_backend.interpret(),
    )(x3, gamma.reshape(1, c).astype(f32), beta.reshape(1, c).astype(f32),
      gmat)


def _gn_bwd_pallas(x3, dy3, gamma, beta, mean, rstd, num_groups, act):
    n, hw, c = x3.shape
    g = num_groups
    cg = c // g
    c_blk = _pick_c_block(hw, c, cg)
    g_blk = c_blk // cg
    gmat = _group_matrix(c_blk, g_blk)
    grid = (n, c // c_blk)
    kernel = functools.partial(_gn_bwd_kernel, act=act,
                               inv_n=1.0 / (hw * cg))
    f32 = jnp.float32
    dx, dgam, dbeta = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((1, hw, c_blk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, hw, c_blk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, c_blk), lambda i, j: (0, j)),
            pl.BlockSpec((1, c_blk), lambda i, j: (0, j)),
            pl.BlockSpec((c_blk, g_blk), lambda i, j: (0, 0)),
            pl.BlockSpec((1, g_blk), lambda i, j: (i, j)),
            pl.BlockSpec((1, g_blk), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((1, hw, c_blk), lambda i, j: (i, 0, j)),
            # per-sample partials, reduced over n by the caller ([n, c]
            # f32 — noise next to the [n, hw, c] activations)
            pl.BlockSpec((1, 1, c_blk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, c_blk), lambda i, j: (i, 0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, hw, c), x3.dtype),
            jax.ShapeDtypeStruct((n, 1, c), f32),
            jax.ShapeDtypeStruct((n, 1, c), f32),
        ),
        interpret=_backend.interpret(),
    )(x3, dy3, gamma.reshape(1, c).astype(f32),
      beta.reshape(1, c).astype(f32), gmat, mean, rstd)
    return dx, dgam.sum(axis=(0, 1)), dbeta.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_group_norm3(x3, gamma, beta, num_groups, eps, act):
    y, _, _ = _gn_fwd_pallas(x3, gamma, beta, num_groups, eps, act)
    return y


def _fused_fwd(x3, gamma, beta, num_groups, eps, act):
    y, mean, rstd = _gn_fwd_pallas(x3, gamma, beta, num_groups, eps, act)
    return y, (x3, gamma, beta, mean, rstd)


def _fused_bwd(num_groups, eps, act, res, dy):
    x3, gamma, beta, mean, rstd = res
    dx, dgam, dbeta = _gn_bwd_pallas(
        x3, dy, gamma, beta, mean, rstd, num_groups, act)
    return (dx, dgam.astype(gamma.dtype), dbeta.astype(beta.dtype))


_fused_group_norm3.defvjp(_fused_fwd, _fused_bwd)


def fused_group_norm(x, gamma, beta, num_groups, epsilon=1e-5,
                     activation=None):
    """Fused GroupNorm(+activation) over NHWC ``x [n, h, w, c]``.

    gamma/beta: [c]. ``activation``: None | "silu" (applied INSIDE the
    kernel after the affine — the UNet's norm→SiLU chain as one HBM
    pass). Differentiable via the fused backward kernel. Shapes outside
    the kernel's budget (``supports_fused`` False) fall back to the lax
    reference — same numerics, no crash."""
    if activation not in (None, "silu"):
        raise ValueError(
            f"fused_group_norm: unknown activation {activation!r}")
    if not supports_fused(x.shape, num_groups):
        return group_norm_reference(x, gamma, beta, num_groups, epsilon,
                                    activation)
    n, h, w, c = x.shape
    y = _fused_group_norm3(x.reshape(n, h * w, c), gamma, beta,
                           int(num_groups), float(epsilon), activation)
    return y.reshape(n, h, w, c)


def group_norm_reference(x, gamma=None, beta=None, num_groups=1,
                         epsilon=1e-5, activation=None):
    """Pure-jnp NHWC GroupNorm(+activation) — the kernel's numeric
    source of truth and the over-budget fallback. Stats, affine, and
    activation all in f32 (matching the kernel), output in x.dtype.
    Still transpose-free: reductions run on the channels-last tensor
    directly."""
    n, c = x.shape[0], x.shape[-1]
    g = num_groups
    spatial = x.shape[1:-1]
    xf = x.astype(jnp.float32).reshape(n, -1, g, c // g)
    mean = jnp.mean(xf, axis=(1, 3), keepdims=True)
    d = xf - mean
    var = jnp.mean(d * d, axis=(1, 3), keepdims=True)
    y = d * jax.lax.rsqrt(var + epsilon)
    y = y.reshape(n, *spatial, c)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    if activation == "silu":
        y = y * jax.nn.sigmoid(y)
    elif activation is not None:
        raise ValueError(f"group_norm: unknown activation {activation!r}")
    return y.astype(x.dtype)
