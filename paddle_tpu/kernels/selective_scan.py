"""Pallas chunked selective scan (S6 linear recurrence) for Mamba-1.

Parity: the reference's selective-scan CUDA kernel (the "selective-scan
+ linear-recurrence Phi op" BASELINE.json config, whose name says
"Mamba-2 / RWKV"). What this file computes is the S6 layer of Mamba-1: a
decay per channel and state column (``A [d, n]``, n = 16), walked step
by step. Mamba-2's recurrence (a scalar decay per head, a ``[64, 128]``
state per head) is another layer and lives in ``kernels/ssd.py``, in its
chunked matmul form, which this kernel cannot express.

Why a kernel when ``jax.lax.associative_scan`` already runs on TPU: the
associative formulation materializes the discretized operands
``dA, dBu`` — two ``[b, s, d, n]`` f32 tensors, a ``2n``-fold blowup of
the activations — and streams them through HBM O(log s) times. This
kernel never forms them: the sequence is processed in chunks with the
``[n, d]`` recurrent state resident in VMEM scratch across the
(sequential) chunk grid dimension, so HBM traffic is just the
``[b, s, d]``/``[b, s, n]`` inputs once and the output once — the same
streaming structure the reference's CUDA scan uses, mapped onto the
Pallas grid. Layout: state is kept ``[n, d]`` with d on lanes (n is
small, e.g. 16), so every VPU op runs full-width.

Backward (recompute-based, like the reference CUDA bwd): the forward
additionally saves the recurrent state at each chunk BOUNDARY —
``[b, s/chunk, n, d]``, a ``chunk``-fold reduction vs ``[b, s, d, n]``.
The backward kernel walks chunks in reverse; within a chunk it first
re-runs the forward recurrence from the saved boundary state (states
live in a VMEM scratch, never HBM), then runs the reverse-time
cotangent recurrence  gh_{t} = C_t⊗g_t + dA_{t+1}·gh_{t+1}  emitting
du/dδ/dB/dC in place and accumulating dA in scratch. No ``[b, s, d, n]``
tensor exists in either pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

# what the forward rule leaves for the backward beside its own arguments,
# by name: the state before each chunk. A caller under ``jax.checkpoint``
# keeps it by its policy, and the forward kernel is not run again
# (``models/mamba.py:_s6_core``)
RESIDUAL_NAMES = ("s6_states",)


def _scan_kernel(u_ref, delta_ref, b_ref, c_ref, at_ref, *out_refs,
                 chunk, with_states):
    if with_states:
        y_ref, h0_ref, h_scratch = out_refs
    else:
        y_ref, h_scratch = out_refs
        h0_ref = None
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _reset():
        h_scratch[:] = jnp.zeros_like(h_scratch)

    if h0_ref is not None:
        # state entering this chunk (end of previous chunk) — the
        # backward's recompute anchor
        h0_ref[0, 0] = h_scratch[...]

    at = at_ref[...]  # [n, d_block]

    def body(t, h):
        # all [n, d] with d on lanes
        dt = delta_ref[0, t][None, :]          # [1, d]
        da = jnp.exp(dt * at)                  # [n, d]
        dbu = (dt * u_ref[0, t][None, :]) * b_ref[0, t][:, None]
        h = da * h + dbu
        y = jnp.sum(h * c_ref[0, t][:, None], axis=0)  # [d]
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h_scratch[:] = jax.lax.fori_loop(0, chunk, body, h_scratch[...])


def associative_selective_scan(u, delta, A, B, C, D):
    """Reference S6 scan via ``jax.lax.associative_scan``.

    u: [b,s,d]; delta: [b,s,d] (softplus-activated); A: [d,n] (negative);
    B, C: [b,s,n]; D: [d]. The combine (a,b)∘(a',b') = (a·a', a'·b+b')
    is associative, so XLA lowers a log-depth scan — but it materializes
    the [b,s,d,n] discretized operands in HBM, which is what the Pallas
    kernel below avoids (in both passes). Kept as the numeric reference
    for the kernel's tests.
    """
    dA = jnp.exp(delta[..., None] * A[None, None])
    dBu = (delta * u)[..., None] * B[:, :, None, :]

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a2 * a1, a2 * b1 + b2

    _, h_all = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    y = jnp.einsum("bsdn,bsn->bsd", h_all, C)
    return y + u * D[None, None]


def _scan_fwd_pallas(u, delta, B, C, at, chunk, d_block, with_states):
    """Run the forward kernel. Returns y (and chunk-boundary states when
    ``with_states``). ``at`` is A.T ([n, d]) in f32."""
    b, s, d = u.shape
    n = at.shape[0]
    n_chunks = s // chunk
    grid = (b, d // d_block, n_chunks)
    f32 = jnp.float32
    in_specs = [
        pl.BlockSpec((1, chunk, d_block), lambda ib, id_, ic: (ib, ic, id_)),
        pl.BlockSpec((1, chunk, d_block), lambda ib, id_, ic: (ib, ic, id_)),
        pl.BlockSpec((1, chunk, n), lambda ib, id_, ic: (ib, ic, 0)),
        pl.BlockSpec((1, chunk, n), lambda ib, id_, ic: (ib, ic, 0)),
        pl.BlockSpec((n, d_block), lambda ib, id_, ic: (0, id_)),
    ]
    y_spec = pl.BlockSpec((1, chunk, d_block),
                          lambda ib, id_, ic: (ib, ic, id_))
    scratch = [pltpu.VMEM((n, d_block), f32)]
    kernel = functools.partial(_scan_kernel, chunk=chunk,
                               with_states=with_states)
    args = (u.astype(f32), delta.astype(f32), B.astype(f32), C.astype(f32),
            at)
    if not with_states:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=y_spec,
            out_shape=jax.ShapeDtypeStruct((b, s, d), f32),
            scratch_shapes=scratch, interpret=_backend.interpret(),
        )(*args)
    h0_spec = pl.BlockSpec((1, 1, n, d_block),
                           lambda ib, id_, ic: (ib, ic, 0, id_))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=(y_spec, h0_spec),
        out_shape=(
            jax.ShapeDtypeStruct((b, s, d), f32),
            jax.ShapeDtypeStruct((b, n_chunks, n, d), f32),
        ),
        scratch_shapes=scratch, interpret=_backend.interpret(),
    )(*args)


def _scan_bwd_kernel(u_ref, delta_ref, b_ref, c_ref, at_ref, h0_ref, g_ref,
                     du_ref, ddelta_ref, db_ref, dc_ref, dat_ref,
                     gh_scratch, hs_scratch, dat_scratch, *, chunk,
                     n_chunks):
    """One reverse-ordered chunk of the cotangent recurrence.

    gh ("grad of h") carries dL/dh_t across the chunk boundary in VMEM
    scratch; hs_scratch holds the chunk's recomputed states (the only
    place full per-step states ever exist — VMEM, [chunk, n, d_block]).
    """
    ic = pl.program_id(2)  # 0 = LAST chunk (reverse iteration)

    @pl.when(ic == 0)
    def _reset():
        gh_scratch[:] = jnp.zeros_like(gh_scratch)
        dat_scratch[:] = jnp.zeros_like(dat_scratch)

    at = at_ref[...]      # [n, d]
    h0 = h0_ref[0, 0]     # [n, d] state entering this chunk

    # ---- pass 1: recompute post-step states h_t for t in [0, chunk) ----
    def fwd_body(t, h):
        dt = delta_ref[0, t][None, :]
        da = jnp.exp(dt * at)
        dbu = (dt * u_ref[0, t][None, :]) * b_ref[0, t][:, None]
        h = da * h + dbu
        hs_scratch[t] = h
        return h

    jax.lax.fori_loop(0, chunk, fwd_body, h0)

    # ---- pass 2: reverse cotangent recurrence ----
    def bwd_body(rt, gh):
        t = chunk - 1 - rt
        g = g_ref[0, t][None, :]               # [1, d]
        dt = delta_ref[0, t][None, :]          # [1, d]
        bt = b_ref[0, t][:, None]              # [n, 1]
        ct = c_ref[0, t][:, None]              # [n, 1]
        ut = u_ref[0, t][None, :]              # [1, d]
        h_t = hs_scratch[t]                    # [n, d]
        h_prev = jnp.where(t == 0, h0, hs_scratch[jnp.maximum(t - 1, 0)])
        da = jnp.exp(dt * at)                  # [n, d]

        # dC_t[n] = Σ_d h_t·g
        dc_ref[0, 0, t] = jnp.sum(h_t * g, axis=1)
        gh = gh + ct * g                       # dL/dh_t, full

        # dbu branch: dbu = (δ·u) ⊗ B
        ghb = gh * bt                          # [n, d]
        sum_ghb = jnp.sum(ghb, axis=0)[None, :]  # [1, d]
        du_ref[0, t] = (dt * sum_ghb)[0].astype(du_ref.dtype)
        ddelta_dbu = ut * sum_ghb              # [1, d]
        db_ref[0, 0, t] = jnp.sum(gh * (dt * ut), axis=1)

        # da branch: da = exp(δ ⊗ at), applied to h_prev
        ghh = gh * h_prev * da                 # [n, d]
        ddelta_da = jnp.sum(ghh * at, axis=0)[None, :]
        ddelta_ref[0, t] = (ddelta_dbu + ddelta_da)[0].astype(
            ddelta_ref.dtype)
        dat_scratch[:] += ghh * dt

        # propagate to t-1
        return da * gh

    gh_scratch[:] = jax.lax.fori_loop(0, chunk, bwd_body, gh_scratch[...])

    @pl.when(ic == n_chunks - 1)  # first chunk (reverse order) → flush dA
    def _fin():
        dat_ref[0] = dat_scratch[...]


def _scan_bwd_pallas(u, delta, B, C, at, h0s, g, chunk, d_block):
    b, s, d = u.shape
    n = at.shape[0]
    n_chunks = s // chunk
    nd = d // d_block
    f32 = jnp.float32
    grid = (b, nd, n_chunks)

    def rev(ic):
        return n_chunks - 1 - ic

    in_specs = [
        pl.BlockSpec((1, chunk, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), id_)),   # u
        pl.BlockSpec((1, chunk, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), id_)),   # delta
        pl.BlockSpec((1, chunk, n),
                     lambda ib, id_, ic: (ib, rev(ic), 0)),     # B
        pl.BlockSpec((1, chunk, n),
                     lambda ib, id_, ic: (ib, rev(ic), 0)),     # C
        pl.BlockSpec((n, d_block), lambda ib, id_, ic: (0, id_)),  # at
        pl.BlockSpec((1, 1, n, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), 0, id_)),  # h0s
        pl.BlockSpec((1, chunk, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), id_)),   # g
    ]
    out_specs = (
        pl.BlockSpec((1, chunk, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), id_)),   # du
        pl.BlockSpec((1, chunk, d_block),
                     lambda ib, id_, ic: (ib, rev(ic), id_)),   # ddelta
        # dB/dC get a leading d-block axis (summed by the caller —
        # different d-blocks each contribute)
        pl.BlockSpec((1, 1, chunk, n),
                     lambda ib, id_, ic: (id_, ib, rev(ic), 0)),  # db
        pl.BlockSpec((1, 1, chunk, n),
                     lambda ib, id_, ic: (id_, ib, rev(ic), 0)),  # dc
        # dat: per-batch accumulator flushed on the last (reverse) chunk;
        # caller sums over batch
        pl.BlockSpec((1, n, d_block), lambda ib, id_, ic: (ib, 0, id_)),
    )
    out_shape = (
        jax.ShapeDtypeStruct((b, s, d), f32),
        jax.ShapeDtypeStruct((b, s, d), f32),
        jax.ShapeDtypeStruct((nd, b, s, n), f32),
        jax.ShapeDtypeStruct((nd, b, s, n), f32),
        jax.ShapeDtypeStruct((b, n, d), f32),
    )
    du, ddelta, db, dc, dat = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((n, d_block), f32),          # gh carry
            pltpu.VMEM((chunk, n, d_block), f32),   # recomputed states
            pltpu.VMEM((n, d_block), f32),          # dat accumulator
        ],
        interpret=_backend.interpret(),
    )(u.astype(f32), delta.astype(f32), B.astype(f32), C.astype(f32),
      at, h0s, g.astype(f32))
    return du, ddelta, db.sum(0), dc.sum(0), dat.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunked_scan(u, delta, A, B, C, D, chunk, d_block):
    f32 = jnp.float32
    at = A.T.astype(f32)
    y = _scan_fwd_pallas(u, delta, B, C, at, chunk, d_block,
                         with_states=False)
    return y + u.astype(f32) * D[None, None].astype(f32)


def _chunked_fwd(u, delta, A, B, C, D, chunk, d_block):
    f32 = jnp.float32
    at = A.T.astype(f32)
    y, h0s = _scan_fwd_pallas(u, delta, B, C, at, chunk, d_block,
                              with_states=True)
    out = y + u.astype(f32) * D[None, None].astype(f32)
    return out, (u, delta, A, B, C, D,
                 checkpoint_name(h0s, RESIDUAL_NAMES[0]))


def _chunked_bwd(chunk, d_block, res, g):
    u, delta, A, B, C, D, h0s = res
    f32 = jnp.float32
    at = A.T.astype(f32)
    du, ddelta, db, dc, dat = _scan_bwd_pallas(
        u, delta, B, C, at, h0s, g, chunk, d_block)
    # D-skip terms (outside the kernel: pure elementwise)
    g32 = g.astype(f32)
    du = du + g32 * D[None, None].astype(f32)
    dD = jnp.sum(g32 * u.astype(f32), axis=(0, 1))
    dA = dat.T  # at = A.T
    return (du.astype(u.dtype), ddelta.astype(delta.dtype),
            dA.astype(A.dtype), db.astype(B.dtype), dc.astype(C.dtype),
            dD.astype(D.dtype))


_chunked_scan.defvjp(_chunked_fwd, _chunked_bwd)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "d_block"))
def chunked_selective_scan(u, delta, A, B, C, D, *, chunk=128,
                           d_block=None):
    """y[b,s,d] for h_t = exp(Δ_t A)·h_{t-1} + Δ_t u_t B_t, y_t = C_t·h_t
    (+ u·D skip). Shapes as ``associative_selective_scan``. Training-safe:
    the custom VJP is recompute-based and never materializes [b,s,d,n]
    (backward VMEM: chunk·n·d_block states per grid cell)."""
    b, s, d = u.shape
    n = A.shape[1]
    if d_block is None:
        d_block = d if d <= 512 else 256
        # keep the backward's recomputed-state scratch within VMEM budget
        while chunk * n * d_block * 4 > 8 * 1024 * 1024 and d_block > 128:
            d_block //= 2
    if s % chunk:
        raise ValueError(f"seq len {s} not divisible by chunk {chunk}")
    if d % d_block:
        raise ValueError(f"d {d} not divisible by d_block {d_block}")
    return _chunked_scan(u, delta, A, B, C, D, chunk, d_block)
