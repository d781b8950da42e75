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
kernel never forms them: HBM traffic is the ``[b, s, d]``/``[b, s, n]``
operands once (``u``, ``B``, ``C`` in the dtype they come in, widened
in VMEM) and the outputs once, with the ``D`` skip and every sum over
channels (``dB``, ``dC``, ``dD``) made inside.

Layout and grid. The state is ``[n, d_block]``, channels on lanes (n is
small, 16: two sublane groups), float32, like everything computed here.
The grid is (batch, chunk, block of channels), the blocks innermost: a
grid step is one chunk of ``chunk`` time steps of one block of
``d_block`` channels, and the state of every block lives in VMEM
scratch (``[d / d_block, n, d_block]``) from chunk to chunk. A chunk's
``B`` and ``C`` rows are brought to sublanes and spread over a vreg's
lanes once, at its first block (``[chunk, n, 128]`` each, one relayout
of the whole block), and read by all its blocks.

The time loop runs over slabs of 8 steps (one sublane group of the
``[chunk, d_block]`` operands; 4, 2 or 1 where the chunk is no multiple
of 8), a slab's steps unrolled into one block (``_steps``; the step is
traced once). A step is ``h = exp(delta_t A) * h + (delta_t u_t) B_t``
(``delta u`` made for the whole chunk ahead of the loop) and the
read-out product with its sum over ``n``; within the block only ``h``
waits on the step before, so a step's loads, ``exp`` and products run
under the steps before it. The recurrence itself is not rearranged: no
cumulative product, no scan inside a slab.

Backward (recompute-based, like the reference CUDA bwd): the forward
additionally saves the recurrent state at each chunk BOUNDARY —
``[b, s/chunk, n, d]``, a ``chunk``-fold reduction vs ``[b, s, d, n]``.
The backward kernel walks chunks in reverse; within a chunk it first
re-runs the forward recurrence from the saved boundary state, keeping
the chunk's states and decays in VMEM scratch (never HBM), then runs the
reverse-time cotangent recurrence  gh_{t} = C_t⊗g_t + dA_{t+1}·gh_{t+1},
slab by slab. Its sums over ``n`` (for du, dδ) are stored row by row
and turned into du (with g·D, rounded once) and dδ after the loop in
whole-block passes. Its sums over CHANNELS (dB, dC) leave the loop as
products folded to one vreg of lanes (elementwise adds), gathered in
scratch over the chunk's blocks and summed along lanes once a chunk;
dA and dD gather in their outputs' own blocks over the chunks. No
``[b, s, d, n]`` tensor exists in either pass.

VMEM a grid step, float32 words: forward ``(d/d_block) n d_block`` of
state, ``chunk d_block`` and two ``chunk n 128``, beside its blocks
(under 4 MB at chunk 128, d_block 512, d 5120); backward ``(2 chunk +
1) n d_block`` for the states and decays (8.4 MB there), four ``chunk
d_block``, four ``chunk n 128`` and two carries of the forward's state
size: 14 MB beside its blocks, for which both calls ask 32 MiB
(``_VMEM_LIMIT_BYTES``, the default scoped limit being 16) and
``_pick_d_block`` narrows the block where the chunk is longer.
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


F32 = jnp.float32
_LANES = 128


def _slab(chunk):
    """Time steps a loop iteration: one aligned sublane group of the
    ``[chunk, d_block]`` operands where the chunk allows it."""
    return next(t for t in (8, 4, 2, 1) if chunk % t == 0)


def _bc_width(d_block):
    """Lanes over which a step's ``B_t`` / ``C_t`` column is spread once
    a chunk (a vreg's worth; the loop repeats it over the block)."""
    return _LANES if d_block % _LANES == 0 else d_block


def _spread(src_ref, out_scr):
    """``src_ref [1, chunk, n]`` -> ``out_scr [chunk, n, w]``: every
    step's row brought to sublanes and repeated along lanes, the whole
    chunk in one relayout, once a chunk (its channel blocks all read
    it)."""
    out_scr[...] = jnp.broadcast_to(src_ref[0].astype(F32)[:, :, None],
                                    out_scr.shape)


def _over_block(tile, d_block):
    """``[n, w]`` -> ``[n, d_block]``: whole vregs side by side."""
    reps = d_block // tile.shape[1]
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _fold(x, w):
    """``[n, d_block]`` -> ``[n, w]``: the block's lane groups added
    elementwise (what is left of a sum over channels is taken once a
    chunk, outside the time loop)."""
    parts = [x[:, k:k + w] for k in range(0, x.shape[1], w)]
    return functools.reduce(jnp.add, parts)


def _row(ref, t):
    """Row ``t`` of a ``[chunk, d_block]`` float32 ref as ``[1, d]``."""
    return ref[pl.ds(t, 1), :]


def _steps(chunk, step, carry):
    """``step(t, carry)`` for t in [0, chunk): a loop over slabs, a
    slab's steps unrolled into one block, so that the scheduler may
    start a step's loads, exponentials and products while the steps
    before it still wait on the state."""
    slab = _slab(chunk)

    def slab_body(si, carry):
        t0 = pl.multiple_of(si * slab, slab)
        return jax.lax.fori_loop(
            0, slab, lambda j, c: step(t0 + j, c), carry, unroll=True)

    return jax.lax.fori_loop(0, chunk // slab, slab_body, carry)


def _scan_kernel(u_ref, delta_ref, b_ref, c_ref, at_ref, d_ref, y_ref,
                 *refs, chunk):
    # h0_ref: the output for the states between chunks, where asked for
    *h0_ref, h_scr, dtu_scr, bb_scr, cb_scr = refs
    ic, id_ = pl.program_id(1), pl.program_id(2)
    d_block = dtu_scr.shape[1]

    @pl.when(ic == 0)
    def _reset():
        h_scr[id_] = jnp.zeros(h_scr.shape[1:], F32)

    @pl.when(id_ == 0)
    def _new_chunk():
        _spread(b_ref, bb_scr)
        _spread(c_ref, cb_scr)

    for ref in h0_ref:
        # the state entering this chunk: the backward's anchor
        ref[0, 0] = h_scr[id_]

    at = at_ref[...]                                    # [n, d_block]
    dref = delta_ref.at[0]
    dtu_scr[...] = delta_ref[0] * u_ref[0].astype(F32)  # whole chunk

    def step(t, h):
        h = (jnp.exp(_row(dref, t) * at) * h
             + _row(dtu_scr, t) * _over_block(bb_scr[t], d_block))
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * _over_block(cb_scr[t], d_block), axis=0, keepdims=True)
        return h

    h_scr[id_] = _steps(chunk, step, h_scr[id_])
    # the D skip, in float32 like y: the caller rounds the sum once
    y_ref[0] += u_ref[0].astype(F32) * d_ref[...]


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


# what a call may take of VMEM (the flash kernels ask the same): the
# backward's states and decays of a chunk may fill half of it
# (``_pick_d_block``); its other scratch and its blocks, twice over,
# are under 9 MB at chunk 128, d_block 512
_VMEM_LIMIT_BYTES = 32 << 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _scan_fwd_pallas(u, delta, B, C, at, D, chunk, d_block, with_states):
    """Run the forward kernel. Returns y + u D in float32 (and the state
    before each chunk when ``with_states``). ``at`` is A.T ([n, d]) in
    f32."""
    b, s, d = u.shape
    n = at.shape[0]
    n_chunks, nd = s // chunk, d // d_block
    w = _bc_width(d_block)
    grid = (b, n_chunks, nd)
    seq = pl.BlockSpec((1, chunk, d_block), lambda ib, ic, id_: (ib, ic, id_))
    col = pl.BlockSpec((1, chunk, n), lambda ib, ic, id_: (ib, ic, 0))
    in_specs = [
        seq, seq, col, col,
        pl.BlockSpec((n, d_block), lambda ib, ic, id_: (0, id_)),
        pl.BlockSpec((1, d_block), lambda ib, ic, id_: (0, id_)),
    ]
    scratch = [
        pltpu.VMEM((nd, n, d_block), F32),      # the state, block by block
        pltpu.VMEM((chunk, d_block), F32),      # delta * u
        pltpu.VMEM((chunk, n, w), F32),         # B over lanes
        pltpu.VMEM((chunk, n, w), F32),         # C over lanes
    ]
    out_specs, out_shape = seq, jax.ShapeDtypeStruct((b, s, d), F32)
    if with_states:
        out_specs = (seq, pl.BlockSpec((1, 1, n, d_block),
                                       lambda ib, ic, id_: (ib, ic, 0, id_)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b, n_chunks, n, d), F32))
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_PARAMS,
        interpret=_backend.interpret(),
    )(u, delta.astype(F32), B, C, at, D.astype(F32)[None])


def _scan_bwd_kernel(u_ref, delta_ref, b_ref, c_ref, at_ref, d_ref, h0_ref,
                     g_ref, du_ref, ddelta_ref, db_ref, dc_ref, dat_ref, dd_ref,
                     gh_scr, hs_scr, da_scr, dtu_scr, g_scr, s1_scr, s2_scr,
                     bb_scr, cb_scr, pb_scr, pc_scr, *, chunk):
    """One chunk of one block of channels, the chunks coming last to
    first. ``gh_scr`` carries dL/dh across the chunk boundary;
    ``hs_scr`` holds the chunk's states made again from the saved one
    and ``da_scr`` their decays (VMEM only: the one place a state per
    step exists); ``pb_scr`` / ``pc_scr`` gather the products whose sums
    over channels are dB and dC, lane group on lane group and block on
    block, and are summed along lanes once a chunk."""
    ic, id_, nd = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    d_block = dtu_scr.shape[1]
    w = pb_scr.shape[2]

    @pl.when(ic == 0)
    def _reset():
        gh_scr[id_] = jnp.zeros(gh_scr.shape[1:], F32)
        dat_ref[0, id_] = jnp.zeros(dat_ref.shape[2:], F32)
        dd_ref[0, id_] = jnp.zeros(dd_ref.shape[2:], F32)

    @pl.when(id_ == 0)
    def _new_chunk():
        _spread(b_ref, bb_scr)
        _spread(c_ref, cb_scr)
        pb_scr[...] = jnp.zeros_like(pb_scr)
        pc_scr[...] = jnp.zeros_like(pc_scr)

    at = at_ref[...]                                    # [n, d_block]
    dref = delta_ref.at[0]
    dtu_scr[...] = delta_ref[0] * u_ref[0].astype(F32)
    g_scr[...] = g_ref[0].astype(F32)

    # ---- pass 1: the states h_t again, hs_scr[t + 1] = h_t ----
    h0 = hs_scr[0] = h0_ref[0, 0]

    def fwd_step(t, h):
        da = jnp.exp(_row(dref, t) * at)
        da_scr[t] = da
        h = da * h + _row(dtu_scr, t) * _over_block(bb_scr[t], d_block)
        hs_scr[t + 1] = h
        return h

    _steps(chunk, fwd_step, h0)

    # ---- pass 2: the cotangent recurrence, last step first ----
    def bwd_step(rt, carry):
        gh, dat = carry
        t = chunk - 1 - rt
        g = _row(g_scr, t)                           # [1, d]
        da = da_scr[t]
        # dC_t = sum over channels of h_t g
        pc_scr[t] += _fold(hs_scr[t + 1] * g, w)
        gh = gh + _over_block(cb_scr[t], d_block) * g   # dL/dh_t
        # through dbu = (delta u) (x) B
        s1_scr[pl.ds(t, 1), :] = jnp.sum(
            gh * _over_block(bb_scr[t], d_block), axis=0, keepdims=True)
        pb_scr[t] += _fold(gh * _row(dtu_scr, t), w)
        # through da = exp(delta (x) at), applied to h_{t-1}
        ghh = gh * hs_scr[t] * da
        s2_scr[pl.ds(t, 1), :] = jnp.sum(ghh * at, axis=0, keepdims=True)
        return da * gh, dat + ghh * _row(dref, t)

    gh, dat = _steps(chunk, bwd_step,
                     (gh_scr[id_], jnp.zeros(gh_scr.shape[1:], F32)))
    gh_scr[id_] = gh
    dat_ref[0, id_] += dat
    # du = delta s1 + g D, rounded once; ddelta = u s1 + s2; dD = sum g u
    s1, g, u = s1_scr[...], g_scr[...], u_ref[0].astype(F32)
    du_ref[0] = (delta_ref[0] * s1 + g * d_ref[...]).astype(du_ref.dtype)
    ddelta_ref[0] = u * s1 + s2_scr[...]
    dd_ref[0, id_] += jnp.sum(g * u, axis=0, keepdims=True)

    @pl.when(id_ == nd - 1)
    def _sums():
        db_ref[0] = jnp.sum(pb_scr[...], axis=2)
        dc_ref[0] = jnp.sum(pc_scr[...], axis=2)


def _scan_bwd_pallas(u, delta, B, C, at, D, h0s, g, chunk, d_block):
    b, s, d = u.shape
    n = at.shape[0]
    n_chunks, nd = s // chunk, d // d_block
    w = _bc_width(d_block)
    grid = (b, n_chunks, nd)

    def rev(ic):
        return n_chunks - 1 - ic

    seq = pl.BlockSpec((1, chunk, d_block),
                       lambda ib, ic, id_: (ib, rev(ic), id_))
    col = pl.BlockSpec((1, chunk, n), lambda ib, ic, id_: (ib, rev(ic), 0))
    in_specs = [
        seq, seq, col, col,
        pl.BlockSpec((n, d_block), lambda ib, ic, id_: (0, id_)),   # at
        pl.BlockSpec((1, d_block), lambda ib, ic, id_: (0, id_)),   # D
        pl.BlockSpec((1, 1, n, d_block),
                     lambda ib, ic, id_: (ib, rev(ic), 0, id_)),    # h0s
        seq,                                                        # g
    ]
    out_specs = (
        seq, seq,       # du, ddelta
        col, col,       # dB, dC: written once a chunk, after its blocks
        # dA^T and dD: gathered over the chunks in the output's own
        # block, one a batch row (the caller sums over those)
        pl.BlockSpec((1, nd, n, d_block), lambda ib, ic, id_: (ib, 0, 0, 0)),
        pl.BlockSpec((1, nd, 1, d_block), lambda ib, ic, id_: (ib, 0, 0, 0)),
    )
    out_shape = (
        jax.ShapeDtypeStruct((b, s, d), u.dtype),
        jax.ShapeDtypeStruct((b, s, d), F32),
        jax.ShapeDtypeStruct((b, s, n), F32),
        jax.ShapeDtypeStruct((b, s, n), F32),
        jax.ShapeDtypeStruct((b, nd, n, d_block), F32),
        jax.ShapeDtypeStruct((b, nd, 1, d_block), F32),
    )
    scratch = [
        pltpu.VMEM((nd, n, d_block), F32),          # gh carry
        pltpu.VMEM((chunk + 1, n, d_block), F32),   # the states again
        pltpu.VMEM((chunk, n, d_block), F32),       # their decays
        pltpu.VMEM((chunk, d_block), F32),          # delta * u
        pltpu.VMEM((chunk, d_block), F32),          # g widened
        pltpu.VMEM((chunk, d_block), F32),          # sum_n gh B
        pltpu.VMEM((chunk, d_block), F32),          # sum_n ghh A
        pltpu.VMEM((chunk, n, w), F32),             # B over lanes
        pltpu.VMEM((chunk, n, w), F32),             # C over lanes
        pltpu.VMEM((chunk, n, w), F32),             # dB's products
        pltpu.VMEM((chunk, n, w), F32),             # dC's products
    ]
    du, ddelta, db, dc, dat, dd = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_PARAMS,
        interpret=_backend.interpret(),
    )(u, delta.astype(F32), B, C, at, D.astype(F32)[None], h0s, g)
    # [b, nd, n, d_block] -> [n, d]
    dat = dat.sum(0).transpose(1, 0, 2).reshape(n, d)
    return du, ddelta, db, dc, dat, dd.sum(0).reshape(d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunked_scan(u, delta, A, B, C, D, chunk, d_block):
    return _scan_fwd_pallas(u, delta, B, C, A.T.astype(F32), D, chunk,
                            d_block, with_states=False)


def _chunked_fwd(u, delta, A, B, C, D, chunk, d_block):
    y, h0s = _scan_fwd_pallas(u, delta, B, C, A.T.astype(F32), D, chunk,
                              d_block, with_states=True)
    return y, (u, delta, A, B, C, D,
               checkpoint_name(h0s, RESIDUAL_NAMES[0]))


def _chunked_bwd(chunk, d_block, res, g):
    u, delta, A, B, C, D, h0s = res
    du, ddelta, db, dc, dat, dD = _scan_bwd_pallas(
        u, delta, B, C, A.T.astype(F32), D, h0s, g, chunk, d_block)
    return (du, ddelta.astype(delta.dtype), dat.T.astype(A.dtype),
            db.astype(B.dtype), dc.astype(C.dtype), dD.astype(D.dtype))


_chunked_scan.defvjp(_chunked_fwd, _chunked_bwd)


def _pick_d_block(d, n, chunk):
    """Channels a grid step: the whole of a narrow layer, else the
    widest multiple of a vreg's lanes up to 512 that divides ``d``, of
    those whose states and decays of a chunk (the backward's scratch)
    fit half the VMEM the call asks for."""
    widths = [w for w in (d, 512, 384, 256, 128) if w <= 512 and d % w == 0]
    fits = [w for w in widths
            if 2 * (chunk + 1) * n * w * 4 <= _VMEM_LIMIT_BYTES // 2]
    return (fits or widths[-1:] or [d])[0]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "d_block"))
def chunked_selective_scan(u, delta, A, B, C, D, *, chunk=128,
                           d_block=None):
    """y[b,s,d] for h_t = exp(Δ_t A)·h_{t-1} + Δ_t u_t B_t, y_t = C_t·h_t
    (+ u·D skip), float32. Shapes as ``associative_selective_scan``;
    ``u``, ``B``, ``C`` in any float dtype (widened in the kernel, their
    gradients rounded once to it), ``chunk`` the distance between the
    states kept for the backward. Training-safe: the custom VJP is
    recompute-based and never materializes [b,s,d,n] (backward VMEM:
    2·chunk·n·d_block words of states and decays a grid step;
    ``d_block`` defaults to ``_pick_d_block``)."""
    b, s, d = u.shape
    if d_block is None:
        d_block = _pick_d_block(d, A.shape[1], chunk)
    if s % chunk:
        raise ValueError(f"seq len {s} not divisible by chunk {chunk}")
    if d % d_block:
        raise ValueError(f"d {d} not divisible by d_block {d_block}")
    return _chunked_scan(u, delta, A, B, C, D, chunk, d_block)
