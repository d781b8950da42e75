"""The Mamba-2 recurrence in its chunked form (state-space duality), as
a Pallas kernel pair.

Per head, with a scalar decay and a state ``S`` of ``[p, n]``
(``S_0 = 0``):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D * x_t

``B`` and ``C`` are shared by the heads of a group. Walked step by step
this is 2 * p * n multiply-adds a token and head on the VPU, one token
after the other. The chunked form does the same sum on the MXU: inside
a chunk of ``Q`` tokens the outputs are a masked ``(C B^T) * decay``
product against ``x``; every chunk leaves a state, and each token adds
what the state before its chunk gives it.

The kernels: one grid step is one chunk of one group of heads; the
chunks are the last, sequential grid axis. A step reads the chunk's
``x [Q, heads * p]``, ``B`` and ``C [Q, n]`` and the heads' ``dt`` and
cumulative log-decay ``cs``, makes ``C B^T`` once for the group and,
head by head, the ``[Q, Q]`` decay ``exp(cs_i - cs_j)`` under the
causal mask, ``mix = scores * decay * dt_j`` and ``mix @ x``: all of it
in VMEM, none of it ever in HBM. The state ``[heads * p, n]`` of the
group lives in a VMEM scratch and goes from chunk to chunk by ``S <-
exp(total) * S + (x * to_end * dt)^T B``. One read of the inputs, one
write of ``y``. The backward
kernel walks the chunks in reverse (by its index maps) with the state's
cotangent in the scratch, makes the same ``[Q, Q]`` tiles again and
writes the gradients of ``x``, ``B`` and ``C`` (summed over a group's
heads on chip) and, per head and token, those of ``dt`` and of the
cumulative log-decay; it reads the state before each chunk, which the
forward rule writes as its one residual (float32 ``[chunks, heads * p,
n]``). What is ``[b, s, heads]`` and float32 (the cumulative sums in a
chunk, their way back into ``dt`` and ``A``) stays in XLA around the
calls: it is a thousandth of the bytes.

Precision: ``dt``, ``A``, every cumulative sum, every decay and the
states are float32; the carry of the states between chunks is an
element-wise float32 update. Every product takes its operands in
``x``'s type (bf16 in training: ``mix``, the decayed ``x`` and, where a
float32 state or cotangent enters a product, that too are rounded to it
just before, as XLA's default precision does on the chip) and
accumulates in float32. ``y`` leaves in float32.

Memory: nothing of ``[.., Q, Q]`` is kept. What the backward kernel reads
is the forward rule's residual: the kernel's operands (``x``, ``B``,
``C`` in their type, ``dt`` and the cumulative sums in both layouts) and
the float32 states, 134 MB a block at the benchmark's shape. The rule
marks ``dt``, the cumulative sums and the states ``RESIDUAL_NAMES``
(``jax.ad_checkpoint.checkpoint_name``), so that a caller under
``jax.checkpoint`` can keep them by its policy and the forward kernel
runs once: ``models/mamba.py:_mamba2_core`` does, and makes ``x``, ``B``,
``C`` again from what it keeps of its own.

Shapes: any the recurrence has; on the chip the blocks must tile
(``chunk`` and ``n`` multiples of 128, a group's ``heads * p`` a
multiple of 128, its heads a multiple of 8, unless there is one chunk or
one group) and the chip's compiler refuses what does not. Off the chip
the kernels run in interpret mode.

The definition above, walked step by step, is the benchmark's plain
reference (``chipbench/reference/nemotron_h.py``); ``tests/test_ssd.py``
holds this file against it. ``kernels/selective_scan.py`` is the other
layer: Mamba-1's S6, a decay per channel and state column, walked by a
Pallas kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

F32 = jnp.float32
# what of the forward rule's residual only this module can make, by
# name: dt and the cumulative sums in both layouts, and D's row; the
# state before each chunk
RESIDUAL_NAMES = ("ssd_decays", "ssd_states")


def _dot(a, b, contract):
    """a . b over the given axis of each, float32 accumulation."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=F32)


def _decay(cs_col, cs_row, dt_row, tril):
    """exp(cs_i - cs_j) * dt_j for i >= j, 0 above the diagonal."""
    return jnp.exp(jnp.where(tril, cs_col - cs_row, -jnp.inf)) * dt_row


def _keep(total, like):
    """exp(total) as a row of ``like``'s lanes, total [1, 1]: Mosaic
    broadcasts along one axis at a time, so the exponent is taken between
    the lanes' broadcast and the sublanes'."""
    return jnp.exp(jnp.broadcast_to(total, (1, like.shape[1])))


def _tril(q):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, b_ref, c_ref, dtr_ref, csr_ref, dtc_ref, csc_ref,
                d_ref, y_ref, s0_ref, s_scr, *, heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _reset():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[0, 0] = s_scr[...]           # the state before this chunk

    x, B, C = x_ref[0], b_ref[0], c_ref[0]
    q, cd = x.shape[0], x.dtype
    csc, dtc = csc_ref[0, 0], dtc_ref[0, 0]         # [Q, heads]
    total = csc[q - 1:q]                            # [1, heads]
    tril = _tril(q)

    scores = _dot(C, B, (1, 1))                     # [Q, Q]
    from_state = _dot(C, s_scr[...].astype(cd), (1, 1))  # [Q, heads * p]
    to_end = jnp.exp(total - csc) * dtc             # [Q, heads]
    x32 = x.astype(F32)
    ys, xws = [], []
    for h in range(heads):
        sl = slice(h * p, (h + 1) * p)
        cs_col = csc[:, h:h + 1]
        mix = scores * _decay(cs_col, csr_ref[0, h:h + 1],
                              dtr_ref[0, h:h + 1], tril)
        ys.append(_dot(mix.astype(cd), x[:, sl], (1, 0))
                  + jnp.exp(cs_col) * from_state[:, sl])
        xws.append(x32[:, sl] * to_end[:, h:h + 1])
    y_ref[0] = jnp.concatenate(ys, axis=1) + d_ref[...] * x32

    # the state this chunk leaves
    new = _dot(jnp.concatenate(xws, axis=1).astype(cd), B, (0, 0))
    for h in range(heads):
        sl = slice(h * p, (h + 1) * p)
        s_scr[sl] = _keep(total[:, h:h + 1], new) * s_scr[sl] + new[sl]


def _expand(cols, p):
    """[Q, heads] -> [Q, heads * p]: each head's column over its lanes."""
    q, heads = cols.shape
    return jnp.concatenate(
        [jnp.broadcast_to(cols[:, h:h + 1], (q, p)) for h in range(heads)],
        axis=1)


def _per_head(t, seg):
    """[rows, heads * p] -> [rows, heads]: the sum over each head's
    lanes, as a float32 product with the heads' 0/1 matrix: a reduction
    along lanes, vreg by vreg, costs several times that."""
    return jax.lax.dot_general(t, seg, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _bwd_kernel(x_ref, b_ref, c_ref, dtr_ref, csr_ref, dtc_ref, csc_ref,
                d_ref, s0_ref, dy_ref, seg_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref, dd_ref,
                ds_scr, dd_scr, *, heads, p):
    """One chunk, the chunks coming last to first. ``ds_scr`` is the
    cotangent of the state this chunk leaves. With ``mix`` [i, j] and
    ``d_mix = dy x^T``, the sums of ``d_mix * mix`` along i and along j,
    which the decays' gradients need, are ``x . (mix^T dy)`` and
    ``dy . (mix x)`` per token: sums over a head's p lanes of products
    the MXU has made, so no [Q, Q] tile is ever reduced."""
    @pl.when(pl.program_id(2) == 0)
    def _reset():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dd_scr[...] = jnp.zeros_like(dd_scr)

    x, B, C, seg = x_ref[0], b_ref[0], c_ref[0], seg_ref[...]
    q, cd = x.shape[0], x.dtype
    csc, dtc = csc_ref[0, 0], dtc_ref[0, 0]         # [Q, heads]
    total = csc[q - 1:q]                            # [1, heads]
    tril = _tril(q)
    s0, ds, dy = s0_ref[0, 0], ds_scr[...], dy_ref[0]
    s0c, dsc, dyc = s0.astype(cd), ds.astype(cd), dy.astype(cd)
    x32 = x.astype(F32)

    scores = _dot(C, B, (1, 1))                     # [Q, Q]
    from_state = _dot(C, s0c, (1, 1))               # [Q, heads * p]
    d_xw = _dot(B, dsc, (1, 1))                     # [Q, heads * p]

    d_scores = jnp.zeros((q, q), F32)
    ys, dxs, kept = [], [], []
    for h in range(heads):
        sl = slice(h * p, (h + 1) * p)
        decay = _decay(csc[:, h:h + 1], csr_ref[0, h:h + 1],
                       dtr_ref[0, h:h + 1], tril)
        mix = (scores * decay).astype(cd)
        d_scores = d_scores + _dot(dyc[:, sl], x[:, sl], (1, 1)) * decay
        ys.append(_dot(mix, x[:, sl], (1, 0)))
        dxs.append(_dot(mix, dyc[:, sl], (0, 0)))
        kept.append(jnp.sum(ds[sl] * s0[sl], keepdims=True))
    y_in, dx_in = jnp.concatenate(ys, axis=1), jnp.concatenate(dxs, axis=1)

    from_end = jnp.exp(total - csc)                 # [Q, heads]
    w = _expand(from_end * dtc, p)                  # [Q, heads * p]
    dz = dy * _expand(jnp.exp(csc), p)
    # d_mix * mix summed along j and along i, from the same rounded dy,
    # x and mix: what one adds to the decays' gradients the other takes
    # away again, to the last bit but the order of the sums
    pull_rows = dyc.astype(F32) * y_in + dz * from_state
    pull_cols = x32 * dx_in
    d_w = d_xw * x32
    w_d_w = w * d_w
    dcs = _per_head(pull_rows - pull_cols - w_d_w, seg)         # [Q, heads]
    # dt_j enters mix once, as a factor, so its gradient is mix's over
    # dt_j; dt == 0 is a padded step (``ssd_chunked`` states the rule)
    over_dt = jnp.where(dtc > 0, 1.0 / dtc, 0.0)
    ddt_ref[0, 0] = _per_head(
        pull_cols * _expand(over_dt, p) + d_w * _expand(from_end, p), seg)
    # the chunk's total log-decay is its last token's cumulative sum
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    d_keep = jnp.zeros((1, heads), F32)
    for h in range(heads):
        d_keep = jnp.where(lane == h, kept[h], d_keep)
    d_total = _per_head(jnp.sum(w_d_w, axis=0, keepdims=True), seg) \
        + jnp.exp(total) * d_keep
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcs_ref[0, 0] = jnp.where(last, dcs + d_total, dcs)

    dx_ref[0] = (dx_in + w * d_xw + d_ref[...] * dy).astype(dx_ref.dtype)
    dzc, xwc = dz.astype(cd), (x32 * w).astype(cd)
    d_sc = d_scores.astype(cd)
    dc_ref[0] = (_dot(d_sc, B, (1, 0))
                 + _dot(dzc, s0c, (1, 0))).astype(dc_ref.dtype)
    db_ref[0] = (_dot(d_sc, C, (0, 0))
                 + _dot(xwc, dsc, (1, 0))).astype(db_ref.dtype)

    # the cotangent of the state before this chunk
    back = _dot(dzc, C, (0, 0))                     # [heads * p, n]
    for h in range(heads):
        sl = slice(h * p, (h + 1) * p)
        ds_scr[sl] = _keep(total[:, h:h + 1], back) * ds[sl] + back[sl]

    dd_scr[...] += jnp.sum(dy * x32, axis=0, keepdims=True)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        dd_ref[0, 0] = dd_scr[...]


def _specs(heads, p, n, chunk, at):
    """The block of each input of both kernels, in the kernels' order,
    for grid (batch, group, step); ``at(step)`` is the step's chunk."""
    hp = heads * p
    seq = pl.BlockSpec((1, chunk, hp), lambda ib, ig, ic: (ib, at(ic), ig))
    grp = pl.BlockSpec((1, chunk, n), lambda ib, ig, ic: (ib, at(ic), ig))
    row = pl.BlockSpec((1, heads, chunk), lambda ib, ig, ic: (ib, ig, at(ic)))
    col = pl.BlockSpec((1, 1, chunk, heads),
                       lambda ib, ig, ic: (ib, ig, at(ic), 0))
    skip = pl.BlockSpec((1, hp), lambda ib, ig, ic: (0, ig))
    state = pl.BlockSpec((1, 1, hp, n), lambda ib, ig, ic: (ib, at(ic), ig, 0))
    return seq, grp, row, col, skip, state


def _sizes(x, B, dtc):
    b, s, hp_all = x.shape
    g, heads = dtc.shape[1], dtc.shape[3]
    return b, s, g, heads, hp_all // (g * heads), B.shape[2] // g


# what a kernel may take of VMEM: a step's blocks, twice over, the
# group's state and the [Q, Q] tiles of a few heads are under 4 MB
_VMEM_LIMIT_BYTES = 16 << 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, inline=True, static_argnames=("chunk",))
def _fwd_call(x, B, C, dtr, csr, dtc, csc, d_row, *, chunk):
    """y and the state before each chunk, float32 [b, chunks, h * p, n]:
    the backward's residual. Where no gradient is taken nothing reads
    it; a variant of the kernel without it ran no faster in the
    benchmark's step."""
    b, s, g, heads, p, n = _sizes(x, B, dtc)
    seq, grp, row, col, skip, state = _specs(
        heads, p, n, chunk, lambda ic: ic)
    shape = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        grid=(b, g, s // chunk),
        in_specs=[seq, grp, grp, row, row, col, col, skip],
        out_specs=[seq, state],
        out_shape=[shape(x.shape, F32),
                   shape((b, s // chunk, g * heads * p, n), F32)],
        scratch_shapes=[pltpu.VMEM((heads * p, n), F32)],
        compiler_params=_PARAMS, interpret=_backend.interpret(),
    )(x, B, C, dtr, csr, dtc, csc, d_row)


@functools.partial(jax.jit, inline=True, static_argnames=("chunk",))
def _bwd_call(x, B, C, dtr, csr, dtc, csc, d_row, states, dy, *, chunk):
    b, s, g, heads, p, n = _sizes(x, B, dtc)
    last = s // chunk - 1
    seq, grp, row, col, skip, state = _specs(
        heads, p, n, chunk, lambda ic: last - ic)
    per_group = pl.BlockSpec((1, 1, 1, heads * p),
                             lambda ib, ig, ic: (ib, ig, 0, 0))
    whole = pl.BlockSpec((heads * p, heads), lambda ib, ig, ic: (0, 0))
    shape = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        grid=(b, g, s // chunk),
        in_specs=[seq, grp, grp, row, row, col, col, skip, state, seq,
                  whole],
        out_specs=[seq, grp, grp, col, col, per_group],
        out_shape=[shape(x.shape, x.dtype), shape(B.shape, B.dtype),
                   shape(C.shape, C.dtype), shape(dtc.shape, F32),
                   shape(csc.shape, F32),
                   shape((b, g, 1, heads * p), F32)],
        scratch_shapes=[pltpu.VMEM((heads * p, n), F32),
                        pltpu.VMEM((1, heads * p), F32)],
        compiler_params=_PARAMS, interpret=_backend.interpret(),
    )(x, B, C, dtr, csr, dtc, csc, d_row, states, dy,
      jnp.repeat(jnp.eye(heads, dtype=F32), p, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _ssd(x, B, C, dtr, csr, dtc, csc, d_row, chunk):
    """x [b, s, h * p]; B, C [b, s, g * n]; dt and the cumulative
    log-decay inside each chunk twice, token on lanes ([b, h, s]) and on
    sublanes ([b, g, s, h / g]); d_row [1, h * p]. The cumulative sum is
    an input of its own: its gradient goes back through XLA's cumsum."""
    return _fwd_call(x, B, C, dtr, csr, dtc, csc, d_row, chunk=chunk)[0]


def _ssd_fwd(x, B, C, dtr, csr, dtc, csc, d_row, chunk):
    y, states = _fwd_call(x, B, C, dtr, csr, dtc, csc, d_row, chunk=chunk)
    decays, states = (checkpoint_name(v, name) for name, v in zip(
        RESIDUAL_NAMES, ((dtr, csr, dtc, csc, d_row), states)))
    return y, (x, B, C, *decays, states)


def _ssd_bwd(chunk, res, dy):
    dx, dB, dC, ddt, dcs, dd = _bwd_call(*res, dy, chunk=chunk)
    # dt and the cumulative sums are read in both layouts and their
    # gradients written in one
    return (dx, dB, dC, jnp.zeros_like(res[3]), jnp.zeros_like(res[4]),
            ddt, dcs, dd.sum(0).reshape(res[7].shape))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_chunked(x, dt, A, B, C, D, chunk: int = 128):
    """x [b, s, h, p]; dt [b, s, h] (positive: softplus already taken);
    A [h] (negative); B, C [b, s, g, n] with h % g == 0; D [h].
    Returns y [b, s, h, p] in float32. A sequence that is no multiple
    of ``chunk`` is padded with steps of dt = 0, which leave the state
    as it is, and cut again.

    dt must be positive on every real step. The backward kernel takes
    dt's gradient inside a chunk from ``mix``'s by dividing dt out again,
    and reads a step of dt == 0 as padding: that step's dt gets the
    gradient of the state's update only, not of its own row of ``mix``.
    ``Mamba2Mixer``'s dt is a softplus, which reaches 0 in float32 only
    where its own derivative is 0 as well, so no parameter's gradient
    sees the difference."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    cd = jnp.result_type(x, B, C)       # one type for the products
    x, B, C = x.astype(cd), B.astype(cd), C.astype(cd)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, B, C))
    sp = s + pad
    dt = dt.astype(F32)
    # the cumulative log-decay inside each chunk
    cs = jnp.cumsum((dt * A.astype(F32)).reshape(b, sp // chunk, chunk, h),
                    axis=2).reshape(b, sp, h)

    def rows(v):
        return v.transpose(0, 2, 1)

    def cols(v):
        return v.reshape(b, sp, g, h // g).transpose(0, 2, 1, 3)

    y = _ssd(x.reshape(b, sp, h * p), B.reshape(b, sp, g * n),
             C.reshape(b, sp, g * n), rows(dt), rows(cs), cols(dt), cols(cs),
             jnp.repeat(D.astype(F32), p)[None], chunk)
    return y.reshape(b, sp, h, p)[:, :s]
