"""Gated (SwiGLU) experts over one buffer of rows sorted by expert, as
grouped Pallas matmuls.

The caller (``distributed/moe.py``) lays the held rows out in tiles of
``tile`` rows: an expert's stretch starts on a tile boundary, so a tile
belongs to ONE expert, and rows past an expert's last are zeros with a
zero gate. Every kernel here walks those tiles with the expert's
matrices chosen by a scalar-prefetched map ``tile -> expert``:

    gate_up     a = x w3, b = x w1, h = silu(a) * b      [rows, h]
    down        y = (h w2) * gate                        [rows, m] float32
    d_hidden    dh = (dy w2^T), from it da, db, h * gate and dgate
    d_rows      dx = da w3^T + db w1^T                   [rows, m]
    d_weights   x^T db, x^T da (one call), (h * gate)^T dy (another):
                an expert's float32 sum stays in VMEM over that expert's
                tiles and is written once, in the matrices' type

The static bound on the tiles is every routed row on the held experts
(dropless); ``n_live`` [1] says how many tiles this step filled, and it
is the grid's size: the tiles past it are not visited, so the cost
follows the live tiles. Every expert owns at least one tile, so every
expert's weight gradient is written.

Grid: (a block of output columns, the tiles); the tiles are the inner
axis, so an expert's block of a matrix is fetched once for all of its
consecutive tiles and the rows stream past it. The blocks of columns
are the widest that fit ``_VMEM_BLOCK_BYTES``.

Precision: the products take their operands in the rows' type (bf16 in
training) and accumulate in float32; ``silu(a) * b``, the gate, the
gate's gradient and the weight gradients' sums over an expert's rows
are float32, rounded once where they leave the kernel. The backward
makes ``h`` again from the ``a`` and ``b`` the forward kept.

Shapes: ``m`` and ``h`` whole 128-lanes, ``tile`` a multiple of 16
(``distributed/moe.py`` asks ``_backend.use_kernel`` with that); off the
chip the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

F32 = jnp.float32
LANES = 128

# what a step's blocks may take, counted twice for the pipeline's second
# buffer; the limit handed to Mosaic leaves room for the float32 values
# the body holds between its products. On a v5e at m 2048, h 1792 a
# layer's forward and backward take 9.81 ms with 16 MB, 7.97 with 40 and
# no less with 64 or 90; tiles of 128, 640 and 1280 rows are slower than
# 256 (PERF.md, PR 39)
_VMEM_BLOCK_BYTES = 40 << 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=96 << 20)


def aligned(m: int, h: int, tile: int) -> bool:
    """Whether the kernels' blocks tile these widths on the chip."""
    return m % LANES == 0 and h % LANES == 0 and tile % 16 == 0


def _dot(a, b, contract):
    """a . b over the given axis of each, float32 accumulation."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=F32)


def _cols(total: int, fixed_bytes: int, bytes_per_col: int) -> int:
    """The widest block of ``total`` columns, in whole lanes and dividing
    it, whose blocks fit the budget (``fixed_bytes`` for what does not
    grow with the block); never under one lane tile."""
    units = total // LANES
    for parts in range(1, units + 1):
        if units % parts == 0 and (
                fixed_bytes + total // parts * bytes_per_col
                <= _VMEM_BLOCK_BYTES):
            return total // parts
    return LANES


def _call(name, kernel, tile_expert, n_live, grid_cols, in_specs, out_specs,
          out_shape, args, scratch=()):
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(grid_cols, n_live[0]),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=list(scratch)),
        out_shape=out_shape, compiler_params=_PARAMS,
        interpret=_backend.interpret(),
    )(tile_expert, n_live, *args)


def _rows(tile, width):
    """A tile's rows, all ``width`` columns."""
    return pl.BlockSpec((tile, width), lambda j, i, te, nl: (i, 0))


def _rows_cols(tile, cols):
    """A tile's rows, block ``j`` of the columns."""
    return pl.BlockSpec((tile, cols), lambda j, i, te, nl: (i, j))


def _expert_cols(rows, cols):
    """Block ``j`` of the columns of the tile's expert's matrix."""
    return pl.BlockSpec((None, rows, cols),
                        lambda j, i, te, nl: (te[i], 0, j))


def _expert_rows(rows, cols):
    """Block ``j`` of the rows of the tile's expert's matrix."""
    return pl.BlockSpec((None, rows, cols),
                        lambda j, i, te, nl: (te[i], j, 0))


def _silu(a):
    s = jax.nn.sigmoid(a)
    return a * s, s


# ---------------------------------------------------------------- forward
def _gate_up_kernel(te, nl, x_ref, w1_ref, w3_ref, a_ref, b_ref, h_ref):
    x = x_ref[...]
    a = _dot(x, w3_ref[...], (1, 0))
    b = _dot(x, w1_ref[...], (1, 0))
    a_ref[...] = a.astype(a_ref.dtype)
    b_ref[...] = b.astype(b_ref.dtype)
    h_ref[...] = (_silu(a)[0] * b).astype(h_ref.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def gate_up(xs, w1, w3, tile_expert, n_live, *, tile):
    """(a, b, h) [rows, h] in ``xs``'s type: both pre-activations (what
    the backward reads) and ``silu(a) * b``."""
    (rows, m), h = xs.shape, w1.shape[2]
    size = xs.dtype.itemsize
    cols = _cols(h, 2 * tile * m * size,
                 2 * (2 * m + 3 * tile) * size + 3 * tile * 4)
    out = jax.ShapeDtypeStruct((rows, h), xs.dtype)
    return _call(
        "experts_gate_up", _gate_up_kernel, tile_expert, n_live, h // cols,
        [_rows(tile, m), _expert_cols(m, cols), _expert_cols(m, cols)],
        [_rows_cols(tile, cols)] * 3, [out] * 3, (xs, w1, w3))


def _down_kernel(te, nl, h_ref, w2_ref, g_ref, y_ref):
    y_ref[...] = _dot(h_ref[...], w2_ref[...], (1, 0)) * g_ref[...]


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def down(hs, w2, gs, tile_expert, n_live, *, tile):
    """(hs w2) * gate, float32 [rows, m]; ``gs`` [rows, 1] float32."""
    (rows, h), m = hs.shape, w2.shape[2]
    size = hs.dtype.itemsize
    cols = _cols(m, 2 * tile * h * size, 2 * (h * size + tile * 4))
    return _call(
        "experts_down", _down_kernel, tile_expert, n_live, m // cols,
        [_rows(tile, h), _expert_cols(h, cols), _rows(tile, 1)],
        _rows_cols(tile, cols), jax.ShapeDtypeStruct((rows, m), F32),
        (hs, w2, gs))


# --------------------------------------------------------------- backward
def _d_hidden_kernel(te, nl, dy_ref, w2_ref, a_ref, b_ref, g_ref,
                     da_ref, db_ref, hg_ref, dg_ref):
    dh = _dot(dy_ref[...], w2_ref[...], (1, 1))  # before the gate
    a, b, g = a_ref[...].astype(F32), b_ref[...].astype(F32), g_ref[...]
    act, s = _silu(a)
    h = act * b
    dg_ref[...] = jnp.sum(dh * h, axis=1, keepdims=True)
    dh = dh * g
    da_ref[...] = (dh * b * (s * (1.0 + a * (1.0 - s)))).astype(da_ref.dtype)
    db_ref[...] = (dh * act).astype(db_ref.dtype)
    hg_ref[...] = (h * g).astype(hg_ref.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def d_hidden(dys, w2, a, b, gs, tile_expert, n_live, *, tile):
    """From the rows' cotangent ``dys`` [rows, m] (before the gate):
    (da, db, h * gate) [rows, h] in its type, and the gate's gradient
    [rows] float32, ``sum(dys * (h w2))`` made as ``sum((dys w2^T) * h)``
    so that ``h w2`` is not made again."""
    (rows, m), h = dys.shape, w2.shape[1]
    size = dys.dtype.itemsize
    cols = _cols(h, 2 * tile * m * size,
                 2 * (m + 5 * tile) * size + 4 * tile * 4)
    parts = h // cols
    col = pl.BlockSpec((None, tile, 1), lambda j, i, te, nl: (j, i, 0))
    out = jax.ShapeDtypeStruct((rows, h), dys.dtype)
    da, db, hg, dg = _call(
        "experts_d_hidden", _d_hidden_kernel, tile_expert, n_live, parts,
        [_rows(tile, m), _expert_rows(cols, m), _rows_cols(tile, cols),
         _rows_cols(tile, cols), _rows(tile, 1)],
        [_rows_cols(tile, cols)] * 3 + [col],
        [out] * 3 + [jax.ShapeDtypeStruct((parts, rows, 1), F32)],
        (dys, w2, a, b, gs))
    return da, db, hg, jnp.sum(dg[:, :, 0], axis=0)


def _d_rows_kernel(te, nl, da_ref, db_ref, w3_ref, w1_ref, dx_ref):
    dx_ref[...] = (_dot(da_ref[...], w3_ref[...], (1, 1))
                   + _dot(db_ref[...], w1_ref[...], (1, 1))
                   ).astype(dx_ref.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def d_rows(da, db, w1, w3, tile_expert, n_live, *, tile):
    """da w3^T + db w1^T [rows, m] in ``da``'s type."""
    (rows, h), m = da.shape, w1.shape[1]
    size = da.dtype.itemsize
    cols = _cols(m, 4 * tile * h * size,
                 2 * (2 * h + tile) * size + tile * 4)
    return _call(
        "experts_d_rows", _d_rows_kernel, tile_expert, n_live, m // cols,
        [_rows(tile, h), _rows(tile, h), _expert_rows(cols, h),
         _expert_rows(cols, h)],
        _rows_cols(tile, cols), jax.ShapeDtypeStruct((rows, m), da.dtype),
        (da, db, w3, w1))


def _d_weights_kernel(te, nl, lhs_ref, *refs):
    """refs: a right side [tile, n] each, then an output block [k, n]
    each, then a float32 accumulator each."""
    n = len(refs) // 3
    rhs, outs, accs = refs[:n], refs[n:2 * n], refs[2 * n:]
    i, last = pl.program_id(1), nl[0] - 1
    e = te[i]
    opens = jnp.logical_or(i == 0, te[jnp.maximum(i - 1, 0)] != e)
    closes = jnp.logical_or(i == last, te[jnp.minimum(i + 1, last)] != e)
    lhs = lhs_ref[...]

    @pl.when(opens)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros(acc.shape, F32)

    for r, acc in zip(rhs, accs):
        acc[...] += _dot(lhs, r[...], (0, 0))

    @pl.when(closes)
    def _():
        for o, acc in zip(outs, accs):
            o[...] = acc[...].astype(o.dtype)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("tile", "n_experts", "dtype"))
def d_weights(lhs, rhs: tuple, tile_expert, n_live, *, tile, n_experts,
              dtype):
    """For each right side ``r`` [rows, n]: per expert, ``lhs^T r`` over
    the expert's tiles, [n_experts, k, n] in ``dtype``. The float32 sum
    of a block of ``k`` stays in VMEM from the expert's first tile to
    its last."""
    (rows, k), n = lhs.shape, rhs[0].shape[1]
    size, out_size = lhs.dtype.itemsize, jnp.dtype(dtype).itemsize
    per = len(rhs)
    cols = _cols(k, 2 * per * tile * n * size,
                 2 * tile * size + per * n * (4 + 2 * out_size))
    out = pl.BlockSpec((None, cols, n), lambda j, i, te, nl: (te[i], j, 0))
    return _call(
        "experts_d_weights", _d_weights_kernel, tile_expert, n_live, k // cols,
        [_rows_cols(tile, cols)] + [_rows(tile, n)] * per, [out] * per,
        [jax.ShapeDtypeStruct((n_experts, k, n), dtype)] * per,
        (lhs, *rhs), [pltpu.VMEM((cols, n), F32)] * per)
