"""The Mamba-2 recurrence in its chunked form (state-space duality).

Per head, with a scalar decay and a state ``S`` of ``[p, n]``
(``S_0 = 0``):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D * x_t

``B`` and ``C`` are shared by the heads of a group. Walked step by step
this is 2 * p * n multiply-adds a token and head on the VPU, one token
after the other. The chunked form does the same sum on the MXU: inside
a chunk of ``Q`` tokens the outputs are a masked ``(C B^T) * decay``
product against ``x``; every chunk leaves a state, the states are
carried from chunk to chunk by a ``[chunks, chunks]`` product of decays,
and each token adds what the state before its chunk gives it. All of it
is ``einsum`` over ``[.., Q, Q]`` and ``[.., p, n]`` blocks; no
``[s, p, n]`` tensor exists.

Precision: ``dt``, ``A``, every cumulative sum, every decay and the
chunk states are float32; the carry of the states between chunks runs
at precision ``highest`` (it is 1/1000 of the work). The two large
products take ``x`` in its own type (bf16 in training) with float32
accumulation.

Memory: the chunk intermediates (``[b, chunks, heads, Q, Q]`` decays
and scores, float32) are large beside the inputs and cheap to make
again. The caller decides: ``Mamba2Mixer`` rematerialises the whole
stretch between its two projections, this function included, so that
it is recomputed once; a caller that differentiates ``ssd_chunked`` on
its own wraps it in ``jax.checkpoint``.

The definition above, walked step by step, is the benchmark's plain
reference (``chipbench/reference/nemotron_h.py``); ``tests/test_ssd.py``
holds this file against it. ``kernels/selective_scan.py`` is the other
layer: Mamba-1's S6, a decay
per channel and state column, walked by a Pallas kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _segsum(a):
    """a [..., Q] -> [..., Q, Q]: sum of a over (j, i], for i >= j;
    -inf above the diagonal, so that exp() of it is the causal decay."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff, -jnp.inf)


def _ssd(x, dt, A, B, C, D, chunk):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    c = s // chunk
    f32 = jnp.float32
    xc = x.reshape(b, c, chunk, g, h // g, p)
    Bc = B.reshape(b, c, chunk, g, n)
    Cc = C.reshape(b, c, chunk, g, n)
    dtc = dt.astype(f32).reshape(b, c, chunk, g, h // g)
    a = dtc * A.astype(f32).reshape(g, h // g)      # log of a step's decay
    a = a.transpose(0, 1, 3, 4, 2)                  # [b, c, g, r, Q]
    a_cum = jnp.cumsum(a, axis=-1)

    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(a_(j,i]) dt_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=f32)
    mix = scores[:, :, :, None] * jnp.exp(_segsum(a)) \
        * dtc.transpose(0, 1, 3, 4, 2)[..., None, :]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mix.astype(x.dtype), xc,
                   preferred_element_type=f32)

    # the state each chunk leaves, from a zero state
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)       # [b, c, g, r, Q]
    xw = xc * (to_end * dtc.transpose(0, 1, 3, 4, 2)).transpose(
        0, 1, 4, 2, 3)[..., None].astype(x.dtype)
    states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, Bc,
                        preferred_element_type=f32)

    # the state before each chunk: earlier chunks' states, decayed
    total = jnp.pad(a_cum[..., -1], ((0, 0), (1, 0), (0, 0), (0, 0)))
    carry = jnp.exp(_segsum(total.transpose(0, 2, 3, 1)))  # [b,g,r,c+1,c+1]
    before = jnp.einsum("bgrzc,bcgrpn->bzgrpn", carry[..., :-1, 1:],
                        states, precision=HI)

    # what that state gives each token of the chunk
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", Cc.astype(f32), before,
                       preferred_element_type=f32) \
        * jnp.exp(a_cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(b, s, h, p)
    return y + x.astype(f32) * D.astype(f32)[:, None]


def ssd_chunked(x, dt, A, B, C, D, chunk: int = 128):
    """x [b, s, h, p]; dt [b, s, h] (positive: softplus already taken);
    A [h] (negative); B, C [b, s, g, n] with h % g == 0; D [h].
    Returns y [b, s, h, p] in float32. A sequence that is no multiple
    of ``chunk`` is padded with steps of dt = 0, which leave the state
    as it is, and cut again."""
    s = x.shape[1]
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, B, C))
    return _ssd(x, dt, A, B, C, D, chunk)[:, :s]
