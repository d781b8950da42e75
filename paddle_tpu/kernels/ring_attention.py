"""Ring attention — context parallelism over the sequence dim.

Parity: PaddleNLP's RingFlashAttention (context_parallel_degree): KV
blocks rotate around the ring of sequence-parallel ranks via p2p while
queries stay resident, with online-softmax merging of per-block results
(SURVEY.md §5 "Long-context"), including its causal load-balanced
variant.

TPU-native: the ring is a ``shard_map`` over the "sep" axis with
``jax.lax.ppermute`` KV rotation — XLA lowers it to collective-permute
over ICI, overlapped with the per-block attention compute. Per-block
attention is the Pallas flash kernel (``mha_with_lse``) when shapes are
MXU-aligned (dense fallback otherwise) and block results merge by
logsumexp renormalization.

Causal load balancing (zigzag): the sequence is viewed as 2n half-chunks
and rank r owns half-chunks (r, 2n-1-r) — the canonical zigzag
assignment. Every ring step then costs every rank exactly two FULL
L×L block attentions (no computed-then-masked blocks), and the local
step is one causal flash call — per-rank FLOPs ≈ half of the naive
compute-everything-mask-later ring under causal. The zigzag
redistribution happens inside this function with two collective permutes
each way, so callers keep ordinary contiguous GSPMD sharding.

Backward is jax autodiff through the scan + ppermute (the reverse ring),
with the flash kernel's custom VJP per block (dlse folded into delta).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from . import _backend

NEG_INF = -1e30


def _use_flash(sq, sk, d) -> bool:
    return _backend.use_kernel(
        sq % 128 == 0 and sk % 128 == 0 and d % 128 == 0)


def _attn_lse(q, k, v, causal, scale):
    """(o [b,s,h,d], lse [b,h,s]) block attention; flash when aligned."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if _use_flash(sq, sk, d):
        from .pallas_attention import mha_with_lse

        return mha_with_lse(q, k, v, causal=causal, sm_scale=scale,
                            q_block=min(256, sq), k_block=min(256, sk))
    if h != hk:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((qi >= ki)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", (p / l).astype(v.dtype), v)
    lse = (m + jnp.log(l))[..., 0]  # [b,h,sq]
    return o.astype(q.dtype), lse


def _merge(o_a, lse_a, o_b, lse_b):
    """logsumexp-renormalized merge of two normalized partials."""
    lse_new = jnp.logaddexp(lse_a, lse_b)
    wa = jnp.exp(lse_a - lse_new)  # [b,h,s]
    wb = jnp.exp(lse_b - lse_new)
    o_new = (o_a * wa.transpose(0, 2, 1)[..., None]
             + o_b * wb.transpose(0, 2, 1)[..., None])
    return o_new, lse_new


def ring_attention(
    q, k, v,
    mesh: Optional[Mesh] = None,
    axis: str = "sep",
    causal: bool = True,
    scale: Optional[float] = None,
):
    """q,k,v: [batch, seq, heads, head_dim] — global shapes with the seq
    dim sharded contiguously over ``axis``. Returns attention output with
    the same sharding."""
    from ..distributed.sharding import current_mesh

    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        from .flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)

    d = q.shape[-1]
    scale_ = scale if scale is not None else d ** -0.5
    n = mesh.shape[axis]

    if not causal:
        local = _plain_local
    elif (q.shape[1] // n) % 2 == 0:
        local = _zigzag_local
    else:
        # odd local chunk: zigzag halves don't split evenly — use the
        # contiguous masked ring (correct, but without load balancing)
        local = _causal_contiguous_local
    spec = P(None, axis, None, None)
    fn = shard_map(
        lambda qc, kc, vc: local(qc, kc, vc, axis=axis, n=n, scale=scale_),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis}, check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# non-causal: plain contiguous ring (every block is full work anyway)
# ---------------------------------------------------------------------------
def _plain_local(qc, kc, vc, *, axis, n, scale):
    o0, lse0 = _attn_lse(qc, kc, vc, False, scale)

    def step(carry, _):
        k_blk, v_blk, o, lse = carry
        perm = [(s, (s + 1) % n) for s in range(n)]
        k_nxt = jax.lax.ppermute(k_blk, axis, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis, perm)
        o_b, lse_b = _attn_lse(qc, k_nxt, v_nxt, False, scale)
        o, lse = _merge(o, lse, o_b, lse_b)
        return (k_nxt, v_nxt, o, lse), None

    (k_f, v_f, o, lse), _ = jax.lax.scan(
        step, (kc, vc, o0, lse0), None, length=n - 1
    )
    return o.astype(qc.dtype)


# ---------------------------------------------------------------------------
# causal, odd local chunks: contiguous ring with masked blocks
# ---------------------------------------------------------------------------
def _causal_contiguous_local(qc, kc, vc, *, axis, n, scale):
    b, sl, h, dd = qc.shape
    hk = kc.shape[2]
    my = jax.lax.axis_index(axis)

    def block(q, k, v, is_diag):
        """Dense block attention with a traced diagonal flag."""
        if h != hk:
            rep = h // hk
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        qi = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 1)
        keep = jnp.logical_or(jnp.logical_not(is_diag),
                              (qi >= ki)[None, None])
        s = jnp.where(keep, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = jnp.einsum("bhqk,bkhd->bqhd", (p / l_safe).astype(v.dtype), v)
        return o.astype(q.dtype), (m + jnp.log(l_safe))[..., 0]

    o0, lse0 = block(qc, kc, vc, jnp.bool_(True))

    def stepi(carry, i):
        k_blk, v_blk, o, lse = carry
        perm = [(s_, (s_ + 1) % n) for s_ in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        src = (my - i) % n
        o_b, lse_b = block(qc, k_blk, v_blk, jnp.bool_(False))
        # blocks from the future contribute nothing
        use = src < my
        lse_b = jnp.where(use, lse_b, NEG_INF)
        o_m, lse_m = _merge(o, lse, o_b, lse_b)
        return (k_blk, v_blk, o_m, lse_m), None

    (k_f, v_f, o, lse), _ = jax.lax.scan(
        stepi, (kc, vc, o0, lse0), jnp.arange(1, n)
    )
    return o.astype(qc.dtype)


# ---------------------------------------------------------------------------
# causal: zigzag load-balanced ring
# ---------------------------------------------------------------------------
def _chunk_owner(c, n):
    """Zigzag owner rank of global half-chunk c (of 2n)."""
    return c if c < n else 2 * n - 1 - c


def _zigzag_local(qc, kc, vc, *, axis, n, scale):
    b, sl, h, dd = qc.shape
    L = sl // 2
    r = jax.lax.axis_index(axis)

    # --- redistribute contiguous -> zigzag -------------------------------
    # rank s holds global half-chunks (2s, 2s+1); zigzag wants (r, 2n-1-r)
    perm_even = [(s, _chunk_owner(2 * s, n)) for s in range(n)]
    perm_odd = [(s, _chunk_owner(2 * s + 1, n)) for s in range(n)]

    def to_zigzag(x):
        a_even = jax.lax.ppermute(x[:, :L], axis, perm_even)
        a_odd = jax.lax.ppermute(x[:, L:], axis, perm_odd)
        # this rank's chunks are {r, 2n-1-r}: exactly one is even
        r_even = (r % 2 == 0)
        slot0 = jnp.where(r_even, a_even, a_odd)  # chunk r
        slot1 = jnp.where(r_even, a_odd, a_even)  # chunk 2n-1-r
        return slot0, slot1

    q0, q1 = to_zigzag(qc)
    k0, k1 = to_zigzag(kc)
    v0, v1 = to_zigzag(vc)

    # --- step 0: local causal attention over [chunk r ; chunk 2n-1-r] ---
    # concat order == global order (r < 2n-1-r), so plain causal applies
    o_loc, lse_loc = _attn_lse(
        jnp.concatenate([q0, q1], axis=1),
        jnp.concatenate([k0, k1], axis=1),
        jnp.concatenate([v0, v1], axis=1),
        True, scale,
    )
    acc0_o, acc0_l = o_loc[:, :L], lse_loc[:, :, :L]
    acc1_o, acc1_l = o_loc[:, L:], lse_loc[:, :, L:]

    # --- ring steps: two FULL LxL attentions per step, no masked work ---
    # scan with explicit step index to know src = (r - i) % n
    def stepi(carry, i):
        k0c, k1c, v0c, v1c, a0o, a0l, a1o, a1l = carry
        perm = [(s, (s + 1) % n) for s in range(n)]
        k0c = jax.lax.ppermute(k0c, axis, perm)
        k1c = jax.lax.ppermute(k1c, axis, perm)
        v0c = jax.lax.ppermute(v0c, axis, perm)
        v1c = jax.lax.ppermute(v1c, axis, perm)
        src = (r - i) % n  # rank whose zigzag pair we now hold
        f = src < r  # True: kv pair is from the "past" side for chunk r

        # call 1: q = (f ? chunk r : chunk 2n-1-r) x kv chunk src (full)
        q_sel = jnp.where(f, q0, q1)
        o1, l1 = _attn_lse(q_sel, k0c, v0c, False, scale)
        # call 2: q = chunk 2n-1-r x (f ? kv chunk src : kv chunk
        # 2n-1-src) (full)
        k_sel = jnp.where(f, k0c, k1c)
        v_sel = jnp.where(f, v0c, v1c)
        o2, l2 = _attn_lse(q1, k_sel, v_sel, False, scale)

        m0o, m0l = _merge(a0o, a0l, o1, l1)
        a0o = jnp.where(f, m0o, a0o)
        a0l = jnp.where(f, m0l, a0l)
        t1o, t1l = _merge(a1o, a1l, o2, l2)
        e1o, e1l = _merge(t1o, t1l, o1, l1)
        a1o = jnp.where(f, t1o, e1o)
        a1l = jnp.where(f, t1l, e1l)
        return (k0c, k1c, v0c, v1c, a0o, a0l, a1o, a1l), None

    (k0, k1, v0, v1, acc0_o, acc0_l, acc1_o, acc1_l), _ = jax.lax.scan(
        stepi,
        (k0, k1, v0, v1, acc0_o, acc0_l, acc1_o, acc1_l),
        jnp.arange(1, n),
    )

    # --- redistribute zigzag -> contiguous ------------------------------
    inv_even = [(d_, s_) for (s_, d_) in perm_even]
    inv_odd = [(d_, s_) for (s_, d_) in perm_odd]
    r_even = (r % 2 == 0)
    even_out = jnp.where(r_even, acc0_o, acc1_o)  # the even chunk we hold
    odd_out = jnp.where(r_even, acc1_o, acc0_o)
    h0 = jax.lax.ppermute(even_out, axis, inv_even)  # chunk 2r
    h1 = jax.lax.ppermute(odd_out, axis, inv_odd)  # chunk 2r+1
    return jnp.concatenate([h0, h1], axis=1).astype(qc.dtype)
