"""Paged KV cache (parity: the reference's decode-path cache machinery —
phi ``masked_multihead_attention`` / ``fused_multi_transformer``'s
contiguous per-sequence caches — upgraded to a vLLM-style page pool).

TPU-native design: XLA needs static shapes, so the pool is a fixed
tensor ``[kv_heads, n_pages, page_size, head_dim]`` per layer and the
indirection is data: a ``block_table`` [slots, max_pages] of page ids
and per-slot ``seq_lens``. Gathers over the page axis compile to
efficient dynamic-gathers; no recompilation as sequences come and go.
The pool is HEAD-MAJOR: one (head, page) block is contiguous with minor
dims (page_size, head_dim), which is what the Pallas decode kernel's
per-step DMA needs (TPU tiles the last two dims — a head-minor pool
would make the per-head slice strided and un-lowerable), and it puts
the tensor-parallel sharding axis (kv heads) first.
The win over per-slot contiguous caches is oversubscription: the pool
holds ``n_pages × page_size`` tokens total, which can be far less than
``slots × max_len`` when sequence lengths vary — the same HBM savings
that motivate paging on GPUs, but with the block-table gather living
inside one jitted decode program.

Page allocation (free-list) is host-side bookkeeping in the engine —
it's O(requests), not O(tokens), and never enters the compiled program.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class PagedLayerCache(NamedTuple):
    """Per-layer page pool + indirection (all device arrays).

    ``k_scale``/``v_scale`` are present only for int8 pools: per-ROW
    f32 dequant scales laid out ``[kv_heads, n_pages, page_size, 1]``
    so a page's scale rows travel WITH the page — adopt/COW/evict are
    page-id bookkeeping, and the scale arrays are indexed by the same
    page ids, so prefix sharing and rollback carry quantization state
    for free. The trailing 1 keeps the scale blocks the same
    (sublane, lane)-shaped as the pool blocks the Pallas decode kernel
    already streams (page_size × d with d→1)."""

    k_pages: jax.Array  # [kv_heads, n_pages, page_size, head_dim]
    v_pages: jax.Array  # [kv_heads, n_pages, page_size, head_dim]
    k_scale: Optional[jax.Array] = None  # [kv_heads, n_pages, page_size, 1]
    v_scale: Optional[jax.Array] = None


class PagedState(NamedTuple):
    """Cross-layer decode state carried through the jitted step."""

    block_tables: jax.Array  # [slots, max_pages] int32 page ids
    seq_lens: jax.Array  # [slots] int32 — tokens already in cache


class QuantizedKV(NamedTuple):
    """int8 CONTIGUOUS cache side (K or V): payload + per-row scales.

    q: [slots, max_len, kv_heads, head_dim] int8;
    scale: [slots, max_len, kv_heads] f32 — one symmetric absmax scale
    per written row per head (the "block row" granularity: dequant is
    ``q * scale[..., None]``). Drop-in for the plain array in the
    engine's per-layer ``(K, V)`` tuples — ``shape``/``dtype`` mirror
    the payload so shape-derived dispatch (chunk length, fused-kernel
    gating) keeps working."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


# one eps for every int8-KV quantization site — the kernels own it,
# this module's XLA append paths import it (see the constant's note
# in kernels/paged_attention.py)
from ..kernels.paged_attention import KV_QUANT_EPS  # noqa: E402


def quantize_kv_rows(x, out_dtype=jnp.int8):
    """Symmetric per-row int8 over the LAST axis: x [..., d] →
    (q int8 [..., d], scale f32 [...]). THE quantization rule for every
    KV append path — host XLA scatters and the fused Pallas kernels
    share the same math (absmax/127, round, clip) so fused and unfused
    engines write bit-identical pools."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, KV_QUANT_EPS)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127) \
        .astype(out_dtype)
    return q, scale


def dequantize_kv(c):
    """QuantizedKV (or raw array) → f32 values."""
    if isinstance(c, QuantizedKV):
        return c.q.astype(jnp.float32) * c.scale[..., None]
    return c


def init_paged_pool(n_layers: int, n_pages: int, page_size: int,
                    kv_heads: int, head_dim: int, dtype=jnp.bfloat16):
    """int8 ``dtype`` builds quantized pools with per-row scale arrays
    alongside (zero-init: q=0 × scale=0 dequantizes to the same zeros a
    fp pool starts with; every read row is appended first)."""
    quant = jnp.dtype(dtype) == jnp.int8

    def one():
        pages = jnp.zeros((kv_heads, n_pages, page_size, head_dim),
                          dtype)
        scale = (jnp.zeros((kv_heads, n_pages, page_size, 1),
                           jnp.float32) if quant else None)
        return pages, scale

    out = []
    for _ in range(n_layers):
        kp, ks = one()
        vp, vs = one()
        out.append(PagedLayerCache(kp, vp, ks, vs))
    return out


def append_kv(cache: PagedLayerCache, state: PagedState, k, v
              ) -> PagedLayerCache:
    """Write one token's K/V per slot at its current length.

    k, v: [slots, 1, kv_heads, head_dim]. The destination of slot i is
    page ``block_tables[i, len_i // page_size]`` offset ``len_i %
    page_size`` — a scatter with computed indices, fully inside jit.
    """
    page_size = cache.k_pages.shape[2]
    slots = k.shape[0]
    lens = state.seq_lens
    page_idx = lens // page_size
    offs = lens % page_size
    pages = state.block_tables[jnp.arange(slots), page_idx]  # [slots]
    if cache.k_scale is not None:
        # quantize-on-append: the row's int8 payload and its f32 scale
        # land at the SAME (page, offset) — the scale rides the page
        kq, ks = quantize_kv_rows(k[:, 0])  # [slots, kvh, d] / [s, kvh]
        vq, vs = quantize_kv_rows(v[:, 0])
        return cache._replace(
            k_pages=cache.k_pages.at[:, pages, offs].set(
                kq.transpose(1, 0, 2)),
            v_pages=cache.v_pages.at[:, pages, offs].set(
                vq.transpose(1, 0, 2)),
            k_scale=cache.k_scale.at[:, pages, offs, 0].set(
                ks.transpose(1, 0)),
            v_scale=cache.v_scale.at[:, pages, offs, 0].set(
                vs.transpose(1, 0)),
        )
    # destination [kvh, pages[i], offs[i]] <- k[i, 0, h]: value laid out
    # head-major to match the pool
    k_pages = cache.k_pages.at[:, pages, offs].set(
        k[:, 0].astype(cache.k_pages.dtype).transpose(1, 0, 2))
    v_pages = cache.v_pages.at[:, pages, offs].set(
        v[:, 0].astype(cache.v_pages.dtype).transpose(1, 0, 2))
    return cache._replace(k_pages=k_pages, v_pages=v_pages)


def append_kv_chunk(cache: PagedLayerCache, state: PagedState, k, v,
                    start) -> PagedLayerCache:
    """Write a CHUNK of tokens per slot through the block table.

    k, v: [slots, s, kv_heads, head_dim]; ``start``: [slots] int32 —
    slot i's rows land at positions ``start[i] .. start[i]+s-1`` (page
    ``block_tables[i, pos // page_size]`` offset ``pos % page_size``).
    Positions past the block table's span (including the engine's
    ``start = max_len`` "not prefilling this call" sentinel) scatter
    with ``mode="drop"`` — a dropped write, never a clamped one.
    """
    page_size = cache.k_pages.shape[2]
    slots, s = k.shape[0], k.shape[1]
    max_pages = state.block_tables.shape[1]
    n_pages = cache.k_pages.shape[1]
    pos = start[:, None] + jnp.arange(s, dtype=start.dtype)[None, :]
    page_idx = pos // page_size
    offs = pos % page_size
    valid = page_idx < max_pages
    safe = jnp.minimum(page_idx, max_pages - 1)
    pages = jnp.take_along_axis(state.block_tables, safe, axis=1)
    pages = jnp.where(valid, pages, n_pages)  # OOB page id -> dropped
    if cache.k_scale is not None:
        kq, ks = quantize_kv_rows(k)  # [slots, s, kvh, d] / [slots, s, kvh]
        vq, vs = quantize_kv_rows(v)
        return cache._replace(
            k_pages=cache.k_pages.at[:, pages, offs].set(
                kq.transpose(2, 0, 1, 3), mode="drop"),
            v_pages=cache.v_pages.at[:, pages, offs].set(
                vq.transpose(2, 0, 1, 3), mode="drop"),
            k_scale=cache.k_scale.at[:, pages, offs, 0].set(
                ks.transpose(2, 0, 1), mode="drop"),
            v_scale=cache.v_scale.at[:, pages, offs, 0].set(
                vs.transpose(2, 0, 1), mode="drop"),
        )
    # value laid out head-major to match the pool: [kvh, slots, s, d]
    k_pages = cache.k_pages.at[:, pages, offs].set(
        k.astype(cache.k_pages.dtype).transpose(2, 0, 1, 3), mode="drop")
    v_pages = cache.v_pages.at[:, pages, offs].set(
        v.astype(cache.v_pages.dtype).transpose(2, 0, 1, 3), mode="drop")
    return cache._replace(k_pages=k_pages, v_pages=v_pages)


def gather_kv(cache: PagedLayerCache, state: PagedState
              ) -> Tuple[jax.Array, jax.Array]:
    """Materialize each slot's logical KV view: [slots, max_ctx, kvh, d]
    where max_ctx = max_pages * page_size (mask handles the tail).
    int8 pools are DEQUANTIZED in the gather (q × per-row scale), so
    every downstream consumer sees f32 values."""
    bt = state.block_tables  # [slots, max_pages]
    slots, max_pages = bt.shape
    kvh, _, page_size, d = cache.k_pages.shape
    k = cache.k_pages[:, bt]  # [kvh, slots, max_pages, page_size, d]
    v = cache.v_pages[:, bt]
    if cache.k_scale is not None:
        k = k.astype(jnp.float32) * cache.k_scale[:, bt]
        v = v.astype(jnp.float32) * cache.v_scale[:, bt]
    k = k.reshape(kvh, slots, max_pages * page_size, d)
    v = v.reshape(kvh, slots, max_pages * page_size, d)
    return (k.transpose(1, 2, 0, 3), v.transpose(1, 2, 0, 3))


def _use_pallas_decode(cache: PagedLayerCache) -> bool:
    from ..kernels import _backend
    from ..kernels.decode_attention import decode_tiles_ok

    if cache.k_scale is not None:
        # int8 pools: the plain (non-fused) block-table kernel has no
        # dequant path — the FUSED kernel is the int8 production path,
        # and this dispatch's fallback is the dense dequant reference
        return False
    page_size, d = cache.k_pages.shape[2], cache.k_pages.shape[3]
    return _backend.use_kernel(decode_tiles_ok(d, page_size))


def paged_attention(q, cache: PagedLayerCache, state: PagedState,
                    scale=None):
    """Decode attention over the paged cache.

    q: [slots, 1, heads, head_dim] (GQA: heads a multiple of kv_heads).
    The current token's K/V must already be appended, so slot i attends
    to positions [0, seq_lens[i]] inclusive of itself.
    Returns [slots, 1, heads, head_dim].

    On TPU this runs the Pallas block-table kernel
    (kernels/paged_attention.py): pages stream straight from the pool by
    page id — per-step HBM traffic ∝ Σ seq_lens rather than the
    slots × max_ctx of the dense gather fallback below.
    """
    slots, one, h, d = q.shape
    kvh_ = cache.k_pages.shape[0]
    if _use_pallas_decode(cache) and h % kvh_ == 0:
        from ..kernels.paged_attention import paged_decode_attention

        qg = q[:, 0].reshape(slots, kvh_, h // kvh_, d)
        out = paged_decode_attention(
            qg, cache.k_pages, cache.v_pages, state.block_tables,
            state.seq_lens, scale=scale,
        )
        return out.reshape(slots, 1, h, d)
    return dense_paged_attention(q, cache, state, scale=scale)


def dense_paged_attention(q, cache: PagedLayerCache, state: PagedState,
                          scale=None):
    """Dense-gather decode fallback (and the kernels' numeric reference):
    materializes each slot's full [max_ctx] view and masks — the
    slots × max_len traffic the Pallas paths avoid."""
    slots, one, h, d = q.shape
    k, v = gather_kv(cache, state)  # [slots, ctx, kvh, d]
    ctx = k.shape[1]
    kvh = k.shape[2]
    if h != kvh:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    # [slots, h, 1, ctx]
    s = jnp.einsum("sqhd,skhd->shqk", qf, k.astype(jnp.float32))
    mask = jnp.arange(ctx)[None, :] <= state.seq_lens[:, None]  # [slots,ctx]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shqk,skhd->sqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


class PagePool:
    """Host-side page allocator (free list) + device state mirror.

    The engine calls ``alloc``/``free`` as requests arrive/finish and
    pushes the updated block table to the device as plain int32 data —
    allocation never triggers recompilation.

    Pages carry REFCOUNTS so a prefix cache can share them: ``ref[p]``
    counts owners (each slot holding p in its block table, plus the
    prefix store if it retains p). A page returns to the free list only
    at refcount 0; a slot must never write a page with refcount > 1 —
    the engine copies it first (``cow``).
    """

    def __init__(self, n_pages: int, page_size: int, slots: int,
                 max_pages_per_slot: int, reserve_sink: bool = False):
        """``reserve_sink``: keep page 0 out of circulation as a write
        sink for inactive slots (their block tables point at it)."""
        self.n_pages = n_pages
        self.page_size = page_size
        self.slots = slots
        self.max_pages_per_slot = max_pages_per_slot
        # recorded for the invariant sanitizer: the sink page must
        # never re-enter circulation
        self.reserve_sink = reserve_sink
        first = 1 if reserve_sink else 0
        self._free = list(range(n_pages - 1, first - 1, -1))
        self.block_tables = np.zeros((slots, max_pages_per_slot), np.int32)
        self.pages_of: dict = {i: [] for i in range(slots)}
        self.ref: dict = {}  # page id -> owner count (absent == 0)
        # pages with ref > 1 — lets the engine's decode-time COW guard
        # skip its per-slot scan when NOTHING is shared (prefix-cache
        # off, or no request published blocks yet). With the cache on
        # and warm, published prompt blocks keep this > 0, and the
        # guard pays its window-bounded scan (a couple of dict lookups
        # per active slot per dispatch)
        self.shared_pages = 0

    def _bump(self, page: int):
        n = self.ref.get(page, 0) + 1
        self.ref[page] = n
        if n == 2:
            self.shared_pages += 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def alloc(self, slot: int, n_tokens: int) -> bool:
        """Ensure slot has pages for n_tokens total; False if pool full."""
        have = len(self.pages_of[slot])
        need = self.pages_needed(n_tokens) - have
        if need > len(self._free) or \
                have + max(need, 0) > self.max_pages_per_slot:
            return False
        for _ in range(max(need, 0)):
            p = self._free.pop()
            self.block_tables[slot, len(self.pages_of[slot])] = p
            self.pages_of[slot].append(p)
            self.ref[p] = 1
        return True

    def adopt(self, slot: int, pages) -> bool:
        """Prefix-share: place already-populated ``pages`` at the FRONT
        of an empty slot's block table (refcount + 1 each) — the caller
        tops the rest up with ``alloc``. False if the list alone would
        exceed the per-slot maximum (nothing adopted)."""
        if self.pages_of[slot]:
            raise ValueError(f"adopt() needs an empty slot; slot {slot} "
                             f"holds {len(self.pages_of[slot])} pages")
        if len(pages) > self.max_pages_per_slot:
            return False
        for p in pages:
            self.block_tables[slot, len(self.pages_of[slot])] = p
            self.pages_of[slot].append(p)
            self._bump(p)
        return True

    def retain(self, page: int):
        """Add an owner (the prefix store pinning a page)."""
        self._bump(page)

    def release(self, page: int):
        """Drop an owner; the page frees at refcount 0. Releasing an
        un-owned page is a double-free — loud, because the silent
        version hands one page to two slots later."""
        was = self.ref.get(page, 0)
        if was <= 0:
            raise ValueError(f"release() of un-owned page {page}")
        if was == 2:
            self.shared_pages -= 1
        if was == 1:
            self.ref.pop(page, None)
            self._free.append(page)
        else:
            self.ref[page] = was - 1

    def cow(self, slot: int, block_idx: int) -> Optional[int]:
        """Copy-on-write bookkeeping: swap the (shared) page at
        ``block_idx`` of this slot for a fresh private one. Returns the
        new page id (the CALLER must device-copy old → new before any
        write), or None when the free list is empty."""
        if not self._free:
            return None
        old = self.pages_of[slot][block_idx]
        new = self._free.pop()
        self.pages_of[slot][block_idx] = new
        self.block_tables[slot, block_idx] = new
        self.ref[new] = 1
        self.release(old)
        return new

    def free(self, slot: int):
        for p in reversed(self.pages_of[slot]):
            self.release(p)
        self.pages_of[slot] = []
        self.block_tables[slot] = 0

    def device_state(self, seq_lens: np.ndarray) -> PagedState:
        return PagedState(
            block_tables=jnp.asarray(self.block_tables),
            seq_lens=jnp.asarray(seq_lens, jnp.int32),
        )
