"""Fused single-pass decode attention (in-kernel RoPE + KV-append +
length-pruned attention) vs the lax reference path, both cache modes
(interpret mode on CPU; compiles via Mosaic on TPU), plus the
fused-vs-unfused engine token-parity run and the modeled-HBM A/B."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.flags as flags
from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.kernels.paged_attention import fused_paged_decode_attention
from paddle_tpu.kernels.rope import rope_frequencies

pytestmark = pytest.mark.fast

# GQA ratios: kvh 1/4/8 at 8 query heads
GQA = [(1, 8), (4, 2), (8, 1)]


@pytest.fixture
def fused_on():
    flags.set_flags({"fused_decode": "on"})
    yield
    flags.set_flags({"fused_decode": "auto"})


def _paged_setup(kvh, group, pool_dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    slots, d, ps, n_pages, max_pages = 3, 32, 16, 32, 4
    cos, sin = rope_frequencies(d, 128)
    kp = jnp.asarray(rng.standard_normal((kvh, n_pages, ps, d)), pool_dtype)
    vp = jnp.asarray(rng.standard_normal((kvh, n_pages, ps, d)), pool_dtype)
    # distinct page ids per slot (vLLM-style arbitrary mapping)
    bt = jnp.asarray(
        rng.permutation(n_pages)[: slots * max_pages].reshape(
            slots, max_pages), jnp.int32)
    # ragged: mid-page, exact page boundary (new token starts page 2),
    # and an empty slot
    lens = jnp.asarray([37, 16, 0], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, kvh, group, 32)),
                    jnp.float32)
    kn = jnp.asarray(rng.standard_normal((slots, kvh, 32)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((slots, kvh, 32)), jnp.float32)
    return q, kn, vn, kp, vp, bt, lens, cos, sin


@pytest.mark.parametrize("kvh,group", GQA)
def test_fused_paged_matches_reference(kvh, group):
    q, kn, vn, kp, vp, bt, lens, cos, sin = _paged_setup(kvh, group)
    out, kp2, vp2 = fused_paged_decode_attention(
        q, kn, vn, kp, vp, bt, lens, lens, cos, sin)
    ref, kpr, vpr = da.fused_paged_decode_reference(
        q, kn, vn, kp, vp, bt, lens, lens, cos, sin)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # the append wrote EXACTLY the rotated rows the scatter writes
    np.testing.assert_allclose(np.asarray(kp2), np.asarray(kpr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vp2), np.asarray(vpr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kvh,group", GQA)
def test_fused_contiguous_matches_reference(kvh, group):
    rng = np.random.default_rng(1)
    slots, d, max_len = 3, 32, 48
    cos, sin = rope_frequencies(d, 128)
    q = jnp.asarray(rng.standard_normal((slots, kvh, group, d)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((slots, max_len, kvh, d)),
                     jnp.float32)
    cv = jnp.asarray(rng.standard_normal((slots, max_len, kvh, d)),
                     jnp.float32)
    # ragged incl. a chunk-boundary crossing (chunk = gcd(48, 128) = 16)
    lens = jnp.asarray([37, 16, 0], jnp.int32)
    out, ck2, cv2 = da.fused_contiguous_decode_attention(
        q, kn, vn, ck, cv, lens, lens, cos, sin)
    ref, ckr, cvr = da.fused_contiguous_decode_reference(
        q, kn, vn, ck, cv, lens, lens, cos, sin)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(ck2), np.asarray(ckr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cv2), np.asarray(cvr),
                               rtol=1e-6, atol=1e-6)


def test_fused_append_touches_only_new_rows():
    """Everything in the pool except each slot's append row survives
    bit-identically — the in-kernel write is row-granular."""
    q, kn, vn, kp, vp, bt, lens, cos, sin = _paged_setup(4, 2)
    ps = kp.shape[2]
    _, kp2, _ = fused_paged_decode_attention(
        q, kn, vn, kp, vp, bt, lens, lens, cos, sin)
    before, after = np.asarray(kp), np.asarray(kp2)
    mask = np.zeros(before.shape, bool)
    for s in range(3):
        L = int(lens[s])
        mask[:, int(bt[s, L // ps]), L % ps, :] = True
    assert (before[~mask] == after[~mask]).all()
    assert (before[mask] != after[mask]).any()


def test_fused_kernels_accept_bf16_pools():
    """PT_FLAGS_kv_cache_dtype=auto serves bf16 pools on TPU — the
    fused kernels must take bf16 caches with f32 activations."""
    q, kn, vn, kp, vp, bt, lens, cos, sin = _paged_setup(
        2, 2, pool_dtype=jnp.bfloat16)
    out, kp2, vp2 = fused_paged_decode_attention(
        q, kn, vn, kp, vp, bt, lens, lens, cos, sin)
    ref, kpr, _ = da.fused_paged_decode_reference(
        q, kn, vn, kp, vp, bt, lens, lens, cos, sin)
    assert kp2.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(  # same bf16 rounding as the scatter
        np.asarray(kp2, np.float32), np.asarray(kpr, np.float32))

    rng = np.random.default_rng(3)
    ck = jnp.asarray(rng.standard_normal((3, 32, 2, 32)), jnp.bfloat16)
    cv = jnp.asarray(rng.standard_normal((3, 32, 2, 32)), jnp.bfloat16)
    clens = jnp.asarray([20, 16, 0], jnp.int32)  # within max_len=32
    out, ck2, cv2 = da.fused_contiguous_decode_attention(
        q, kn, vn, ck, cv, clens, clens, cos, sin)
    ref, ckr, _ = da.fused_contiguous_decode_reference(
        q, kn, vn, ck, cv, clens, clens, cos, sin)
    assert ck2.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(
        np.asarray(ck2, np.float32), np.asarray(ckr, np.float32))


def test_fused_decode_flag_gating():
    assert not da.fused_decode_active(16, 8)  # auto, off-TPU → lax path
    flags.set_flags({"fused_decode": "on"})
    try:
        assert da.fused_decode_active(16, 8)  # forced → interpret mode
    finally:
        flags.set_flags({"fused_decode": "off"})
    try:
        assert not da.fused_decode_active(128, 64)
    finally:
        flags.set_flags({"fused_decode": "auto"})


@pytest.mark.parametrize("paged", [False, True])
def test_engine_fused_decode_token_parity(fused_on, paged):
    """End-to-end step_chunk run with PT_FLAGS_fused_decode=on (Pallas
    interpret mode on CPU) must emit exactly the tokens of the unfused
    engine — the fused kernel replaces append_kv + rope + attention
    without changing a single greedy token."""
    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine, EngineConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(11)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    prompts = [np.arange(1, 6), np.arange(3, 10), np.arange(2, 4)]
    ecfg = dict(max_slots=2, max_len=32, seq_buckets=(16,), paged=paged,
                page_size=8)

    flags.set_flags({"fused_decode": "off"})
    eng = ContinuousBatchingEngine(model, EngineConfig(**ecfg))
    ref = [r.output for r in eng.run(prompts, max_new_tokens=6,
                                     max_chunk=4)]

    flags.set_flags({"fused_decode": "on"})
    eng = ContinuousBatchingEngine(model, EngineConfig(**ecfg))
    got = [r.output for r in eng.run(prompts, max_new_tokens=6,
                                     max_chunk=4)]
    assert got == ref


def test_fused_decode_trace_has_no_append_scatter(fused_on):
    """Acceptance: the fused path removes the separate append_kv
    program — the decode trace carries no scatter op (the unfused trace
    does: append_kv's ``.at[...].set``)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.inference.paged import (
        PagedLayerCache,
        PagedState,
        init_paged_pool,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    cache = init_paged_pool(1, 8, 8, 2, 16, dtype=jnp.float32)[0]
    state = PagedState(jnp.zeros((2, 4), jnp.int32),
                       jnp.asarray([3, 1], jnp.int32))
    layer = model.model.layers[0].self_attn
    cos = model.model._buffers["rope_cos"]
    sin = model.model._buffers["rope_sin"]

    x = jnp.zeros((2, 1, 64), jnp.float32)

    def trace(flag):
        # fresh closure per trace: jax caches jaxprs on fn identity, so
        # reusing one fn would return the first flag's trace for both
        flags.set_flags({"fused_decode": flag})

        def step(x, cache, state):
            out, (cache, state) = layer(
                x, cos, sin, position_ids=state.seq_lens[:, None],
                kv_cache=(cache, state), cache_index=state.seq_lens)
            return out, cache

        return str(jax.make_jaxpr(step)(x, cache, state))

    assert "scatter" not in trace("on")
    assert "scatter" in trace("off")


@pytest.mark.parametrize("mode", ["paged", "contiguous"])
@pytest.mark.parametrize("kvh,group", GQA)
def test_fused_modeled_hbm_bytes_lower(mode, kvh, group):
    """The kernelbench A/B model at every tested GQA config.
    Contiguous: length pruning prices the fused path at under half the
    dense unfused view. Paged: both paths prune, and the fused append
    writes back a sublane tile where the scatter writes one row (the
    chip's compiler refuses a one-row block), so fused bytes sit a few
    percent ABOVE unfused — the model must say so, not flatter it."""
    from benchmarks.kernelbench import decode_hbm_bytes

    lens = [937, 512, 120, 64, 0, 1000, 333, 240]
    kw = dict(page_size=64) if mode == "paged" else dict(max_len=1024)
    fused = decode_hbm_bytes(mode, True, lens, kvh, group, 128, **kw)
    unfused = decode_hbm_bytes(mode, False, lens, kvh, group, 128, **kw)
    if mode == "contiguous":
        assert fused < 0.5 * unfused
    else:
        assert unfused < fused < 1.05 * unfused


def test_engine_free_slot_heap_and_bucket_lookup():
    """Admission bookkeeping after the O(slots²)→O(log slots) cleanup:
    the free-slot heap tracks the active mask through admit/finish
    cycles (lowest index first, as before) and the bisect bucket lookup
    matches the old linear scan."""
    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine, EngineConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    eng = ContinuousBatchingEngine(model, EngineConfig(
        max_slots=3, max_len=64, seq_buckets=(8, 16, 128)))
    assert eng._free_slots() == [0, 1, 2]
    for n, want in ((1, 8), (8, 8), (9, 16), (17, 64), (200, 64)):
        assert eng._bucket(n) == want, n
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n) for n in (4, 7, 3, 9, 5)]
    reqs = eng.run(prompts, max_new_tokens=4)
    assert all(r.done for r in reqs)
    assert eng._free_slots() == [0, 1, 2]
    assert not eng.active.any()

    # the failure-injection halves below pin the LEGACY bucketed
    # admission path (per-request dispatch; partial-batch integration)
    # — the chunked path's all-or-nothing rollback is pinned in
    # tests/test_prefix_prefill.py
    from paddle_tpu import flags as F

    saved_chunk = F.flag("prefill_chunk")
    F.set_flags({"prefill_chunk": 0})
    try:
        eng = ContinuousBatchingEngine(model, EngineConfig(
            max_slots=3, max_len=64, seq_buckets=(8, 16, 128)))
        # a claimed slot is returned to the heap when admission fails
        # mid-dispatch (the heap no longer self-heals from the active
        # mask)
        eng.add_request(np.arange(1, 5), max_new_tokens=4)
        import pytest as _pytest

        def boom(*a, **k):
            raise RuntimeError("prefill exploded")

        eng._prefill_c = boom
        with _pytest.raises(RuntimeError, match="prefill exploded"):
            eng._admit()
        eng._prefill_c = None
        assert eng._free_slots() == [0, 1, 2]
        assert len(eng._queue) == 1  # request requeued, not dropped
        while eng.step_chunk(4) or eng._queue or eng.active.any():
            pass
        assert all(r.done for r in eng._finished.values())

        # partial-batch failure: first request admits, second prefill
        # blows up — the admitted one must be INTEGRATED (length +
        # first token), the failed one requeued, and both complete
        # after recovery
        real = eng._prefill()
        calls = {"n": 0}

        def flaky(*a, **k):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("second prefill exploded")
            return real(*a, **k)

        eng._prefill_c = flaky
        p1, p2 = np.arange(1, 5), np.arange(2, 8)
        r1 = eng.add_request(p1, max_new_tokens=3)
        r2 = eng.add_request(p2, max_new_tokens=3)
        with _pytest.raises(RuntimeError, match="second prefill"):
            eng._admit()
        slot1 = next(s for s, r in eng._slot_req.items() if r.rid == r1)
        assert eng.seq_lens[slot1] == p1.size  # integrated, not stranded
        assert len(eng._slot_req[slot1].output) == 1
        eng._prefill_c = real
        while eng.step_chunk(4) or eng._queue or eng.active.any():
            pass
        assert eng._finished[r1].done and eng._finished[r2].done
        ref = ContinuousBatchingEngine(model, EngineConfig(
            max_slots=3, max_len=64, seq_buckets=(8, 16, 128))).run(
            [p1, p2], max_new_tokens=3)
        assert eng._finished[r1].output == ref[0].output
        assert eng._finished[r2].output == ref[1].output
    finally:
        F.set_flags({"prefill_chunk": saved_chunk})
