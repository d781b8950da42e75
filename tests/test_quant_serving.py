"""Quantized serving: int8/int4 weight streaming + int8 KV-cache pools
with in-kernel dequant.

The contract under test:
  - int4 pack/unpack edges raise ACTIONABLE errors, nibble order is
    pinned (low nibble = even row);
  - ``_resolve_cache_dtype`` / ``EngineConfig`` reject combinations
    with no kernel path AT INIT (bad dtypes, int8 KV on the legacy
    bucketed prefill, any quantized mode under a mesh);
  - the engine quantizes a DEEP COPY by default — the caller's model
    stays full-precision and servable;
  - greedy spec-off vs spec-on parity holds under int8 weights and
    int8 KV pools in BOTH cache modes (quantization changes WHICH
    tokens greedy decode emits vs bf16 — measured by the bench quant
    scenario, never asserted here — but within a quant config the
    engine must stay bit-stable across schedulers and spec modes);
  - fused Pallas kernels (interpret mode on CPU) match the lax
    references bit-for-bit on int8 pools at GQA kvh 1/4/8 with ragged
    lengths incl. len-0 and page-boundary slots, and fused-vs-unfused
    engines emit identical tokens;
  - shared-prefix pages CARRY THEIR SCALE ROWS through adopt/COW/
    evict; spec-decode rollback under int8 KV is a pure length
    non-advance; crash-recovery replay under int8 weights+KV is
    deterministic and compiles ZERO new programs;
  - int8-weight serving exercises all compiled serving programs with
    no per-dtype program growth (trace-count guard);
  - the kernelbench quant models report >=1.8x bytes/token for int8-W
    alone and ~4.6x for int8-W x int8-KV x acceptance 0.6 vs bf16
    plain decode, as JSON-serializable rows on any backend.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.resilience import FaultInjector
from paddle_tpu.inference.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    _resolve_cache_dtype,
)
from paddle_tpu.inference.spec_decode import Drafter
from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.kernels import quant_matmul as qmm
from paddle_tpu.kernels.paged_attention import fused_paged_decode_attention
from paddle_tpu.kernels.rope import rope_frequencies
from paddle_tpu.quantization import WeightOnlyLinear

from serving_utils import (
    assert_spec_parity,
    drain,
    mixed_prompts,
    spec_parity_outputs,
    tiny_ecfg,
    tiny_model,
)

pytestmark = pytest.mark.fast


# ---------------- int4 edge hardening ----------------

def test_int4_odd_k_actionable_error():
    w = jnp.zeros((129, 8))
    with pytest.raises(ValueError, match="even.*k=129|k=129.*even"):
        qmm.quantize_weight_int4_grouped(w, group_size=129)
    # the message tells the caller what to DO about it
    with pytest.raises(ValueError, match="[Pp]ad"):
        qmm.quantize_weight_int4_grouped(w, group_size=129)


def test_int4_group_mismatch_actionable_error():
    w = jnp.zeros((128, 8))
    with pytest.raises(ValueError, match="group_size=96"):
        qmm.quantize_weight_int4_grouped(w, group_size=96)
    # suggests a group size that actually divides k
    with pytest.raises(ValueError, match="group_size=64"):
        qmm.quantize_weight_int4_grouped(w, group_size=96)
    # int8 grouped path rejects too (its own message)
    with pytest.raises(ValueError, match="group_size"):
        qmm.quantize_weight_int8_grouped(w, group_size=96)


def test_int4_pack_unpack_roundtrip_nibble_order_pinned():
    """Property: pack→unpack is the identity on int4 values, and the
    nibble order is PINNED — row 2i in the LOW nibble of packed row i,
    row 2i+1 in the HIGH nibble (a silent order flip would still
    round-trip, so the order is checked against hand-packed bytes)."""
    rng = np.random.default_rng(3)
    vals = rng.integers(-7, 8, (64, 16)).astype(np.int8)
    lo = vals[0::2].astype(np.int32) & 0xF
    hi = (vals[1::2].astype(np.int32) & 0xF) << 4
    packed = jnp.asarray((lo | hi).astype(np.int8))
    unpacked = np.asarray(qmm._unpack_int4(packed))
    np.testing.assert_array_equal(unpacked, vals)
    # and the quantizer produces exactly that packing for its own q
    w = rng.standard_normal((64, 16)).astype(np.float32)
    pk, s = qmm.quantize_weight_int4_grouped(jnp.asarray(w), 32)
    q = np.asarray(qmm._unpack_int4(pk))
    repack_lo = q[0::2].astype(np.int32) & 0xF
    repack_hi = (q[1::2].astype(np.int32) & 0xF) << 4
    np.testing.assert_array_equal(
        np.asarray(pk), (repack_lo | repack_hi).astype(np.int8))


# ---------------- config validation ----------------

def test_resolve_cache_dtype_error_lists_full_allowed_set():
    with pytest.raises(ValueError) as ei:
        _resolve_cache_dtype("int3")
    msg = str(ei.value)
    for name in ("int8", "bf16", "bfloat16", "float16", "float32"):
        assert name in msg
    # and the new member actually resolves
    assert _resolve_cache_dtype("int8") == jnp.int8


def test_engine_rejects_no_kernel_path_combos_at_init(serving_flags):
    model, cfg = tiny_model()
    with pytest.raises(ValueError, match="weight_dtype"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, weight_dtype="fp8"))
    with pytest.raises(ValueError, match="cache_dtype"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, cache_dtype="int4"))
    with pytest.raises(ValueError, match="weight_group_size"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, weight_dtype="int8",
                             weight_group_size=0))
    # int8 KV has no quantize-on-append path through the legacy
    # bucketed prefill: rejected at init, not at first dispatch
    serving_flags({"prefill_chunk": 0})
    with pytest.raises(ValueError, match="chunked prefill"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, cache_dtype="int8"))
    serving_flags({"prefill_chunk": 256})
    # quantized serving is single-chip: any mesh is rejected before
    # params are sharded
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="mesh|tensor-parallel"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, weight_dtype="int8"), mesh=mesh)
    with pytest.raises(ValueError, match="mesh|tensor-parallel"):
        ContinuousBatchingEngine(
            model, tiny_ecfg(True, cache_dtype="int8"), mesh=mesh)


def test_weight_dtype_flag_resolution(serving_flags):
    """EngineConfig.weight_dtype='auto' defers to
    PT_FLAGS_serve_weight_dtype; explicit config wins."""
    model, cfg = tiny_model()
    serving_flags({"serve_weight_dtype": "int8"})
    eng = ContinuousBatchingEngine(model, tiny_ecfg(False))
    assert eng.weight_dtype == "int8"
    assert any("qweight" in n for n in eng.buffers)
    serving_flags({"serve_weight_dtype": "bf16"})
    eng2 = ContinuousBatchingEngine(
        model, tiny_ecfg(False, weight_dtype="int4"))
    assert eng2.weight_dtype == "int4"


def test_engine_quantizes_a_copy_by_default():
    model, cfg = tiny_model(1)
    p = np.arange(1, 9)
    ref = ContinuousBatchingEngine(model, tiny_ecfg(False)).run(
        [p], max_new_tokens=6)[0].output
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(False, weight_dtype="int8"))
    eng.run([p], max_new_tokens=6)
    # the caller's tree still has zero WeightOnlyLinear layers and
    # serves the exact pre-quantization stream
    assert not any(isinstance(m, WeightOnlyLinear)
                   for m in model.sublayers(include_self=True))
    again = ContinuousBatchingEngine(model, tiny_ecfg(False)).run(
        [p], max_new_tokens=6)[0].output
    assert again == ref
    # inplace opt-in mutates (the 7B memory trade)
    model2, _ = tiny_model(1)
    ContinuousBatchingEngine(
        model2, tiny_ecfg(False, weight_dtype="int8",
                          quantize_inplace=True))
    assert any(isinstance(m, WeightOnlyLinear)
               for m in model2.sublayers(include_self=True))


# ---------------- greedy parity under quantization ----------------

@pytest.mark.parametrize("paged", [False, True])
def test_int8_weight_spec_parity(paged, serving_flags):
    """Spec-off vs spec-ngram stays bit-identical when the engine
    serves int8 weights (the shared parity comparison from
    serving_utils, same as the fp suite runs)."""
    model, cfg = tiny_model(3)
    rng = np.random.default_rng(5)
    prompts = mixed_prompts(cfg, rng)
    outs, snaps = spec_parity_outputs(
        model,
        lambda: tiny_ecfg(paged, weight_dtype="int8"),
        prompts, serving_flags, flags_extra={"prefix_cache": True},
        replay=True)
    assert_spec_parity(outs, snaps)


@pytest.mark.parametrize("paged", [False, True])
def test_int8_kv_spec_parity(paged, serving_flags):
    """Spec-off vs spec-ngram parity on int8 KV pools x int8 weights
    (the FULL quantized stack — int8 weights over a float cache are
    covered by test_int8_weight_spec_parity) in both cache modes."""
    model, cfg = tiny_model(3)
    rng = np.random.default_rng(5)
    prompts = mixed_prompts(cfg, rng)
    outs, snaps = spec_parity_outputs(
        model,
        lambda: tiny_ecfg(paged, cache_dtype="int8",
                          weight_dtype="int8"),
        prompts, serving_flags, flags_extra={"prefix_cache": True},
        replay=True)
    assert_spec_parity(outs, snaps)


@pytest.mark.parametrize("paged", [False, True])
def test_fused_engine_int8_kv_token_parity(paged, serving_flags):
    """PT_FLAGS_fused_decode on (Pallas interpret) vs off (lax
    reference) emits identical tokens on int8 pools — in-kernel
    quantize-on-append and dequant match the XLA paths bit-for-bit."""
    model, cfg = tiny_model(7)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, 9),
               rng.integers(1, cfg.vocab_size, 5)]
    outs = {}
    for fd in ("off", "on"):
        serving_flags({"fused_decode": fd})
        eng = ContinuousBatchingEngine(
            model, tiny_ecfg(paged, cache_dtype="int8"))
        rids = [eng.add_request(p, 8) for p in prompts]
        drain(eng)
        outs[fd] = [eng._finished[r].output for r in rids]
    assert outs["on"] == outs["off"]


# ---------------- kernel-level parity ----------------

@pytest.mark.parametrize("kvh", [1, 4, 8])
def test_fused_int8_kernels_match_references(kvh):
    """Fused Pallas (interpret) vs lax reference on int8 pools at GQA
    kvh 1/4/8 with ragged lengths incl. a len-0 slot and a
    page/chunk-boundary slot: outputs allclose, written pools AND
    scale rows bit-equal."""
    rng = np.random.default_rng(kvh)
    heads = 4 * kvh
    d = 128
    group = heads // kvh
    slots, page_size, max_len = 4, 16, 128
    n_pages = slots * (max_len // page_size) + 1
    lens = np.array([0, 17, 63, 111], np.int32)
    cos, sin = rope_frequencies(d, max_len + 1)
    q = jnp.asarray(rng.standard_normal((slots, kvh, group, d)),
                    jnp.float32)
    kn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.float32)
    lens_j = jnp.asarray(lens)

    # paged
    kp = jnp.asarray(rng.integers(-127, 128,
                                  (kvh, n_pages, page_size, d)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128,
                                  (kvh, n_pages, page_size, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                 (kvh, n_pages, page_size, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                 (kvh, n_pages, page_size, 1)), jnp.float32)
    bt = jnp.asarray(1 + np.arange(slots * (max_len // page_size))
                     .reshape(slots, -1), jnp.int32)
    of, kpf, vpf, ksf, vsf = fused_paged_decode_attention(
        q, kn, vn, kp, vp, bt, lens_j, lens_j, cos, sin,
        k_scale=ks, v_scale=vs)
    orf, kpr, vpr, ksr, vsr = da.fused_paged_decode_reference(
        q, kn, vn, kp, vp, bt, lens_j, lens_j, cos, sin,
        k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(of), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kpf), np.asarray(kpr))
    np.testing.assert_array_equal(np.asarray(vpf), np.asarray(vpr))
    # scale rows: last-ulp f32 drift between in-kernel rope and the
    # reference's apply_rope can move an absmax by ~1e-9 — the int8
    # payloads above are bit-equal, which is the bit that matters
    np.testing.assert_allclose(np.asarray(ksf), np.asarray(ksr),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(vsf), np.asarray(vsr),
                               rtol=1e-5, atol=1e-8)

    # contiguous
    ck = jnp.asarray(rng.integers(-127, 128,
                                  (slots, max_len, kvh, d)), jnp.int8)
    cv = jnp.asarray(rng.integers(-127, 128,
                                  (slots, max_len, kvh, d)), jnp.int8)
    cks = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                  (slots, max_len, kvh)), jnp.float32)
    cvs = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                  (slots, max_len, kvh)), jnp.float32)
    of, ckf, cvf, ksf, vsf = da.fused_contiguous_decode_attention(
        q, kn, vn, ck, cv, lens_j, lens_j, cos, sin,
        k_scale=cks, v_scale=cvs)
    orf, ckr, cvr, ksr, vsr = da.fused_contiguous_decode_reference(
        q, kn, vn, ck, cv, lens_j, lens_j, cos, sin,
        k_scale=cks, v_scale=cvs)
    np.testing.assert_allclose(np.asarray(of), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ckf), np.asarray(ckr))
    np.testing.assert_array_equal(np.asarray(cvf), np.asarray(cvr))
    # scale rows: last-ulp f32 drift between in-kernel rope and the
    # reference's apply_rope can move an absmax by ~1e-9 — the int8
    # payloads above are bit-equal, which is the bit that matters
    np.testing.assert_allclose(np.asarray(ksf), np.asarray(ksr),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(vsf), np.asarray(vsr),
                               rtol=1e-5, atol=1e-8)


# ---------------- quant x prefix cache ----------------

def test_prefix_pages_carry_scale_rows_through_adopt_cow(serving_flags):
    """Shared-prefix pages on int8 pools: the second (full-cover)
    request adopts the cached pages, COW fires for the recompute row,
    and the store's pages — int8 payload AND f32 scale rows — stay
    bit-identical; outputs match the first request."""
    model, cfg = tiny_model(2)
    rng = np.random.default_rng(9)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompt = np.concatenate([unit] * 4)  # 16 tokens = 2 pages of 8
    serving_flags({"spec_decode": "ngram", "prefix_cache": True})
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8"))
    r1 = eng.add_request(prompt, max_new_tokens=24)
    drain(eng)
    ref = eng._finished[r1].output
    assert eng.spec_stats["accepted"] > 0  # verify wrote K+1 windows
    pages = [p for p, _ns in eng._prefix._blocks.values()]
    assert len(pages) == 2
    before = [(np.asarray(c.k_pages[:, p]).copy(),
               np.asarray(c.k_scale[:, p]).copy(),
               np.asarray(c.v_scale[:, p]).copy())
              for c in eng.layer_caches for p in pages]

    r2 = eng.add_request(prompt, max_new_tokens=24)
    drain(eng)
    assert eng._finished[r2].output == ref
    assert eng.prefix_stats["cow_copies"] >= 1
    after = [(np.asarray(c.k_pages[:, p]),
              np.asarray(c.k_scale[:, p]),
              np.asarray(c.v_scale[:, p]))
             for c in eng.layer_caches for p in pages]
    for b, a in zip(before, after):
        for bb, aa in zip(b, a):
            np.testing.assert_array_equal(bb, aa)
    # evict returns the pages (and implicitly their scale rows) to the
    # pool cleanly — the refcount audit stays exact
    eng._evict_pages(10 ** 9)
    assert eng._prefix.cached_pages == 0


def test_contig_prefix_store_blocks_carry_scales(serving_flags):
    """Contiguous mode: stored prefix blocks are QuantizedKV pairs —
    a second identical prompt hits the store and reproduces the first
    stream exactly (scale rows inserted with the payload)."""
    model, cfg = tiny_model(2)
    rng = np.random.default_rng(4)
    unit = rng.integers(1, cfg.vocab_size, 8)
    prompt = np.concatenate([unit, unit])
    serving_flags({"prefix_cache": True})
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(False, cache_dtype="int8"))
    r1 = eng.add_request(prompt, max_new_tokens=8)
    drain(eng)
    base_hits = eng.prefix_stats["hits"]
    r2 = eng.add_request(prompt, max_new_tokens=8)
    drain(eng)
    assert eng.prefix_stats["hits"] > base_hits
    assert eng._finished[r2].output == eng._finished[r1].output


# ---------------- quant x spec rollback ----------------

def test_spec_rollback_int8_pure_length_non_advance(serving_flags):
    """All-rejected verify under int8 KV: the engine advances by
    exactly one token (rollback = length non-advance — scale rows are
    append-only like the pools) and the remaining stream matches the
    spec-off int8 oracle bit-for-bit."""
    model, cfg = tiny_model(6)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 9)
    serving_flags({"spec_decode": "off"})
    ref = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8")).run(
        [prompt], max_new_tokens=12)[0].output

    class WrongDrafter(Drafter):
        def __init__(self, oracle):
            self.oracle = oracle
            self.fired = False

        def propose(self, history, k):
            if self.fired:
                return np.zeros((0,), np.int64)
            self.fired = True
            nxt = len(history) - 9
            wrong = [(self.oracle[nxt + j] + 1) % 256 for j in range(k)]
            return np.asarray(wrong, np.int64)

    serving_flags({"spec_decode": "ngram"})
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8"),
        drafter=WrongDrafter(ref))
    rid = eng.add_request(prompt, max_new_tokens=12)
    eng._admit()
    len0 = int(eng.seq_lens[0])
    assert eng.step()
    assert eng.spec_stats["verify_calls"] == 1
    assert eng.spec_stats["accepted"] == 0
    assert int(eng.seq_lens[0]) == len0 + 1  # bonus token only
    drain(eng)
    assert eng._finished[rid].output == ref


# ---------------- quant x crash recovery ----------------

def test_recovery_replay_int8_deterministic_zero_new_programs(
        compile_counter, serving_flags):
    """A seeded step-fault storm on the fully-quantized engine (int8
    weights + int8 KV): outputs stay bit-identical to a clean run
    (deterministic replay re-prefills prompt+history, _rebuild is not
    needed for injected faults) and the whole chaos run compiles ZERO
    programs beyond the clean engine's set."""
    model, cfg = tiny_model(6)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab_size,
                            (int(rng.integers(5, 14)),))
               for _ in range(4)]

    def ecfg():
        return tiny_ecfg(True, cache_dtype="int8", weight_dtype="int8",
                         max_slots=2)

    clean = ContinuousBatchingEngine(model, ecfg())
    ref = [r.output for r in clean.run(prompts, max_new_tokens=10)]
    base = compile_counter()

    chaos = ContinuousBatchingEngine(
        model, ecfg(),
        fault_injector=FaultInjector("step:0.25,seed:3"))
    got = [r.output for r in chaos.run(prompts, max_new_tokens=10)]
    assert got == ref
    assert chaos.resilience_stats["recoveries"] > 0
    # the replayed engine compiled exactly the same program set the
    # clean engine did (each engine compiles its own closures), and
    # recovery added NOTHING on top
    after = compile_counter()
    delta = {k: after[k] - base.get(k, 0) for k in after
             if after[k] - base.get(k, 0)}
    assert delta == base, (
        f"chaos engine's program set {delta} != clean set {base}")
    compile_counter.assert_programs(set(base))


def test_hard_recovery_rebuilds_int8_scale_pools(serving_flags):
    """serve_recovery=all + a real (non-injected) failure: the cache
    REBUILD path reconstructs the int8 pools including their scale
    arrays with identical shapes, and the replayed outputs stay on the
    clean stream."""
    model, cfg = tiny_model(5)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, 7)
    serving_flags({"serve_recovery": "all"})  # fixture restores
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8"))
    ref = eng.run([prompt], max_new_tokens=8)[0].output
    shapes = [(c.k_scale.shape, c.v_scale.shape)
              for c in eng.layer_caches]

    eng2 = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8"))
    rid = eng2.add_request(prompt, max_new_tokens=8)
    eng2._admit()
    # a host logic error mid-step, recovered under "all": hard path →
    # _rebuild_caches
    boom = {"armed": True}
    orig = eng2._cow_for_decode

    def exploding(k):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("synthetic device loss")
        return orig(k)

    eng2._cow_for_decode = exploding
    drain(eng2)
    assert eng2.resilience_stats["rebuilds"] == 1
    assert [(c.k_scale.shape, c.v_scale.shape)
            for c in eng2.layer_caches] == shapes
    assert eng2._finished[rid].output == ref


# ---------------- trace-count guard ----------------

def test_int8_weight_serving_program_set_pinned(compile_counter,
                                                serving_flags):
    """int8-weight + int8-KV serving runs through ALL the compiled
    serving programs — prefill_chunk, decode_chunk, spec verify and
    the COW page copy — with no per-dtype program growth: exactly one
    specialization each (the single dtype-variant set)."""
    model, cfg = tiny_model(3)
    rng = np.random.default_rng(5)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompts = [np.concatenate([unit] * 4),
               rng.integers(1, cfg.vocab_size, 11)]
    class RepeatDrafter(Drafter):
        """Always proposes, so a verify pass is dispatched whatever
        this seed's model emits (the n-gram drafter found nothing)."""

        def propose(self, history, k):
            return np.full((k,), int(history[-1]), np.int64)

    serving_flags({"spec_decode": "ngram", "prefix_cache": True})
    eng = ContinuousBatchingEngine(
        model, tiny_ecfg(True, cache_dtype="int8",
                         weight_dtype="int8"),
        drafter=RepeatDrafter())
    eng.run(prompts, max_new_tokens=20)
    # full-cover readmission: prefix adopt + COW page copy
    eng.run([prompts[0]], max_new_tokens=20)
    # per-token scheduler: the plain decode program too
    rid = eng.add_request(prompts[1], max_new_tokens=4)
    drain(eng)
    assert eng.spec_stats["verify_calls"] > 0
    assert eng.prefix_stats["cow_copies"] >= 1
    got = compile_counter()
    assert got == {"prefill_chunk": 1, "decode_chunk": 1,
                   "spec_verify": 1, "page_copy": 1, "decode_step": 1}, got


# ---------------- kernelbench models ----------------

def test_quant_models_report_expected_speedups():
    from benchmarks.kernelbench import (
        llama7b_weight_stream_bytes,
        quant_decode_model,
    )

    int8_alone = quant_decode_model("int8", "bf16", accept_rate=0.0)
    assert int8_alone["modeled_speedup"] >= 1.8
    compound = quant_decode_model("int8", "int8", accept_rate=0.6)
    assert 4.0 <= compound["modeled_speedup"] <= 5.2  # "~4.6x"
    # compounding is real: each factor multiplies
    int8_kv = quant_decode_model("int8", "int8", accept_rate=0.0)
    assert compound["modeled_speedup"] > int8_kv["modeled_speedup"] \
        > int8_alone["modeled_speedup"]
    # int4 halves the stream again
    int4 = quant_decode_model("int4", "bf16", accept_rate=0.0)
    assert int4["modeled_speedup"] > int8_alone["modeled_speedup"]
    # weight stream rows: scale overhead shrinks with group size
    w64 = llama7b_weight_stream_bytes("int8", group_size=64)
    w128 = llama7b_weight_stream_bytes("int8", group_size=128)
    assert w64["stream_bytes"] > w128["stream_bytes"]
    bf16 = llama7b_weight_stream_bytes("bf16")
    assert 1.9 < bf16["stream_bytes"] / w128["stream_bytes"] < 2.0
    # every row is a JSON line on any backend
    for row in (int8_alone, compound, int4, w64, bf16):
        json.dumps(row)


def test_spec_decode_model_weight_byte_width():
    from benchmarks.kernelbench import spec_decode_model

    bf16 = spec_decode_model(0.6, k=4, kvh=8, weight_byte_width=2)
    int8 = spec_decode_model(0.6, k=4, kvh=8, weight_byte_width=1)
    assert bf16["weight_bytes"] == 2 * int8["weight_bytes"]
    int8kv = spec_decode_model(0.6, k=4, kvh=8, weight_byte_width=1,
                               cache_bytes=1, cache_scale_bytes=4)
    assert int8kv["attn_bytes_verify"] < int8["attn_bytes_verify"]
