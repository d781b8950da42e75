"""Speculative decoding in the serving engine: n-gram self-drafting +
batched multi-token verify with KV rollback, plus the per-request
sampling params that ride the same PR.

The contract under test:
  - greedy token PARITY: ``PT_FLAGS_spec_decode=ngram`` outputs are
    bit-identical to spec-off in BOTH cache modes (incl. bf16 KV pools
    and prefix-cache on), across ragged lengths and slots that never
    produce a draft — greedy acceptance emits exactly the argmax chain;
  - ROLLBACK: rejected draft rows are logically discarded (seq_lens
    advance only past the accepted prefix; later attention never reads
    the garbage rows);
  - COW-under-verify: the K+1-token write window never mutates a page
    the prefix store still shares;
  - compile count: a mixed spec-on workload adds at most the verify
    program (+ the sampling variant) on top of the spec-off set, and
    spec-off compiles EXACTLY the pre-spec program set;
  - per-request sampling params route through
    ``generation.process_logits_batch`` without perturbing greedy
    neighbors, and sampling slots never draft.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags as F
from paddle_tpu.inference.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
)
from paddle_tpu.inference.spec_decode import Drafter, NgramDrafter

# greedy-parity helpers shared with test_quant_serving (satellite of
# PR 9: the quant parity tests reuse the same comparison instead of
# copy-pasting it); serving_flags comes from conftest now
from serving_utils import (
    ReplayDrafter,
    assert_spec_parity,
    drain as _drain,
    mixed_prompts as _mixed_prompts,
    spec_parity_outputs,
    tiny_ecfg as _ecfg,
    tiny_model as _model,
)

pytestmark = pytest.mark.fast


# ---------------- n-gram drafter ----------------

def test_ngram_drafter_basic_lookup():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # history ends in the bigram (7, 8) seen earlier, followed by 9, 10
    h = np.array([1, 7, 8, 9, 10, 5, 7, 8])
    np.testing.assert_array_equal(d.propose(h, 2), [9, 10])
    # k bounds the proposal
    np.testing.assert_array_equal(d.propose(h, 1), [9])
    # no earlier occurrence of any suffix -> empty
    assert d.propose(np.array([1, 2, 3, 4]), 4).size == 0
    # degenerate histories never crash
    assert d.propose(np.array([1]), 4).size == 0
    assert d.propose(np.array([], np.int64), 4).size == 0
    assert d.propose(h, 0).size == 0


def test_ngram_drafter_longest_suffix_and_recency_win():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # suffix (2, 3) occurs twice; trigram (9, 2, 3) only once — the
    # longer match decides, not the more recent shorter one
    h = np.array([9, 2, 3, 50, 4, 2, 3, 60, 9, 2, 3])
    np.testing.assert_array_equal(d.propose(h, 1), [50])
    # only unigram matches: the MOST RECENT occurrence's continuation
    h2 = np.array([5, 1, 5, 2, 5])
    np.testing.assert_array_equal(d.propose(h2, 1), [2])
    # proposal may run into the suffix itself (periodic history)
    h3 = np.array([1, 2, 3, 1, 2, 3, 1, 2, 3])
    np.testing.assert_array_equal(d.propose(h3, 3), [1, 2, 3])


def test_ngram_drafter_validates():
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=1, min_ngram=2)
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=3, min_ngram=0)


# ---------------- greedy token parity ----------------

@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
def test_spec_token_parity(paged, cache_dtype, serving_flags):
    """THE acceptance criterion: spec-on greedy outputs are identical
    to spec-off in both cache modes incl. bf16 pools, with the prefix
    cache on, across ragged lengths and non-drafting slots — and the
    spec arm must actually have accepted drafts (or the test proves
    nothing). The comparison itself lives in serving_utils, shared
    with the quantized-serving parity suite."""
    model, cfg = _model(3)
    rng = np.random.default_rng(5)
    prompts = _mixed_prompts(cfg, rng)
    outs, snaps = spec_parity_outputs(
        model, lambda: _ecfg(paged, cache_dtype=cache_dtype), prompts,
        serving_flags, flags_extra={"prefix_cache": True})
    assert_spec_parity(outs, snaps)


@pytest.mark.parametrize("paged", [False, True])
def test_spec_parity_per_token_step(paged, serving_flags):
    """step() (per-token scheduler) offers a draft opportunity every
    tick — parity must hold there too, at high draft pressure."""
    model, cfg = _model(7)
    rng = np.random.default_rng(2)
    unit = rng.integers(1, cfg.vocab_size, 3)
    prompts = [np.concatenate([unit] * 6),
               rng.integers(1, cfg.vocab_size, 7)]
    outs = {}
    for mode in ("off", "ngram"):
        serving_flags({"spec_decode": mode})
        eng = ContinuousBatchingEngine(model, _ecfg(paged))
        rids = [eng.add_request(p, max_new_tokens=30) for p in prompts]
        _drain(eng)
        outs[mode] = [eng._finished[r].output for r in rids]
        if mode == "ngram":
            assert eng.spec_stats["accepted"] > 0
    assert outs["ngram"] == outs["off"]


def test_spec_auto_mode_parity_and_throttle(serving_flags):
    """auto = ngram drafting + a per-request throttle for undraftable
    traffic. Parity is unconditional; the throttle must stop proposing
    for a request whose drafts never accept."""
    model, cfg = _model(4)
    rng = np.random.default_rng(8)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompts = [np.concatenate([unit] * 5)]
    serving_flags({"spec_decode": "off"})
    ref = [r.output for r in ContinuousBatchingEngine(
        model, _ecfg(True)).run(prompts, max_new_tokens=24)]

    serving_flags({"spec_decode": "auto"})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    got = [r.output for r in eng.run(prompts, max_new_tokens=24)]
    assert got == ref

    # throttle: a drafter that always proposes garbage stops getting
    # called for the request once its acceptance proves hopeless
    class Garbage(Drafter):
        def __init__(self):
            self.calls = 0

        def propose(self, history, k):
            self.calls += 1
            return np.full((k,), -1, np.int64)  # never a real token

    bad = Garbage()
    eng2 = ContinuousBatchingEngine(model, _ecfg(True), drafter=bad)
    rid = eng2.add_request(prompts[0], max_new_tokens=40)
    _drain(eng2)
    assert eng2._finished[rid].output == ContinuousBatchingEngine(
        model, _ecfg(True)).run(prompts, max_new_tokens=40)[0].output
    assert eng2.spec_stats["accepted"] == 0
    req = eng2._finished[rid]
    # proposals stopped at the throttle threshold, well before the 39
    # decode ticks the request took
    assert 16 <= req._spec_proposed <= 20
    assert eng2.spec_stats["fallback_steps"] > 0


def test_spec_flag_validated():
    model, cfg = _model()
    F.set_flags({"spec_decode": "bogus"})
    try:
        with pytest.raises(ValueError, match="spec_decode"):
            ContinuousBatchingEngine(model, _ecfg(False))
    finally:
        F.set_flags({"spec_decode": "off"})
    with pytest.raises(ValueError, match="spec_k"):
        F.set_flags({"spec_decode": "ngram"})
        try:
            ContinuousBatchingEngine(model, _ecfg(False, spec_k=0))
        finally:
            F.set_flags({"spec_decode": "off"})


# ---------------- rollback ----------------

def test_rollback_rejected_rows_never_read(serving_flags):
    """A verify pass whose drafts are ALL rejected wrote K garbage KV
    rows past the slot's length; the engine advances by exactly one
    token and later attention must never read those rows — pinned by
    bit-parity of the remaining stream against the spec-off oracle."""
    model, cfg = _model(6)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 9)
    serving_flags({"spec_decode": "off"})
    ref = ContinuousBatchingEngine(model, _ecfg(True)).run(
        [prompt], max_new_tokens=12)[0].output

    class WrongDrafter(Drafter):
        """Proposes the WRONG token (off-by-one of the oracle) for the
        first verify, then stops — every draft must be rejected."""

        def __init__(self, oracle):
            self.oracle = oracle
            self.fired = False

        def propose(self, history, k):
            if self.fired:
                return np.zeros((0,), np.int64)
            self.fired = True
            nxt = len(history) - 9  # tokens generated so far
            wrong = [(self.oracle[nxt + j] + 1) % 256 for j in range(k)]
            return np.asarray(wrong, np.int64)

    serving_flags({"spec_decode": "ngram"})
    eng = ContinuousBatchingEngine(model, _ecfg(True),
                                   drafter=WrongDrafter(ref))
    rid = eng.add_request(prompt, max_new_tokens=12)
    eng._admit()
    len0 = int(eng.seq_lens[0])
    assert eng.step()  # the all-rejected verify pass
    assert eng.spec_stats["verify_calls"] == 1
    assert eng.spec_stats["accepted"] == 0
    assert eng.spec_stats["proposed"] == eng.cfg.spec_k
    # rollback: advanced by the bonus token ONLY, not K+1
    assert int(eng.seq_lens[0]) == len0 + 1
    _drain(eng)
    assert eng._finished[rid].output == ref


def test_partial_acceptance_advances_by_accepted_plus_one(serving_flags):
    """Drafts correct for j tokens then wrong: accepted == j exactly
    (greedy acceptance is a prefix rule), seq_lens advances j+1, and
    the stream stays on the oracle."""
    model, cfg = _model(6)
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, cfg.vocab_size, 9)
    serving_flags({"spec_decode": "off"})
    ref = ContinuousBatchingEngine(model, _ecfg(False)).run(
        [prompt], max_new_tokens=12)[0].output

    class HalfRight(Drafter):
        def __init__(self, oracle):
            self.oracle = oracle
            self.fired = False

        def propose(self, history, k):
            if self.fired or k < 3:
                return np.zeros((0,), np.int64)
            self.fired = True
            nxt = len(history) - 9
            d = [self.oracle[nxt], self.oracle[nxt + 1],
                 (self.oracle[nxt + 2] + 1) % 256]
            return np.asarray(d, np.int64)

    serving_flags({"spec_decode": "ngram"})
    eng = ContinuousBatchingEngine(model, _ecfg(False),
                                   drafter=HalfRight(ref))
    rid = eng.add_request(prompt, max_new_tokens=12)
    eng._admit()
    len0 = int(eng.seq_lens[0])
    eng.step()  # verify: 2 accepted, 1 rejected
    assert eng.spec_stats["accepted"] == 2
    assert int(eng.seq_lens[0]) == len0 + 3  # 2 drafts + bonus
    _drain(eng)
    assert eng._finished[rid].output == ref


# ---------------- copy-on-write under verify ----------------

def test_cow_under_verify_never_dirties_shared_page(serving_flags):
    """The verify window (K+1 rows, pad rows included) must trigger the
    decode-time COW guard when it overlaps a shared page — the cached
    prefix entry stays bit-identical through an entire spec-on run."""
    model, cfg = _model(2)
    rng = np.random.default_rng(9)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompt = np.concatenate([unit] * 4)  # 16 tokens = 2 pages of 8
    serving_flags({"spec_decode": "ngram", "prefix_cache": True})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    r1 = eng.add_request(prompt, max_new_tokens=24)
    _drain(eng)  # per-token steps: every tick is a draft opportunity
    ref = eng._finished[r1].output
    assert eng.spec_stats["accepted"] > 0  # verify actually wrote
    store = eng._prefix
    pages = [p for p, _ns in store._blocks.values()]
    assert len(pages) == 2
    before = [[np.asarray(c.k_pages[:, p]).copy() for p in pages]
              for c in eng.layer_caches]

    # full-cover hit: adopts both shared pages; the verify window's
    # writes start INSIDE the last shared page
    r2 = eng.add_request(prompt, max_new_tokens=24)
    _drain(eng)
    out2 = eng._finished[r2].output
    assert out2 == ref
    assert eng.prefix_stats["cow_copies"] >= 1
    after = [[np.asarray(c.k_pages[:, p]) for p in pages]
             for c in eng.layer_caches]
    for lb, la in zip(before, after):
        for b, a in zip(lb, la):
            np.testing.assert_array_equal(b, a)


def test_cow_guard_covers_full_verify_window(serving_flags):
    """Externally pin the page the verify window writes into (the
    guard test pattern from PR 4, widened to the K-token window): the
    engine must copy it before dispatching verify."""
    model, cfg = _model(4)
    rng = np.random.default_rng(1)
    unit = rng.integers(1, cfg.vocab_size, 2)
    prompt = np.concatenate([unit] * 3)  # repetitive → drafts fire
    serving_flags({"spec_decode": "ngram"})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    rid = eng.add_request(prompt, max_new_tokens=10)
    eng._admit()
    slot = eng._slot_req[0].slot
    page = int(eng.pool.block_tables[slot, 0])
    eng.pool.retain(page)
    snap = np.asarray(eng.layer_caches[0].k_pages[:, page]).copy()
    _drain(eng)
    assert eng.spec_stats["verify_calls"] >= 1
    assert eng.prefix_stats["cow_copies"] >= 1
    np.testing.assert_array_equal(
        snap, np.asarray(eng.layer_caches[0].k_pages[:, page]))
    assert eng._finished[rid].done
    eng.pool.release(page)


# ---------------- compile-count guard ----------------

def test_spec_compile_counts(compile_counter, serving_flags):
    """Spec-off compiles exactly the PR-4 program set; a mixed spec-on
    workload (drafting slots + fallback steps + admissions mid-stream)
    adds AT MOST the verify program on top — and re-running at other
    prompt lengths must not re-specialize anything."""
    model, cfg = _model(6)
    rng = np.random.default_rng(3)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompts = [np.concatenate([unit] * 5),
               rng.integers(1, cfg.vocab_size, 7),
               rng.integers(1, cfg.vocab_size, 19)]

    serving_flags({"spec_decode": "off"})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    eng.run(prompts, max_new_tokens=12)
    off_set = compile_counter()
    assert off_set == {"prefill_chunk": 1, "decode_chunk": 1}
    assert compile_counter("spec_verify") == 0

    serving_flags({"spec_decode": "ngram"})
    eng2 = ContinuousBatchingEngine(model, _ecfg(True))
    eng2.run(prompts, max_new_tokens=12)
    assert eng2.spec_stats["verify_calls"] > 0
    assert eng2.spec_stats["fallback_steps"] > 0  # mixed workload
    on_set = compile_counter()
    new = {k: on_set[k] - off_set.get(k, 0) for k in on_set
           if on_set[k] - off_set.get(k, 0)}
    # ≤ 2 new programs: the verify pass + the (already-counted-per-
    # engine) fallback chunk this second engine compiled for itself
    assert new.pop("spec_verify") == 1
    assert new == {"prefill_chunk": 1, "decode_chunk": 1}

    # other prompt lengths / a second wave: nothing re-specializes
    eng2.run([rng.integers(1, cfg.vocab_size, 30),
              np.concatenate([unit] * 3)], max_new_tokens=8)
    assert compile_counter("spec_verify") == 1
    assert compile_counter("prefill_chunk") == on_set["prefill_chunk"]
    assert compile_counter("decode_chunk") == on_set["decode_chunk"]


def test_chunk_preemption_gated_on_drafting_share(serving_flags):
    """A lone drafting slot in a wide batch must NOT preempt the
    K-token chunk (every other slot would drop from max_chunk tokens
    per sync to 1); with a majority drafting, verify preempts. The
    per-token step() scheduler preempts unconditionally either way.
    A marker-keyed drafter makes WHO drafts deterministic (the n-gram
    drafter's firing depends on what the model happens to emit)."""
    model, cfg = _model(6)
    rng = np.random.default_rng(12)
    marker = int(rng.integers(1, cfg.vocab_size))
    drafting = np.concatenate(
        [[marker], rng.integers(1, cfg.vocab_size, 8)])
    others = [np.concatenate(
        [[(marker + 1 + i) % cfg.vocab_size or 1],
         rng.integers(1, cfg.vocab_size, 7 + i)]) for i in range(3)]

    class MarkerDrafter(Drafter):
        """Drafts (garbage — rejection is fine, the gate fires on
        PROPOSALS) only for histories starting with the marker."""

        def propose(self, history, k):
            if history.size and int(history[0]) == marker:
                return np.full((min(k, 2),), int(history[-1]), np.int64)
            return np.zeros((0,), np.int64)

    serving_flags({"spec_decode": "ngram"})

    # 1 drafter of 4 active: the chunk is never preempted
    eng = ContinuousBatchingEngine(
        model, _ecfg(True, max_slots=4), drafter=MarkerDrafter())
    for p in [drafting] + others:
        eng.add_request(p, max_new_tokens=12)
    _drain(eng, lambda: eng.step_chunk(4))
    assert eng.spec_stats["verify_calls"] == 0
    assert eng.spec_stats["fallback_steps"] > 0

    # 2 drafters of 2 active: verify preempts the chunk
    eng2 = ContinuousBatchingEngine(
        model, _ecfg(True), drafter=MarkerDrafter())
    eng2.add_request(drafting, max_new_tokens=12)
    eng2.add_request(np.concatenate([[marker], drafting[1:5]]),
                     max_new_tokens=12)
    _drain(eng2, lambda: eng2.step_chunk(4))
    assert eng2.spec_stats["verify_calls"] > 0

    # step(): even the lone drafter preempts (beats a 1-token pass)
    eng3 = ContinuousBatchingEngine(
        model, _ecfg(True, max_slots=4), drafter=MarkerDrafter())
    for p in [drafting] + others:
        eng3.add_request(p, max_new_tokens=12)
    _drain(eng3)
    assert eng3.spec_stats["verify_calls"] > 0


# ---------------- step_adaptive ----------------

def test_step_adaptive_parity_spec_on_and_off(serving_flags):
    """step_adaptive (previously untested): mixed prefill/decode — more
    requests than slots so admission stays queued across chunks — must
    produce exactly step_chunk's tokens, with spec decoding off AND
    on (and the same stream in all four arms)."""
    model, cfg = _model(11)
    rng = np.random.default_rng(6)
    unit = rng.integers(1, cfg.vocab_size, 3)
    prompts = [np.concatenate([unit] * 5),
               rng.integers(1, cfg.vocab_size, 8),
               np.concatenate([unit] * 4),
               rng.integers(1, cfg.vocab_size, 5)]

    outs = {}
    for mode in ("off", "ngram"):
        serving_flags({"spec_decode": mode})
        for sched in ("chunk", "adaptive"):
            # the spec arms draft the off arm's own continuation: a
            # verify pass is certain, and all four arms must still agree
            drafter = ReplayDrafter(prompts, outs[("off", "chunk")]) \
                if mode == "ngram" else None
            eng = ContinuousBatchingEngine(model, _ecfg(True),
                                           drafter=drafter)
            rids = [eng.add_request(p, max_new_tokens=12)
                    for p in prompts]
            if sched == "chunk":
                while eng.step_chunk(4) or eng._queue or \
                        eng.active.any():
                    pass
            else:
                while eng.step_adaptive(max_chunk=4) or \
                        eng.active.any():
                    pass
            outs[(mode, sched)] = [eng._finished[r].output
                                   for r in rids]
            if mode == "ngram":
                assert eng.spec_stats["verify_calls"] > 0
    assert len({tuple(map(tuple, v)) for v in outs.values()}) == 1


# ---------------- per-request sampling params ----------------

def test_per_request_params_validated():
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    p = np.arange(1, 6)
    with pytest.raises(ValueError, match="temperature"):
        eng.add_request(p, 4, temperature=0.0)
    with pytest.raises(ValueError, match="top_k"):
        eng.add_request(p, 4, top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        eng.add_request(p, 4, top_p=1.5)
    with pytest.raises(ValueError, match="top_p"):
        eng.add_request(p, 4, top_p=0.0)


def test_defaults_equivalent_overrides_keep_plain_arm():
    """Passing overrides that LAND on the engine defaults
    (greedy=True on a greedy engine, top_k=0, top_p=1.0, the engine's
    own temperature) must not flip the compiled programs onto the
    per-slot sampling arm — use_samp stays False and the trace (and
    its per-step vocab sort) is the pre-override one. A real override
    still flips it."""
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    p = np.arange(1, 8)
    eng.add_request(p, 4, greedy=True, top_k=0, top_p=1.0,
                    temperature=eng.cfg.temperature)
    eng._admit()
    use, _ = eng._slot_sampling()
    assert use is False

    eng2 = ContinuousBatchingEngine(model, _ecfg(False))
    eng2.add_request(p, 4, top_k=1)
    eng2._admit()
    use2, _ = eng2._slot_sampling()
    assert use2 is True


def test_per_request_top_k1_matches_greedy():
    """temperature + top_k=1 is sampling with a single survivor — the
    stream must equal the plain greedy reference token for token (the
    in-jit vectorized processor path, deterministically checked)."""
    model, cfg = _model(5)
    prompt = np.arange(1, 8)
    ref = ContinuousBatchingEngine(model, _ecfg(False)).run(
        [prompt], max_new_tokens=8)[0].output

    eng = ContinuousBatchingEngine(model, _ecfg(False))
    rid = eng.add_request(prompt, max_new_tokens=8, temperature=2.0,
                          top_k=1)
    _drain(eng, lambda: eng.step_chunk(4))
    assert eng._finished[rid].output == ref


def test_mixed_greedy_and_sampled_slots_isolated():
    """A sampling neighbor in the same compiled step must not perturb a
    greedy slot's stream (per-slot params are vectors, greedy rows stay
    pure argmax)."""
    model, cfg = _model(9)
    rng = np.random.default_rng(0)
    pa = rng.integers(1, cfg.vocab_size, 6)
    pb = rng.integers(1, cfg.vocab_size, 9)
    ref = ContinuousBatchingEngine(model, _ecfg(True)).run(
        [pa], max_new_tokens=10)[0].output

    eng = ContinuousBatchingEngine(model, _ecfg(True))
    ra = eng.add_request(pa, max_new_tokens=10)  # greedy (engine default)
    rb = eng.add_request(pb, max_new_tokens=10, temperature=5.0,
                         top_p=0.9)
    _drain(eng, lambda: eng.step_chunk(4))
    assert eng._finished[ra].output == ref
    assert len(eng._finished[rb].output) == 10


def test_sampled_request_varies_across_seeds():
    model, cfg = _model(9)
    prompt = np.arange(1, 6)
    firsts = set()
    for seed in range(6):
        eng = ContinuousBatchingEngine(
            model, _ecfg(False, seed=seed))
        rid = eng.add_request(prompt, max_new_tokens=1, temperature=8.0)
        _drain(eng)
        firsts.add(eng._finished[rid].output[0])
    assert len(firsts) > 1


def test_sampling_slots_skip_drafting(serving_flags):
    """Spec decode + sampling compose: the greedy repetitive slot
    drafts, the sampling slot never does (no argmax chain to verify),
    and the greedy slot's stream still matches the oracle."""
    model, cfg = _model(3)
    rng = np.random.default_rng(7)
    unit = rng.integers(1, cfg.vocab_size, 4)
    pa = np.concatenate([unit] * 5)
    pb = rng.integers(1, cfg.vocab_size, 8)
    serving_flags({"spec_decode": "off"})
    refe = ContinuousBatchingEngine(model, _ecfg(True))
    rr = refe.add_request(pa, max_new_tokens=32)
    _drain(refe)
    ref = refe._finished[rr].output

    serving_flags({"spec_decode": "ngram"})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    ra = eng.add_request(pa, max_new_tokens=32)
    rb = eng.add_request(pb, max_new_tokens=32, temperature=3.0)
    _drain(eng)
    assert eng._finished[ra].output == ref
    assert eng.spec_stats["accepted"] > 0
    # every proposal came from the greedy request
    assert eng._finished[rb]._spec_proposed == 0
    assert eng._finished[ra]._spec_proposed == \
        eng.spec_stats["proposed"]


# ---------------- snapshots / telemetry ----------------

def test_spec_snapshot_and_metrics(serving_flags):
    from paddle_tpu import observability
    from paddle_tpu.flags import set_flags as set_pt_flags

    model, cfg = _model(3)
    rng = np.random.default_rng(5)
    unit = rng.integers(1, cfg.vocab_size, 4)
    serving_flags({"spec_decode": "ngram"})
    set_pt_flags({"telemetry": True})
    try:
        eng = ContinuousBatchingEngine(model, _ecfg(True))
        eng.add_request(np.concatenate([unit] * 5), max_new_tokens=32)
        _drain(eng)
        snap = eng.spec_snapshot()
        assert snap["enabled"] and snap["mode"] == "ngram"
        assert snap["proposed"] >= snap["accepted"] > 0
        assert 0 < snap["acceptance_rate"] <= 1
        m = eng.metrics_snapshot()
        assert m["spec_decode"]["verify_calls"] == \
            snap["verify_calls"]
        sd = eng._tel.snapshot()["spec_decode"]
        assert sd["proposed_tokens"] == snap["proposed"]
        assert sd["accepted_tokens"] == snap["accepted"]
        assert sd["acceptance_rate"] == pytest.approx(
            snap["acceptance_rate"])
        text = observability.global_registry().prometheus_text()
        assert "pt_serve_spec_accepted_tokens_total" in text
        assert "pt_serve_spec_acceptance_rate" in text
    finally:
        set_pt_flags({"telemetry": False})
