"""Concurrency/soak tests for the host-threaded subsystems: the async
checkpoint writer, the process DataLoader, and the serving scheduler.

Parity intent: the reference runs sanitizer CI builds and worker-kill
tests (test/collective/, DataLoader worker-exit tests); functional purity
covers device races here, so the host-side threads are what need stress
coverage.
"""

import gc
import os
import queue
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import checkpoint as dck


# ---------------------------------------------------------------------------
# async checkpoint writer
# ---------------------------------------------------------------------------

def test_async_overlapping_saves_serialize(tmp_path):
    """Back-to-back async saves: the second must wait for the first (one
    in-flight writer), and both checkpoints must be committed + correct."""
    saver = dck.AsyncCheckpointer()
    arrays = {f"w{i}": jnp.full((64, 64), float(i)) for i in range(4)}
    paths = []
    for step in range(4):
        p = str(tmp_path / f"ck{step}")
        sd = {k: v + step for k, v in arrays.items()}
        saver.save(sd, p)
        paths.append((p, step))
    saver.wait_until_finished()
    for p, step in paths:
        assert dck.is_committed(p)
        got = dck.load_state_dict(p)
        np.testing.assert_array_equal(
            np.asarray(got["w3"]), np.full((64, 64), 3.0 + step))


def test_async_rotation_same_path(tmp_path):
    """Repeated async saves to the SAME path (checkpoint rotation): the
    final committed state is the last save, never a torn mix."""
    saver = dck.AsyncCheckpointer()
    p = str(tmp_path / "latest")
    for step in range(5):
        sd = {"w": jnp.full((32, 32), float(step)),
              "step": jnp.asarray(step)}
        saver.save(sd, p)
    saver.wait_until_finished()
    got = dck.load_state_dict(p)
    assert int(got["step"]) == 4
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.full((32, 32), 4.0))


def test_crash_mid_save_keeps_previous(tmp_path):
    """A save that died before the COMMITTED marker must not damage the
    previous checkpoint; recovery serves the old state."""
    p = str(tmp_path / "c")
    dck.save_state_dict({"w": jnp.zeros((8,))}, p)
    # simulate a writer that crashed mid-write: partial tmp, no marker
    os.makedirs(p + ".tmp", exist_ok=True)
    with open(os.path.join(p + ".tmp", "w.part0.npy"), "wb") as f:
        f.write(b"garbage")
    got = dck.load_state_dict(p)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.zeros((8,)))
    # a fresh save over the wreckage must succeed and win
    dck.save_state_dict({"w": jnp.ones((8,))}, p)
    got = dck.load_state_dict(p)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.ones((8,)))


def test_crash_between_commit_renames_promotes_new(tmp_path):
    """Crash between _commit's two renames (path gone, marked tmp
    present): recovery must finish the commit and serve the NEW state."""
    import shutil

    p = str(tmp_path / "c")
    dck.save_state_dict({"w": jnp.zeros((8,))}, p)
    dck.save_state_dict({"w": jnp.ones((8,))}, str(tmp_path / "v2"))
    # recreate the mid-commit wreckage: old ckpt at .old, new (marked)
    # at .tmp, nothing at path
    open(os.path.join(str(tmp_path / "v2"), "COMMITTED"), "a").close()
    os.rename(p, p + ".old")
    shutil.rmtree(p + ".tmp", ignore_errors=True)
    os.rename(str(tmp_path / "v2"), p + ".tmp")
    got = dck.load_state_dict(p)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.ones((8,)))


def test_crash_before_swap_keeps_acknowledged_state(tmp_path):
    """Crash after the marker write but BEFORE the swap (path intact):
    the unacknowledged save is dropped and the last acknowledged
    checkpoint keeps serving — never a torn state."""
    p = str(tmp_path / "c")
    dck.save_state_dict({"w": jnp.zeros((8,))}, p)
    dck.save_state_dict({"w": jnp.ones((8,))}, str(tmp_path / "v2"))
    open(os.path.join(str(tmp_path / "v2"), "COMMITTED"), "a").close()
    os.rename(str(tmp_path / "v2"), p + ".tmp")
    got = dck.load_state_dict(p)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.zeros((8,)))


def test_async_writer_error_propagates(tmp_path):
    """A failing background write surfaces on wait_until_finished (or the
    next save), not silently."""
    saver = dck.AsyncCheckpointer()
    target = tmp_path / "blocked"
    saver.save({"w": jnp.ones((4,))}, str(target))
    saver.wait_until_finished()
    # now make the path unwritable-over: a FILE where the dir must go
    bad = tmp_path / "f" / "nested"  # parent doesn't exist and can't
    with open(tmp_path / "f", "w") as f:
        f.write("x")
    with pytest.raises(Exception):
        saver.save({"w": jnp.ones((4,))}, str(bad))
        saver.wait_until_finished()


def test_async_save_under_training_mutation(tmp_path):
    """Soak: snapshot isolation — the training loop keeps mutating (and
    re-binding) arrays while the writer flushes; every committed ckpt
    must equal the state at ITS save point."""
    saver = dck.AsyncCheckpointer()
    w = jnp.zeros((128, 128))
    expect = {}
    for step in range(6):
        p = str(tmp_path / f"s{step}")
        saver.save({"w": w, "step": jnp.asarray(step)}, p)
        expect[p] = float(w[0, 0])
        w = w + 1.0  # training continues immediately
    saver.wait_until_finished()
    for p, v in expect.items():
        got = dck.load_state_dict(p)
        assert float(np.asarray(got["w"])[0, 0]) == v


# ---------------------------------------------------------------------------
# DataLoader process workers
# ---------------------------------------------------------------------------

class _CrashAt:
    """Dataset whose worker hard-exits on one index (simulates an OOM-
    killed / segfaulted worker)."""

    def __init__(self, n=64, crash_at=37):
        self.n, self.crash_at = n, crash_at

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.crash_at:
            os._exit(13)
        return np.full((4,), i, np.float32)


@pytest.mark.timeout(120)
def test_process_worker_crash_raises_not_hangs():
    """A worker killed mid-batch must surface as an exception on the
    training loop promptly — never a silent hang (reference parity:
    DataLoader worker-exit detection)."""
    from paddle_tpu import io

    dl = io.DataLoader(_CrashAt(), batch_size=8, num_workers=2,
                       use_process_workers=True, shuffle=False)
    with pytest.raises(Exception):
        for _ in dl:
            pass


@pytest.mark.timeout(120)
def test_process_loader_abandoned_mid_epoch_shuts_down():
    """Dropping the iterator mid-epoch must tear the pool down without
    leaking live worker processes."""
    import multiprocessing as mp

    from paddle_tpu import io

    class _Slow:
        def __len__(self):
            return 256

        def __getitem__(self, i):
            time.sleep(0.01)
            return np.full((4,), i, np.float32)

    dl = io.DataLoader(_Slow(), batch_size=4, num_workers=2,
                       use_process_workers=True, shuffle=False)
    it = iter(dl)
    next(it)
    next(it)
    before = {p.pid for p in mp.active_children()}
    assert before  # workers exist mid-epoch
    it.close()  # abandon the epoch
    gc.collect()
    deadline = time.time() + 30
    while time.time() < deadline:
        alive = [p for p in mp.active_children() if p.is_alive()]
        if not alive:
            break
        time.sleep(0.25)
    assert not [p for p in mp.active_children() if p.is_alive()]


@pytest.mark.timeout(120)
def test_thread_loader_epoch_soak():
    """Threaded loader: several full epochs back-to-back with shuffle —
    every element delivered exactly once per epoch, no dropped/duplicated
    futures under prefetch pressure."""
    from paddle_tpu import io

    class _Ds:
        def __len__(self):
            return 101  # prime: exercises ragged last batch

        def __getitem__(self, i):
            return np.asarray([i], np.int64)

    dl = io.DataLoader(_Ds(), batch_size=7, num_workers=4, shuffle=True,
                       drop_last=False)
    for _ in range(3):
        seen = sorted(int(x) for b in dl for x in np.asarray(b).ravel())
        assert seen == list(range(101))


# ---------------------------------------------------------------------------
# serving scheduler
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_serving_scheduler_threaded_arrivals():
    """Requests land from a producer thread while the engine loop runs:
    every request must finish with the requested token count — no lost,
    duplicated, or starved slots (soak for the admission bookkeeping)."""
    from paddle_tpu.inference.serving import (
        ContinuousBatchingEngine,
        EngineConfig,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128, use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(model, EngineConfig(
        max_slots=3, max_len=96, seq_buckets=(32,),
        cache_dtype=jnp.float32))

    n_requests, new_tokens = 14, 6
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (int(rng.integers(4, 28)),))
               for _ in range(n_requests)]
    ids = []
    errs = []

    def producer():
        try:
            for p in prompts:
                ids.append(eng.add_request(p, new_tokens))
                time.sleep(float(rng.uniform(0.0, 0.02)))
        except BaseException as e:  # surfaces in the main thread assert
            errs.append(e)

    t = threading.Thread(target=producer)
    t.start()
    deadline = time.time() + 240
    while time.time() < deadline:
        busy = eng.step_chunk(4)
        if not t.is_alive() and not busy and not eng.active.any() \
                and len(eng._finished) >= n_requests:
            break
    t.join(timeout=10)
    assert not errs, errs
    assert sorted(eng._finished) == sorted(ids)
    for rid in ids:
        out = eng._finished[rid].output
        assert len(out) == new_tokens, (rid, len(out))


@pytest.mark.timeout(300)
def test_cancel_soak_no_leaks():
    """Soak for ``engine.cancel``: requests land from a producer thread
    while the scheduler loop cancels every third one at staggered
    points (queued, mid-prefill-wave boundaries, mid-decode). After the
    storm: every rid is accounted for, survivors got their full token
    count, and the paged pool + prefix-cache refcounts recover to the
    initial state — the leak-free primitive the SLO-aware scheduler's
    timeout path builds on (ROADMAP item 5)."""
    from paddle_tpu.inference.serving import (
        ContinuousBatchingEngine,
        EngineConfig,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128, use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(model, EngineConfig(
        max_slots=3, max_len=96, seq_buckets=(32,),
        cache_dtype=jnp.float32, paged=True, page_size=8))
    free0 = eng.pool.free_pages

    n_requests, new_tokens = 18, 6
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, (16,))  # 2 prefix blocks
    prompts = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size,
                              (int(rng.integers(2, 10)),))])
        for _ in range(n_requests)]
    ids = []
    errs = []
    prng = np.random.default_rng(7)

    def producer():
        try:
            for p in prompts:
                ids.append(eng.add_request(p, new_tokens))
                time.sleep(float(prng.uniform(0.0, 0.01)))
        except BaseException as e:
            errs.append(e)

    t = threading.Thread(target=producer)
    t.start()
    cancelled = set()
    deadline = time.time() + 240
    while time.time() < deadline:
        busy = eng.step_chunk(4)
        # cancel every 3rd rid exactly once, whatever state it is in
        for rid in list(ids):
            if rid % 3 == 0 and rid not in cancelled \
                    and eng.cancel(rid):
                cancelled.add(rid)
        if not t.is_alive() and not busy and not eng.active.any() \
                and len(eng._finished) >= n_requests:
            break
    t.join(timeout=10)
    assert not errs, errs
    assert sorted(eng._finished) == sorted(ids)
    for rid in ids:
        req = eng._finished[rid]
        if rid in cancelled:
            assert req.cancelled and req.finish_reason == "cancel"
        else:
            assert len(req.output) == new_tokens, (rid, len(req.output))
    assert cancelled  # the storm actually cancelled something
    # leak check: beyond store-retained prefix pages (all evictable),
    # the pool must fully recover — no page stranded by a cancel
    assert not eng.active.any()
    assert sorted(eng._free_heap) == [0, 1, 2]
    eng._evict_pages(10 ** 9)
    assert eng.pool.free_pages == free0
    assert not eng.pool.ref
    # and the engine still serves after the churn
    out = eng.run([prompts[0]], max_new_tokens=4)
    assert len(out[0].output) == 4


@pytest.mark.timeout(300)
@pytest.mark.chaos
def test_chaos_storm_no_leaks():
    """Fault-injection storm over the paged engine: step faults + NaN
    storms + latency spikes + simulated pool exhaustion from a seeded
    injector, INTERLEAVED with producer-thread arrivals, a cancel
    storm and per-request deadlines. After the storm: every rid is
    accounted for exactly once, survivors carry their exact token
    counts, zero slots / KV pages / prefix refs leak, and the engine
    still serves — the chaos coverage ROADMAP item 5 queued."""
    from paddle_tpu.inference.resilience import FaultInjector
    from paddle_tpu.inference.serving import (
        ContinuousBatchingEngine,
        EngineConfig,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128, use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    inj = FaultInjector(
        "step:0.08,nan:0.04,latency:0.25,pool:0.05,seed:13",
        latency_ms=2.0)
    eng = ContinuousBatchingEngine(model, EngineConfig(
        max_slots=3, max_len=96, seq_buckets=(32,),
        cache_dtype=jnp.float32, paged=True, page_size=8),
        fault_injector=inj)
    free0 = eng.pool.free_pages

    n_requests, new_tokens = 18, 6
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, (16,))  # 2 prefix blocks
    prompts = [np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size,
                              (int(rng.integers(2, 10)),))])
        for _ in range(n_requests)]
    ids = []
    errs = []
    prng = np.random.default_rng(7)

    def producer():
        try:
            for i, p in enumerate(prompts):
                # every 5th rides a deadline it may or may not make
                kw = {"deadline_ms": 400.0} if i % 5 == 4 else {}
                ids.append(eng.add_request(p, new_tokens, **kw))
                time.sleep(float(prng.uniform(0.0, 0.01)))
        except BaseException as e:
            errs.append(e)

    t = threading.Thread(target=producer)
    t.start()
    cancelled = set()
    deadline = time.time() + 240
    while time.time() < deadline:
        busy = eng.step_chunk(4)
        # cancel every 4th rid exactly once, whatever state it is in
        for rid in list(ids):
            if rid % 4 == 0 and rid not in cancelled \
                    and eng.cancel(rid):
                cancelled.add(rid)
        if not t.is_alive() and not busy and not eng.active.any() \
                and len(eng._finished) >= n_requests:
            break
    t.join(timeout=10)
    assert not errs, errs
    assert sorted(eng._finished) == sorted(ids)
    rs = eng.resilience_stats
    assert rs["recoveries"] > 0, "storm fired no faults — vacuous"
    assert inj.fires["pool"] > 0 and inj.fires["latency"] > 0
    for rid in ids:
        req = eng._finished[rid]
        if rid in cancelled:
            assert req.cancelled and req.finish_reason == "cancel"
        elif req.finish_reason in ("timeout", "failed"):
            # deadline victims / retry-exhausted: released cleanly,
            # partial output only
            assert len(req.output) <= new_tokens
        else:
            # survivors: EXACT token count despite replays
            assert req.finish_reason == "max_new_tokens"
            assert len(req.output) == new_tokens, (rid, len(req.output))
    assert cancelled
    # leak check: beyond store-retained prefix pages (all evictable),
    # the pool must fully recover — no page stranded by any of the
    # cancel/timeout/quarantine paths
    assert not eng.active.any()
    assert sorted(eng._free_heap) == [0, 1, 2]
    eng._evict_pages(10 ** 9)
    assert eng.pool.free_pages == free0
    assert not eng.pool.ref
    # and the engine still serves after the storm (injector off)
    eng._injector = None
    out = eng.run([prompts[0]], max_new_tokens=4)
    assert len(out[0].output) == 4


# ---------------------------------------------------------------------------
# nested-checkpoint structure edge cases (review findings r5)
# ---------------------------------------------------------------------------

def test_nested_roundtrip_preserves_empty_subtrees(tmp_path):
    """SGD slot dicts and an fp32 model's master dict are EMPTY dicts —
    the nested flatten must round-trip them, or restoring a
    TrainStep.state_dict() fails on pytree-structure mismatch."""
    sd = {
        "params": {"w": jnp.ones((4,))},
        "opt_state": {
            "step": jnp.asarray(3),
            "slots": {"w": {}},
            "master": {},
        },
    }
    p = str(tmp_path / "c")
    dck.save_state_dict(sd, p)
    got = dck.load_state_dict(p)
    assert got["opt_state"]["slots"] == {"w": {}}
    assert got["opt_state"]["master"] == {}
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.ones((4,)))
