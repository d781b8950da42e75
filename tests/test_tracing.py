"""Per-request lifecycle tracing, SLO accounting, cancel, and the
serving front-end's honest readiness — the observability contract this
PR adds on top of PR 2's aggregate telemetry.

Under test:
  - lifecycle spans reconstruct a request's queued → admitted →
    prefill → decode/verify → finish timeline EXACTLY (token counts
    match the engine's host-side state) in BOTH cache modes × spec
    decode on/off;
  - the exported trace is valid Chrome trace-event JSON (Perfetto-
    loadable shape: ph/ts/dur/pid/tid on every event);
  - ``PT_FLAGS_telemetry=off`` leaves the engine with NO tracer and no
    telemetry objects — every hook site is a single identity check;
  - ``PT_FLAGS_trace_sample`` thins deterministically (a sampled
    request's events are complete, never a torn subset);
  - tracing + SLO accounting add ZERO compiled programs to the PR-5
    program set (the whole layer is host-side);
  - SLO attainment (met/violated/goodput) lands in slo_snapshot, the
    unified metrics_snapshot, and the registry counters;
  - ``cancel()`` frees the slot, paged KV pages and prefix-cache refs
    leak-free, queued or mid-flight;
  - ``/healthz`` returns 503 while admission is saturated; ``/trace``
    serves the tracer; flight-recorder dumps attach the trace tail.
"""

import json
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags as F
from paddle_tpu import observability as obs
from paddle_tpu.inference.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    start_metrics_server,
)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import tracing

pytestmark = pytest.mark.fast


def _model(seed=0):
    pt.seed(seed)
    cfg = LlamaConfig.tiny()
    return LlamaForCausalLM(cfg), cfg


def _ecfg(paged, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 128)
    kw.setdefault("seq_buckets", (32,))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    return EngineConfig(paged=paged, **kw)


def _drain(eng, step=None):
    step = step or eng.step
    while step() or eng._queue or eng.active.any():
        pass


@pytest.fixture
def obs_flags():
    """set_flags with restore for the flags this file flips (telemetry
    defaults OFF in conftest — tracing tests turn it on explicitly)."""
    keys = ("telemetry", "trace_sample", "trace_buffer", "spec_decode",
            "prefix_cache", "prefill_chunk")
    saved = {k: F.flag(k) for k in keys}
    yield F.set_flags
    F.set_flags(saved)


def _validate_chrome(doc):
    """Minimal Chrome trace-event JSON schema check (the shape
    Perfetto / chrome://tracing loads)."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    json.loads(json.dumps(doc))  # fully JSON-serializable
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] in ("X", "i", "M")
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")


# ---------------- lifecycle reconstruction ----------------

@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("spec", ["off", "ngram"])
def test_lifecycle_trace_reconstructs(paged, spec, obs_flags):
    """The exported spans reconstruct each request's admit → prefill →
    decode/verify → finish timeline exactly: token counts derived from
    the trace equal the engine's own host-side state, in both cache
    modes with spec decode on and off."""
    model, cfg = _model(1)
    obs_flags({"telemetry": True, "trace_sample": 1.0,
               "spec_decode": spec})
    eng = ContinuousBatchingEngine(model, _ecfg(paged))
    rng = np.random.default_rng(2)
    unit = rng.integers(1, cfg.vocab_size, 4)
    prompts = [np.concatenate([unit] * 5),
               rng.integers(1, cfg.vocab_size, 9),
               rng.integers(1, cfg.vocab_size, 17)]
    rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    _drain(eng)

    tr = eng._tracer
    assert tr is not None
    _validate_chrome(tracing.chrome_trace([tr]))

    raw = tr.events()
    steps = [e for e in raw if e["kind"] == "step"]
    assert {e["name"] for e in steps} >= {"prefill_chunk", "decode"} \
        if spec == "off" else True
    for rid in rids:
        req = eng._finished[rid]
        mine = [e for e in raw if e.get("rid") == rid]
        names = [e["name"] for e in mine]
        assert names.count("queued") == 1
        assert names.count("admitted") == 1
        assert names.count("active") == 1
        assert "prefill_chunk" in names  # chunked admission default
        admitted = next(e for e in mine if e["name"] == "admitted")
        active = next(e for e in mine if e["name"] == "active")
        # spans: queued..admitted covers TTFT; admitted..finish covers
        # decode; they tile the request's life in order
        assert admitted["t1"] is not None and active["t1"] is not None
        assert admitted["t0"] <= admitted["t1"] <= active["t0"] \
            <= active["t1"]
        assert admitted["args"]["first_tokens"] == 1
        assert admitted["args"]["prompt_tokens"] == \
            eng._finished[rid].prompt.size
        # EXACT reconstruction: prefill's first token + every step
        # event's per-request advancement == the tokens the engine
        # actually emitted
        advanced = sum(
            e["args"]["advanced"].get(rid, 0) for e in steps
            if "advanced" in e["args"])
        assert 1 + advanced == len(req.output)
        assert active["args"]["tokens"] == len(req.output)
        assert active["args"]["reason"] == "max_new_tokens"
    if spec == "ngram" and eng.spec_stats["verify_calls"] > 0:
        verifies = [e for e in steps if e["name"] == "verify"]
        assert len(verifies) == eng.spec_stats["verify_calls"]
        assert sum(e["args"]["proposed"] for e in verifies) == \
            eng.spec_stats["proposed"]
        assert sum(e["args"]["accepted"] for e in verifies) == \
            eng.spec_stats["accepted"]
    # step composition fields are present on every sampled decode step
    for e in steps:
        if e["name"] in ("decode", "decode_chunk", "verify"):
            assert 0 < e["args"]["occupancy"] <= 1.0
            assert e["args"]["chunk_budget_spent"] >= 1
            assert e["args"]["dispatch_ms"] >= 0
            # profiler off (this file's default): the honest fallback
            # estimate — host wall dispatch-done -> token sync (the
            # field PR 6 called device_wall_ms_est; renamed because it
            # is a host-wall upper bound, not a device measurement)
            assert e["args"]["sync_wall_ms"] >= 0
            assert "device_ms" not in e["args"]


def test_chunked_scheduler_trace_and_jsonl(obs_flags):
    """step_chunk drives produce decode_chunk step events; the JSONL
    export round-trips every raw event."""
    model, cfg = _model(2)
    obs_flags({"telemetry": True})
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    rng = np.random.default_rng(0)
    eng.run([rng.integers(1, cfg.vocab_size, 8) for _ in range(3)],
            max_new_tokens=6, max_chunk=4)
    raw = eng._tracer.events()
    chunks = [e for e in raw if e["name"] == "decode_chunk"]
    assert chunks and all(e["args"]["chunk_budget_spent"] == 4
                          for e in chunks)
    lines = tracing.jsonl([eng._tracer]).splitlines()
    assert len(lines) == len(raw)
    ts = [json.loads(l)["t0"] for l in lines]
    assert ts == sorted(ts)


# ---------------- off-switch + sampling ----------------

def test_telemetry_off_is_noop():
    """conftest default: PT_FLAGS_telemetry=off — the engine holds no
    tracer and no telemetry, and serving works untouched."""
    assert not obs.enabled()
    model, cfg = _model(3)
    before = set(map(id, tracing.all_tracers()))
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    assert eng._tracer is None and eng._tel is None
    reqs = eng.run([np.arange(1, 9)], max_new_tokens=4)
    assert len(reqs[0].output) == 4
    after = set(map(id, tracing.all_tracers()))
    assert after <= before  # no tracer was registered


def test_trace_sample_zero_disables_tracer(obs_flags):
    obs_flags({"telemetry": True, "trace_sample": 0.0})
    model, _ = _model(3)
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    assert eng._tel is not None and eng._tracer is None


def test_trace_sample_thins_deterministically(obs_flags):
    """rate 0.5 → every 2nd request id is traced COMPLETELY; the
    others leave no events at all (never a torn subset)."""
    obs_flags({"telemetry": True, "trace_sample": 0.5})
    model, cfg = _model(4)
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    assert eng._tracer.period == 2
    rng = np.random.default_rng(1)
    rids = [eng.add_request(rng.integers(1, cfg.vocab_size, 8),
                            max_new_tokens=3) for _ in range(4)]
    _drain(eng)
    raw = eng._tracer.events()
    traced = {e["rid"] for e in raw if e["kind"] == "request"}
    assert traced == {r for r in rids if r % 2 == 0}
    for rid in traced:
        names = [e["name"] for e in raw if e.get("rid") == rid]
        assert {"queued", "admitted", "active"} <= set(names)


def test_trace_ring_bounded(obs_flags):
    obs_flags({"telemetry": True, "trace_buffer": 8})
    model, cfg = _model(4)
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    rng = np.random.default_rng(2)
    eng.run([rng.integers(1, cfg.vocab_size, 8) for _ in range(4)],
            max_new_tokens=8)
    assert len(eng._tracer) <= 8  # old events fell off, no growth


# ---------------- compile-count guard ----------------

def test_tracing_and_slo_add_zero_compiled_programs(compile_counter,
                                                    obs_flags):
    """The whole observability layer is host-side: an engine with
    telemetry + tracing + SLO accounting + a mid-flight cancel compiles
    EXACTLY the same program set as the telemetry-off PR-5 engine."""
    model, cfg = _model(5)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in (7, 13, 19)]

    eng = ContinuousBatchingEngine(model, _ecfg(True))
    eng.run(prompts, max_new_tokens=8, max_chunk=4)
    off_set = compile_counter()
    assert off_set == {"prefill_chunk": 1, "decode_chunk": 1}

    obs_flags({"telemetry": True, "trace_sample": 1.0})
    eng2 = ContinuousBatchingEngine(model, _ecfg(True))
    rids = [eng2.add_request(p, max_new_tokens=8, slo="interactive")
            for p in prompts]
    eng2.step_chunk(4)
    eng2.cancel(rids[-1])
    while eng2.step_chunk(4) or eng2._queue or eng2.active.any():
        pass
    assert eng2._tracer is not None and len(eng2._tracer) > 0
    assert eng2.slo_snapshot()["met"] + eng2.slo_snapshot()["violated"] \
        >= 2
    on_set = compile_counter()
    delta = {k: on_set[k] - off_set.get(k, 0) for k in on_set
             if on_set[k] - off_set.get(k, 0)}
    # the second engine re-specializes its OWN two programs (fresh jit
    # closures per engine) and nothing else: tracing/SLO/cancel added
    # zero programs
    assert delta == off_set


# ---------------- SLO accounting ----------------

def test_slo_met_and_violated(obs_flags):
    obs_flags({"telemetry": True})
    model, cfg = _model(6)
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    rng = np.random.default_rng(4)
    # impossible targets → violated; absurdly generous → met
    r_bad = eng.add_request(rng.integers(1, cfg.vocab_size, 8),
                            max_new_tokens=3, slo="interactive",
                            ttft_target_ms=1e-6, tpot_target_ms=1e-6)
    r_good = eng.add_request(rng.integers(1, cfg.vocab_size, 8),
                             max_new_tokens=3, slo="interactive",
                             ttft_target_ms=1e9, tpot_target_ms=1e9)
    _drain(eng)
    snap = eng.slo_snapshot()
    cls = snap["classes"]["interactive"]
    assert cls["met"] == 1 and cls["violated"] == 1
    assert cls["ttft_violations"] == 1
    assert snap["goodput"] == 0.5
    assert eng._finished[r_bad].slo_met is False
    assert eng._finished[r_good].slo_met is True
    assert eng._finished[r_good].tpot_ms > 0
    # registry counters + goodput gauge carry the slo label
    reg = obs.global_registry()
    lab = {"engine": eng._tel.engine_id, "slo": "interactive",
           "tenant": "-"}
    assert reg.get("pt_serve_slo_met_total").value(**lab) == 1
    assert reg.get("pt_serve_slo_violated_total").value(**lab) == 1
    assert reg.get("pt_serve_slo_goodput").value(**lab) == 0.5
    # unified document carries the same numbers
    m = eng.metrics_snapshot()
    assert m["slo"]["classes"]["interactive"]["met"] == 1
    assert m["request_tpot_ms"]["count"] == 2


def test_slo_class_defaults_and_validation():
    model, cfg = _model(6)
    eng = ContinuousBatchingEngine(model, _ecfg(False))
    with pytest.raises(ValueError, match="slo"):
        eng.add_request(np.arange(1, 5), slo="platinum")
    with pytest.raises(ValueError, match="ttft_target_ms"):
        eng.add_request(np.arange(1, 5), slo="batch", ttft_target_ms=-1)
    rid = eng.add_request(np.arange(1, 5), max_new_tokens=2,
                          slo="batch")
    req = next(r for r in eng._queue if r.rid == rid)
    assert req.ttft_target_ms == 5000.0  # class default applied
    assert req.tpot_target_ms == 1000.0
    # bare targets imply the "custom" class
    rid2 = eng.add_request(np.arange(1, 5), max_new_tokens=2,
                           ttft_target_ms=1e9)
    req2 = next(r for r in eng._queue if r.rid == rid2)
    assert req2.slo == "custom" and req2.tpot_target_ms is None
    # a targetless "custom" would trivially always be met — rejected
    with pytest.raises(ValueError, match="custom"):
        eng.add_request(np.arange(1, 5), slo="custom")
    _drain(eng)
    snap = eng.slo_snapshot()
    assert set(snap["classes"]) == {"batch", "custom"}


def test_metrics_snapshot_unified_with_telemetry_off():
    """One document, no stitching: prefix/spec/SLO sub-snapshots ride
    metrics_snapshot even when the registry is off."""
    model, cfg = _model(7)
    eng = ContinuousBatchingEngine(model, _ecfg(True))
    eng.run([np.arange(1, 10)], max_new_tokens=3)
    snap = eng.metrics_snapshot()
    assert snap["telemetry"] == "off"
    assert snap["prefix_cache"]["enabled"] is True
    assert snap["spec_decode"]["mode"] == "off"
    assert snap["slo"] == {"classes": {}, "met": 0, "violated": 0,
                           "goodput": None}
    assert snap["slots"]["max"] == 2


# ---------------- cancel ----------------

def test_cancel_queued_and_active_leak_free(obs_flags):
    """Cancel frees the slot, every paged KV page and the adopted
    prefix refs mid-flight; the pool is fully recoverable and the
    engine keeps serving."""
    obs_flags({"telemetry": True})
    model, cfg = _model(8)
    eng = ContinuousBatchingEngine(model, _ecfg(True, max_slots=2))
    free0 = eng.pool.free_pages
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab_size, 16)  # two hash blocks
    mk = lambda: np.concatenate(  # noqa: E731
        [shared, rng.integers(1, cfg.vocab_size, 4)])
    rids = [eng.add_request(mk(), max_new_tokens=20) for _ in range(3)]
    eng.step()  # admit 2, third queues
    assert eng.cancel(rids[2])  # queued cancel
    eng.step()
    assert eng.cancel(rids[0])  # active cancel, mid-flight
    assert not eng.cancel(rids[0])  # idempotent: already gone
    assert not eng.cancel(10**9)  # unknown rid
    _drain(eng)
    for rid in rids:
        assert rid in eng._finished
    assert eng._finished[rids[2]].cancelled
    assert eng._finished[rids[2]].output == []  # never admitted
    assert eng._finished[rids[0]].cancelled
    assert eng._finished[rids[0]].finish_reason == "cancel"
    assert len(eng._finished[rids[1]].output) == 20  # survivor intact
    # cancel events in the trace
    raw = eng._tracer.events()
    cancels = [e for e in raw if e["name"] == "cancel"]
    assert {e["rid"] for e in cancels} == {rids[0], rids[2]}
    assert {e["args"]["stage"] for e in cancels} == {"queued", "active"}
    # leak-free: beyond store-retained prefix pages (evictable), the
    # pool fully recovers
    eng._evict_pages(10 ** 9)
    assert eng.pool.free_pages == free0
    assert not eng.pool.ref
    assert sorted(eng._free_heap) == [0, 1]
    # cancelled counter exported
    assert eng.metrics_snapshot()["requests"]["cancelled"] == 2
    # engine still serves after the churn
    assert len(eng.run([mk()], max_new_tokens=4)[0].output) == 4


def test_cancel_contiguous_mode():
    model, cfg = _model(8)
    eng = ContinuousBatchingEngine(model, _ecfg(False, max_slots=1))
    r0 = eng.add_request(np.arange(1, 9), max_new_tokens=30)
    r1 = eng.add_request(np.arange(1, 9), max_new_tokens=3)
    eng.step()
    assert eng.cancel(r0)  # active → slot frees for the queued r1
    _drain(eng)
    assert eng._finished[r0].cancelled
    assert len(eng._finished[r1].output) == 3


# ---------------- endpoints + recorder + dump ----------------

def test_healthz_backpressure_and_trace_endpoint(obs_flags):
    obs_flags({"telemetry": True})
    model, cfg = _model(9)
    eng = ContinuousBatchingEngine(model, _ecfg(False, max_slots=1))
    r0 = eng.add_request(np.arange(1, 9), max_new_tokens=40)
    r1 = eng.add_request(np.arange(1, 9), max_new_tokens=2)
    eng.step()  # r0 admitted, r1 waits: saturated
    bp = eng.backpressure()
    assert bp == {"queue_depth": 1, "free_slots": 0, "occupancy": 1.0,
                  "saturated": True, "draining": False,
                  "degraded": False, "degradation_level": 0}
    srv = start_metrics_server(eng, port=0)
    try:
        port = srv.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert ei.value.code == 503
        hz = json.loads(ei.value.read())
        assert hz["status"] == "saturated"
        assert hz["backpressure"]["queue_depth"] == 1
        # routers need the RUNG, not just the flag: the payload
        # carries the numeric ladder level alongside the degraded bit
        assert hz["degraded"] is False
        assert hz["degradation_level"] == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace", timeout=10) as r:
            doc = json.loads(r.read())
        _validate_chrome(doc)
        _drain(eng)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert r.status == 200
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.shutdown()
    assert len(eng._finished[r1].output) == 2


def test_backpressure_sees_pool_exhaustion():
    """The paged engine's dominant stall — slots FREE but the pool out
    of pages — must read as saturated, not as a healthy replica."""
    model, cfg = _model(9)
    # pool sized for exactly one resident request (+ sink page)
    eng = ContinuousBatchingEngine(model, _ecfg(
        True, max_slots=2, max_len=128, page_size=8, n_pages=10))
    rng = np.random.default_rng(6)
    r0 = eng.add_request(rng.integers(1, cfg.vocab_size, 8),
                         max_new_tokens=56)  # 64 tokens = 8 pages
    r1 = eng.add_request(rng.integers(1, cfg.vocab_size, 8),
                         max_new_tokens=56)
    eng.step()  # admits r0; r1 blocks on pages with a slot still free
    bp = eng.backpressure()
    assert bp["free_slots"] >= 1
    assert bp["queue_depth"] == 1
    assert bp["pool_blocked"] and bp["saturated"]
    _drain(eng)  # r0 finishes -> pages free -> r1 admits and finishes
    assert len(eng._finished[r1].output) == 56
    bp = eng.backpressure()
    assert not bp["saturated"] and not bp["pool_blocked"]


def test_trace_endpoint_404_when_tracing_off():
    model, cfg = _model(9)
    eng = ContinuousBatchingEngine(model, _ecfg(False))  # telemetry off
    srv = start_metrics_server(eng, port=0)
    try:
        port = srv.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.shutdown()


def test_flight_recorder_attaches_trace_tail(tmp_path, obs_flags):
    import time as _time

    obs_flags({"telemetry": True})
    tr = tracing.Tracer(engine_id="fr-test")
    # timestamps beyond any event earlier tests' still-live tracers
    # recorded: recent_events is process-wide and keeps the NEWEST
    base = _time.perf_counter() + 3600.0
    for i in range(5):
        tr.step(tr.next_step(), "decode", base + i, base + i + 0.5,
                tokens_advanced=1)
    rec = obs.FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                             trace_tail=3)
    rec.record(step=1, loss=float("nan"))
    path = rec.dump("nan loss")
    payload = json.load(open(path))
    tail = payload["trace_tail"]
    assert len(tail) == 3  # bounded to trace_tail
    assert all(e["name"] == "decode" for e in tail)
    # the tail is the MOST RECENT events
    assert [e["t0"] for e in tail] == [base + 2, base + 3, base + 4]
    # trace_tail=0 disables the attachment entirely
    rec2 = obs.FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                              trace_tail=0)
    rec2.record(step=1, loss=1.0)
    assert "trace_tail" not in json.load(open(rec2.dump("x")))


def test_dump_cli_trace(capsys, obs_flags):
    obs_flags({"telemetry": True})
    from paddle_tpu.observability import dump
    tr = tracing.Tracer(engine_id="cli-test")
    tr.request(0, "queued", prompt_tokens=4)
    tr.step(tr.next_step(), "decode", 0.0, 0.1, tokens_advanced=1)
    assert dump.main(["--trace", "--no-device"]) == 0
    doc = json.loads(capsys.readouterr().out)
    _validate_chrome(doc)
    assert any(e["name"] == "decode" for e in doc["traceEvents"])
    assert dump.main(["--trace-jsonl"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert any(json.loads(l)["name"] == "queued" for l in lines)
