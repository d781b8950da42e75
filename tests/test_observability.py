"""Always-on telemetry: registry semantics, Prometheus exposition,
TrainStep sampling cadence + flight-recorder NaN dump, serving engine
metrics smoke, collective byte accounting, and the dump CLI."""

import gc
import json
import re
import subprocess
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distributed as dist, observability as obs
from paddle_tpu import optimizer as opt
from paddle_tpu.trainer import TrainStep


@pytest.fixture(autouse=True)
def _telemetry_on():
    """conftest runs the suite with telemetry off (CI compile-time);
    this module tests the instrumented paths, so flip it on per-test
    and restore."""
    prev = pt.flags.flag("telemetry")
    pt.flags.set_flags({"FLAGS_telemetry": True})
    yield
    pt.flags.set_flags({"FLAGS_telemetry": prev})


# ---------------- registry semantics ----------------

@pytest.mark.fast
def test_counter_gauge_labels():
    reg = obs.MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("op",))
    c.inc(op="read")
    c.inc(2, op="read")
    c.inc(op="write")
    assert c.value(op="read") == 3
    assert c.value(op="write") == 1
    assert c.value(op="never") == 0
    with pytest.raises(ValueError):
        c.inc(bad_label="x")
    with pytest.raises(ValueError):
        c.inc(-1, op="read")
    g = reg.gauge("depth", "queue depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value() == 3
    g.set_max(10)
    g.set_max(7)  # lower: keeps the peak
    assert g.value() == 10
    # get-or-create is idempotent; kind/label mismatch raises
    assert reg.counter("req_total", labels=("op",)) is c
    with pytest.raises(ValueError):
        reg.gauge("req_total")


@pytest.mark.fast
def test_histogram_bucket_edges():
    reg = obs.MetricsRegistry()
    edges = obs.exp_buckets(1.0, 2.0, 4)  # 1, 2, 4, 8
    assert edges == (1.0, 2.0, 4.0, 8.0)
    h = reg.histogram("lat_ms", "latency", buckets=edges)
    for v in (0.5, 1.0, 3.0, 8.0, 100.0):
        h.observe(v)
    assert h.count() == 5
    snap = reg.snapshot()["lat_ms"]["series"][0]
    # per-bucket (non-cumulative) counts: le=1 gets 0.5 and 1.0;
    # 3.0 -> le=4; 8.0 -> le=8; 100.0 -> +Inf
    assert snap["buckets"] == {"1": 2, "2": 0, "4": 1, "8": 1}
    assert snap["inf"] == 1
    assert snap["sum"] == pytest.approx(112.5)
    assert h.percentile(50) == 3.0
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(4.0, 2.0))
    with pytest.raises(ValueError):
        obs.exp_buckets(0, 2, 3)


@pytest.mark.fast
def test_prometheus_exposition_parses():
    reg = obs.MetricsRegistry()
    reg.counter("a_total", "with \"quotes\"", labels=("op",)).inc(
        op='weird "value"\nline')
    reg.gauge("b_bytes", "a gauge").set(1.5)
    h = reg.histogram("c_ms", "a histogram", labels=("route",),
                      buckets=(1.0, 10.0))
    h.observe(0.5, route="/x")
    h.observe(20.0, route="/x")
    text = reg.prometheus_text()
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
        r' -?[0-9.eE+-]+(inf|nan)?$')
    seen_types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            seen_types[name] = kind
            continue
        assert sample_re.match(line), f"unparseable sample line: {line!r}"
    assert seen_types == {"a_total": "counter", "b_bytes": "gauge",
                          "c_ms": "histogram"}
    # histogram contract: cumulative le buckets + +Inf + _sum/_count
    assert 'c_ms_bucket{route="/x",le="1"} 1' in text
    assert 'c_ms_bucket{route="/x",le="10"} 1' in text
    assert 'c_ms_bucket{route="/x",le="+Inf"} 2' in text
    assert 'c_ms_count{route="/x"} 2' in text


@pytest.mark.fast
def test_noop_registry_when_disabled():
    assert obs.enabled()  # default is on
    pt.flags.set_flags({"FLAGS_telemetry": False})
    try:
        reg = obs.get_registry()
        assert isinstance(reg, obs.NullRegistry)
        c = reg.counter("nope_total", "x")
        c.inc()
        c.inc(100)
        assert c.value() == 0.0
        h = reg.histogram("nope_ms", "x")
        h.observe(5.0)
        assert h.percentile(50) is None
        assert reg.prometheus_text() == ""
        assert reg.snapshot() == {}
        # the same shared null object backs every metric: no dict churn
        assert reg.gauge("other") is c
    finally:
        pt.flags.set_flags({"FLAGS_telemetry": True})
    assert isinstance(obs.get_registry(), obs.MetricsRegistry)


# ---------------- trainer instrumentation ----------------

class _Reg(pt.Layer):
    def __init__(self):
        super().__init__()
        self.fc = pt.nn.Linear(8, 8)

    def forward(self, x):
        return self.fc(x)


def _mse(o, l):
    return jnp.mean((o - l) ** 2)


@pytest.mark.fast
def test_trainstep_sampling_cadence_and_gnorm(tmp_path):
    pt.seed(0)
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    tel = obs.TrainTelemetry(sample_every=3, flight_window=16,
                             dump_dir=str(tmp_path))
    ts = TrainStep(_Reg(), opt.AdamW(1e-3), mesh, loss_fn=_mse,
                   telemetry=tel)
    x = jnp.ones((4, 8))
    y = jnp.zeros((4, 8))
    for _ in range(7):
        ts.run({"input": x, "label": y})
    # steps 3 and 6 sampled; every step leaves a ring record
    assert tel.samples == 2
    recs = tel.recorder.records()
    assert len(recs) == 7
    sampled = [r for r in recs if "loss" in r]
    assert [r["step"] for r in sampled] == [3, 6]
    for r in sampled:
        assert np.isfinite(r["loss"])
        assert np.isfinite(r["grad_norm"]) and r["grad_norm"] > 0
        assert r["tokens_per_sec"] > 0
    # non-sampled records carry only host-side fields (no device sync)
    unsampled = [r for r in recs if "loss" not in r]
    assert all(set(r) == {"step", "t_ns", "wall_ms", "shard_ms",
                          "dispatch_ms", "gc_ms", "compile_ms", "tokens"}
               for r in unsampled)
    assert not tel.watchdog.tripped


@pytest.mark.fast
def test_flight_recorder_dump_on_nan(tmp_path):
    pt.seed(0)
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    tel = obs.TrainTelemetry(sample_every=1, flight_window=8,
                             dump_dir=str(tmp_path / "fr"))
    ts = TrainStep(_Reg(), opt.AdamW(1e-3), mesh, loss_fn=_mse,
                   telemetry=tel)
    x = jnp.ones((4, 8))
    y = jnp.zeros((4, 8))
    for _ in range(3):
        ts.run({"input": x, "label": y})
    ts.run({"input": x, "label": jnp.full((4, 8), jnp.nan)})
    assert len(tel.watchdog.tripped) == 1
    step, reason, path = tel.watchdog.tripped[0]
    assert step == 4 and "non-finite loss" in reason
    dump = json.loads(open(path).read())
    assert dump["reason"] == reason
    # the window holds the K steps leading into the anomaly, with
    # grad-norms (sample_every=1 -> every record is sampled)
    assert [r["step"] for r in dump["records"]] == [1, 2, 3, 4]
    assert all("grad_norm" in r for r in dump["records"])
    assert not np.isfinite(dump["records"][-1]["loss"])


@pytest.mark.fast
def test_watchdog_grad_spike(tmp_path):
    rec = obs.FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    wd = obs.AnomalyWatchdog(rec, spike_factor=10.0, min_history=3)
    for s in range(5):
        rec.record(step=s, grad_norm=1.0)
        assert wd.check(s, 0.5, 1.0) is None
    path = wd.check(5, 0.5, 50.0)  # 50x the median
    assert path and "spike" in wd.tripped[0][1]
    assert json.loads(open(path).read())["n_records"] == 4


@pytest.mark.fast
def test_watchdog_slow_interval(tmp_path, capsys):
    rec = obs.FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    wd = obs.AnomalyWatchdog(rec, min_history=5)
    for s in range(5):  # too few intervals to judge: no check at all
        rec.record(step=s)
        assert wd.check(s, 0.5, 1.0, step_s=0.1 if s else 9.0) is None
    assert wd.check(5, 0.5, 1.0, step_s=0.19) is None  # under 2x
    path = wd.check(6, 0.5, 1.0, step_s=0.25)
    assert path and wd.tripped == [(6, "slow interval", path)]
    dump = json.loads(open(path).read())
    assert dump["reason"] == "slow interval"
    assert dump["extra"]["step"] == 6
    assert dump["extra"]["step_ms"] == pytest.approx(250.0)
    assert dump["extra"]["median_step_ms"] == pytest.approx(100.0)
    # one line on stderr names the dump
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and path in err[0] and "slow interval" in err[0]


def _sleepy_run(tel, steps, sleep_s, slow_before=None):
    """``steps`` steps of a tiny regression, each after a sleep; one
    more sleep of a second before step ``slow_before``. The collector
    is off: its pauses are what the check exists for."""
    pt.seed(0)
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    ts = TrainStep(_Reg(), opt.AdamW(1e-3), mesh, loss_fn=_mse,
                   telemetry=tel)
    batch = {"input": jnp.ones((4, 8)), "label": jnp.zeros((4, 8))}
    gc.disable()
    try:
        for step in range(1, steps + 1):
            time.sleep(sleep_s + (1.0 if step == slow_before else 0.0))
            ts.run(batch)
    finally:
        gc.enable()
    return ts


def test_a_slow_interval_dumps_and_a_steady_run_does_not(tmp_path):
    steady = obs.TrainTelemetry(sample_every=2, dump_dir=str(tmp_path / "a"))
    _sleepy_run(steady, 16, 0.03)
    assert steady.samples == 8 and not steady.watchdog.tripped
    tel = obs.TrainTelemetry(sample_every=2, dump_dir=str(tmp_path / "b"))
    _sleepy_run(tel, 16, 0.03, slow_before=14)  # a sampled step
    (step, reason, path), = tel.watchdog.tripped
    assert (step, reason) == (14, "slow interval")
    dump = json.loads(open(path).read())
    assert dump["extra"]["step_ms"] > 400
    last = dump["records"][-1]
    assert last["step"] == 14 and last["wall_ms"] < 1000
    for key in ("t_ns", "shard_ms", "dispatch_ms", "gc_ms", "compile_ms"):
        assert key in last, key


def test_step_ms_histogram_observes_each_sampled_interval(tmp_path,
                                                          monkeypatch):
    hist = obs.get_registry().histogram("pt_train_step_ms", "")
    before = hist.count()
    tel = obs.TrainTelemetry(sample_every=2, dump_dir=str(tmp_path))
    seen = []
    real = tel._step_ms.observe
    monkeypatch.setattr(tel._step_ms, "observe",
                        lambda v: (seen.append(v), real(v)))
    _sleepy_run(tel, 6, 0.02)
    assert hist.count() - before == len(seen) == tel.samples == 3
    # an interval's time per step: each step came after 20 ms of sleep
    assert all(v >= 20.0 for v in seen[1:])


def test_a_sampled_step_reads_its_scalars_once(tmp_path, monkeypatch):
    from paddle_tpu.observability import train as obs_train

    reads = []
    real = jax.device_get

    def counted(x):
        reads.append(x)
        return real(x)

    monkeypatch.setattr(obs_train.jax, "device_get", counted)
    tel = obs.TrainTelemetry(sample_every=3, dump_dir=str(tmp_path))
    _sleepy_run(tel, 6, 0.0)
    assert len(reads) == tel.samples == 2
    loss, gnorm, counters = reads[0]
    assert isinstance(loss, jax.Array) and isinstance(gnorm, jax.Array)
    assert counters is None  # a model without expert layers


def test_host_hooks_install_once_and_count_compiles(tmp_path):
    from paddle_tpu.observability import train as obs_train

    obs.TrainTelemetry(dump_dir=str(tmp_path))
    obs.TrainTelemetry(dump_dir=str(tmp_path))
    assert gc.callbacks.count(obs_train._on_gc) == 1
    host = obs_train.HOST_EVENTS
    x = jnp.arange(5.0)
    compiles, ms = host["compiles"], host["compile_ms"]
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert host["compiles"] == compiles + 1 and host["compile_ms"] > ms
    count, ms = host["gc_count"], host["gc_ms"]
    gc.collect()
    assert host["gc_count"] == count + 1 and host["gc_ms"] > ms


def test_log_memory_stats_flag(tmp_path):
    pt.seed(0)
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    pt.flags.set_flags({"FLAGS_log_memory_stats": True})
    try:
        tel = obs.TrainTelemetry(sample_every=1, dump_dir=str(tmp_path))
        ts = TrainStep(_Reg(), opt.AdamW(1e-3), mesh, loss_fn=_mse,
                       telemetry=tel)
        ts.run({"input": jnp.ones((2, 8)), "label": jnp.zeros((2, 8))})
    finally:
        pt.flags.set_flags({"FLAGS_log_memory_stats": False})
    rec = tel.recorder.records()[-1]
    # CPU backends may not implement memory_stats(); when they do, the
    # sampled record and the registry gauge must carry it
    if "memory" in rec:
        assert rec["memory"]["bytes_in_use"] >= 0
        g = obs.global_registry().get("pt_device_memory_bytes")
        assert g.value(stat="bytes_in_use") == rec["memory"]["bytes_in_use"]


# ---------------- serving instrumentation ----------------

def _tiny_engine(paged=False):
    from paddle_tpu.inference import ContinuousBatchingEngine, EngineConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    cfg = EngineConfig(max_slots=2, max_len=64, seq_buckets=(16,),
                       paged=paged, page_size=16)
    return ContinuousBatchingEngine(model, cfg), model.config


def test_serving_metrics_smoke():
    eng, mcfg = _tiny_engine(paged=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, mcfg.vocab_size, (10,)) for _ in range(4)]
    reqs = eng.run(prompts, max_new_tokens=5, max_chunk=2)
    assert all(len(r.output) == 5 for r in reqs)
    snap = eng.metrics_snapshot()
    assert snap["ttft_ms"]["count"] == 4
    assert snap["ttft_ms"]["p50"] > 0
    assert snap["ttft_ms"]["p90"] >= snap["ttft_ms"]["p50"]
    # 4 requests into 2 slots: at least 2 had to queue
    assert snap["queue_depth"]["peak"] >= 2
    assert snap["batch_occupancy"]["peak"] == 1.0
    assert snap["kv_pool"]["total"] > 0
    assert snap["kv_pool"]["peak_utilization"] > 0
    assert snap["requests"] == {"submitted": 4, "admitted": 4,
                                "finished": 4, "cancelled": 0}
    assert snap["tokens_generated"] >= 4 * 5
    assert snap["tpot_ms"]["p50"] > 0
    # window reset clears percentiles/peaks, keeps counters
    eng.metrics_window_reset()
    snap2 = eng.metrics_snapshot()
    assert snap2["ttft_ms"]["count"] == 0
    assert snap2["queue_depth"]["peak"] == 0
    assert snap2["requests"]["finished"] == 4


def test_serving_metrics_endpoint():
    from paddle_tpu.inference import start_metrics_server

    import urllib.error

    eng, mcfg = _tiny_engine(paged=False)
    eng.run([np.arange(8)], max_new_tokens=3, max_chunk=2)
    srv = start_metrics_server(eng, port=0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        assert "pt_serve_ttft_ms_bucket" in text
        # every serve series carries the engine label
        assert re.search(
            r'pt_serve_requests_finished_total\{engine="\d+"\} \d+', text)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            hz = json.loads(r.read())
        assert hz["status"] == "ok"
        assert hz["engine"]["requests"]["finished"] >= 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.shutdown()


@pytest.mark.fast
def test_serving_telemetry_per_engine_isolation():
    a = obs.ServingTelemetry()
    b = obs.ServingTelemetry()
    a.on_submit(3)
    a.on_admit(10.0)
    b.on_submit(1)
    assert a.snapshot()["queue_depth"]["peak"] == 3
    assert b.snapshot()["queue_depth"]["peak"] == 1
    # one engine's window reset must not clobber the other's series
    a.window_reset()
    assert a.snapshot()["ttft_ms"]["count"] == 0
    assert a.snapshot()["queue_depth"]["peak"] == 0
    assert b.snapshot()["queue_depth"]["peak"] == 1
    assert a.snapshot()["requests"]["submitted"] == 1
    assert b.snapshot()["requests"]["submitted"] == 1
    # cumulative histogram totals survive the window reset
    reg = obs.global_registry()
    assert reg.get("pt_serve_ttft_ms").count(engine=a.engine_id) == 1


# ---------------- collective byte accounting ----------------

@pytest.mark.fast
def test_collective_byte_accounting():
    obs.reset_comm_log()
    mesh = dist.build_mesh(dp=8)
    x = jnp.arange(32, dtype=jnp.float32)
    out = dist.all_reduce(x, mesh=mesh)
    assert out.shape == x.shape
    log = [e for e in obs.comm_log() if e["op"] == "all_reduce"]
    assert len(log) == 1
    # per-participant payload at trace time: 32/8 rows of 4 bytes
    assert log[0]["bytes"] == 16
    assert log[0]["axis"] == "dp"
    assert log[0]["traced_calls"] == 1
    # call-site attribution points at THIS file, not the plumbing
    assert log[0]["site"].startswith("test_observability.py:")
    # a second execution of the SAME compiled program adds nothing
    dist.all_reduce(x, mesh=mesh)
    log2 = [e for e in obs.comm_log() if e["op"] == "all_reduce"]
    assert log2[0]["traced_calls"] <= 2  # retrace at most (new shard_map)
    c = obs.global_registry().get("pt_collective_traced_bytes_total")
    assert c.value(op="all_reduce", axis="dp") >= 16


# ---------------- dump CLI ----------------

def test_dump_cli_smoke():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PT_FLAGS_telemetry="on")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.dump",
         "--no-device"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 0, proc.stderr
    snap = json.loads(proc.stdout)
    assert snap["telemetry_enabled"] is True
    assert "metrics" in snap and "collectives" in snap
    assert "device_memory" not in snap
