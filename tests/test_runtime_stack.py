"""Flags, profiler scheduler, metrics, hapi Model, launch CLI (parity
model: the aux-subsystem tests in SURVEY.md §4/§5)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, io, metric, nn, optimizer as opt
from paddle_tpu.hapi import EarlyStopping, Model
from paddle_tpu.profiler import ProfilerState, make_scheduler


def test_flags_roundtrip():
    assert flags.flag("io_prefetch_depth") == 2
    flags.set_flags({"FLAGS_io_prefetch_depth": 4})
    assert flags.get_flags("FLAGS_io_prefetch_depth") == {
        "FLAGS_io_prefetch_depth": 4
    }
    with pytest.raises(KeyError):
        flags.set_flags({"FLAGS_nope": 1})
    flags.set_flags({"FLAGS_io_prefetch_depth": 2})


def test_profiler_scheduler():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(6)]
    assert states[0] == ProfilerState.CLOSED
    assert states[1] == ProfilerState.READY
    assert states[2] == ProfilerState.RECORD
    assert states[3] == ProfilerState.RECORD_AND_RETURN
    assert states[4] == ProfilerState.CLOSED  # repeat exhausted


def test_profiler_timer_only():
    from paddle_tpu.profiler import Profiler

    p = Profiler(timer_only=True)
    p.start()
    for _ in range(2):
        p.step()
    # stop() records the final in-flight step (work since the last
    # step() call would otherwise vanish from summary())
    p.stop()
    assert "steps: 3" in p.summary()
    p.stop()  # idempotent: no double-record
    assert "steps: 3" in p.summary()


def test_export_chrome_tracing_repoints_before_start(tmp_path):
    from paddle_tpu.profiler import Profiler, export_chrome_tracing

    target = str(tmp_path / "chrome_out")
    cb = export_chrome_tracing(target)
    p = Profiler(log_dir=str(tmp_path / "default"), timer_only=True,
                 on_trace_ready=cb)
    # the export dir must be in effect BEFORE any start_trace, not
    # swapped in by the callback after the trace was already written
    assert p.log_dir == target
    p.start()
    p.step()
    p.stop()
    assert p.log_dir == target


def test_metrics():
    acc = metric.Accuracy(topk=(1, 2))
    pred = np.array([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]])
    label = np.array([1, 2])
    acc.update(pred, label)
    top1, top2 = acc.accumulate()
    assert top1 == 0.5
    assert top2 == 0.5
    p = metric.Precision()
    p.update(np.array([0.9, 0.9, 0.1]), np.array([1, 0, 1]))
    assert p.accumulate() == 0.5
    r = metric.Recall()
    r.update(np.array([0.9, 0.9, 0.1]), np.array([1, 0, 1]))
    assert r.accumulate() == 0.5
    auc = metric.Auc()
    auc.update(np.array([0.9, 0.8, 0.2, 0.1]), np.array([1, 1, 0, 0]))
    assert auc.accumulate() > 0.9


def test_hapi_model_fit_evaluate_predict(tmp_path):
    pt.seed(0)
    x = np.random.default_rng(0).standard_normal((64, 4)).astype(np.float32)
    w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    y = (x @ w).astype(np.float32)
    ds = io.TensorDataset(x, y)
    net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 1))
    model = Model(net)
    model.prepare(
        optimizer=opt.AdamW(learning_rate=1e-2, multi_precision=False),
        loss=lambda out, label: ((out - label) ** 2).mean(),
    )
    model.fit(ds, batch_size=16, epochs=25, verbose=0)
    logs = model.evaluate(ds, batch_size=16, verbose=0)
    assert logs["loss"] < 0.5
    preds = model.predict(ds, batch_size=16)
    assert preds.shape == (64, 1)
    model.save(str(tmp_path / "m"))
    model2 = Model(
        nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 1))
    )
    model2.prepare(loss=lambda o, l: ((o - l) ** 2).mean())
    model2.load(str(tmp_path / "m"))
    logs2 = model2.evaluate(ds, batch_size=16, verbose=0)
    np.testing.assert_allclose(logs2["loss"], logs["loss"], rtol=1e-4)


def test_launch_cli_single_node(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os
        print("rank", os.environ["PADDLE_TRAINER_ID"],
              "of", os.environ["PADDLE_TRAINERS_NUM"])
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "log"),
         str(script)],
        cwd="/root/repo", env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    logs = sorted((tmp_path / "log").glob("workerlog.*"))
    assert len(logs) == 2
    content = "".join(p.read_text() for p in logs)
    assert "rank 0 of 2" in content and "rank 1 of 2" in content


@pytest.mark.parametrize("argv,platform,refused", [
    (["--nproc_per_node", "2"], "tpu", "one process at a time"),
    (["--nproc_per_node", "1"], "tpu", None),
    (["--nproc_per_node", "2"], "cpu", None),
    (["--devices", "0,1"], "cpu", "no TPU reads"),
])
def test_launch_one_process_per_tpu_host(argv, platform, refused,
                                         monkeypatch):
    """A chip belongs to one process: on a TPU host a second worker per
    node is refused in words (CPU/debug meshes keep nproc > 1), and the
    GPU-visibility list is refused everywhere instead of being exported
    as an env var nothing reads."""
    from paddle_tpu.distributed.launch import main as launch_main

    monkeypatch.setattr(launch_main, "host_platform", lambda: platform)
    args = launch_main.parse_args(argv + ["train.py"])
    if refused is None:
        launch_main.check_one_process_per_tpu_host(args)
    else:
        with pytest.raises(SystemExit, match=refused):
            launch_main.check_one_process_per_tpu_host(args)


def test_launch_host_platform_creates_no_backend(monkeypatch):
    """The launcher must learn the platform without starting JAX's
    backend, or it would hold the chip its worker needs."""
    from jax._src import xla_bridge

    from paddle_tpu.distributed.launch import main as launch_main

    before = set(xla_bridge._backends)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch_main.host_platform() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert launch_main.host_platform() in ("cpu", "tpu")
    assert set(xla_bridge._backends) == before


def test_launch_cli_elastic_restart(tmp_path):
    # worker fails on first run (marker file absent), succeeds on restart
    script = tmp_path / "flaky.py"
    marker = tmp_path / "marker"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        m = {str(repr(str(marker)))}
        if not os.path.exists(m):
            open(m, "w").close()
            sys.exit(1)
        print("recovered")
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--elastic", "--max_restarts", "2",
         "--poll_interval", "0.2", "--log_dir", str(tmp_path / "log"),
         str(script)],
        cwd="/root/repo", env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "elastic restart" in r.stdout
    log = (tmp_path / "log" / "workerlog.0").read_text()
    assert "recovered" in log


def test_early_stopping():
    es = EarlyStopping(monitor="loss", patience=1)

    class FakeModel:
        stop_training = False

    es.set_model(FakeModel())
    es.on_eval_end({"loss": 1.0})
    es.on_eval_end({"loss": 0.9})
    es.on_eval_end({"loss": 0.95})
    assert not es.model.stop_training
    es.on_eval_end({"loss": 0.96})
    assert es.model.stop_training


def test_xplane_device_op_summary(tmp_path):
    """Per-op device-time table from a (synthesized, TPU-shaped) chrome
    trace: aggregation, percentages, category rollup."""
    import gzip
    import json

    from paddle_tpu.profiler import xplane

    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 2, "tid": 20, "name": "thread_name",
         "args": {"name": "python"}},
        # device ops (dur in us)
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.dot.1",
         "ts": 0, "dur": 3000.0},
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.dot.1",
         "ts": 4000, "dur": 1000.0},
        {"ph": "X", "pid": 1, "tid": 10, "name": "all-reduce.2",
         "ts": 8000, "dur": 2000.0},
        {"ph": "X", "pid": 1, "tid": 10, "name": "copy.3",
         "ts": 11000, "dur": 500.0},
        # host noise that must NOT be counted
        {"ph": "X", "pid": 2, "tid": 20, "name": "PjitFunction",
         "ts": 0, "dur": 99999.0},
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    s = xplane.device_op_summary(str(tmp_path))
    assert s is not None and s.plane == "/device:TPU:0"
    rows = {r.name: r for r in s.rows}
    assert rows["fusion.dot.1"].total_ms == 4.0
    assert rows["fusion.dot.1"].count == 2
    assert rows["fusion.dot.1"].category == "matmul/conv"
    assert rows["all-reduce.2"].category == "collective"
    assert rows["copy.3"].category == "copy/layout"
    assert s.total_ms == 6.5
    cats = s.by_category()
    assert cats["matmul/conv"] == 4.0 and cats["collective"] == 2.0
    text = xplane.format_summary(s)
    assert "fusion.dot.1" in text and "category rollup" in text
    # rows sorted by total time
    assert s.rows[0].name == "fusion.dot.1"


def test_xplane_hlo_category_attribution(tmp_path):
    """The trace's ``hlo_category`` arg wins over name heuristics
    (fused GEMMs named "bitcast_add_fusion" ARE matmuls; Pallas kernels
    are custom-calls), and while/cond container events — which duplicate
    the body ops they wrap — are excluded from the totals."""
    import gzip
    import json

    from paddle_tpu.profiler import xplane

    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_01"
    run.mkdir(parents=True)
    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        # fused GEMM with a copy-looking name: hlo_category must win
        {"ph": "X", "pid": 1, "tid": 10, "name": "bitcast_add_fusion.2",
         "ts": 0, "dur": 1000.0,
         "args": {"hlo_category": "convolution fusion"}},
        # pallas flash attention
        {"ph": "X", "pid": 1, "tid": 10, "name": "jvp__.7",
         "ts": 2000, "dur": 2000.0,
         "args": {"hlo_category": "custom-call"}},
        # scan wrapper duplicating its body — excluded
        {"ph": "X", "pid": 1, "tid": 10, "name": "while.9",
         "ts": 0, "dur": 3000.0, "args": {"hlo_category": "while"}},
        # an XLA category with no bucket surfaces as-is
        {"ph": "X", "pid": 1, "tid": 10, "name": "rsqrt.4",
         "ts": 5000, "dur": 500.0,
         "args": {"hlo_category": "non-fusion elementwise"}},
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": ev}, f)

    s = xplane.device_op_summary(str(tmp_path))
    rows = {r.name: r for r in s.rows}
    assert "while.9" not in rows
    assert rows["bitcast_add_fusion.2"].category == "matmul/conv"
    assert rows["jvp__.7"].category == "custom-call (pallas)"
    assert rows["rsqrt.4"].category == "non-fusion elementwise"
    assert s.total_ms == 3.5


def test_profiler_summary_with_real_trace(tmp_path):
    """End-to-end on the CPU backend: trace capture + summary must not
    crash and must state that the CPU trace has no device op events."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import Profiler

    prof = Profiler(log_dir=str(tmp_path / "prof"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    with prof:
        for _ in range(2):
            f(x).block_until_ready()
            prof.step()
    text = prof.summary()
    assert "step time summary" in text
    assert ("no device op events" in text) or ("device op summary" in text)


def test_xplane_long_tail_categories():
    """The round-4 capture left 16.2% of device time as one opaque
    'other' bucket; fusion-name heuristics must attribute the tail."""
    from paddle_tpu.profiler.xplane import categorize

    assert categorize("loop_add_fusion.3") == "elementwise"
    assert categorize("wrapped_convert") == "elementwise"
    assert categorize("fused_reduce.1") == "reduce"
    assert categorize("scatter.42") == "scatter/gather/slice"
    assert categorize("dynamic-update-slice.7") == "scatter/gather/slice"
    assert categorize("rng_bit_generator") == "rng"
    # hlo_category still wins over name heuristics
    assert categorize("loop_add_fusion", "convolution fusion") \
        == "matmul/conv"
    # truly unknown stays honest
    assert categorize("fusion.99") == "other"


def test_xplane_long_name_attribution():
    """Anonymous fusion.N events carry the HLO text in long_name; the
    round-5 headline's 12.9% 'other' decoded into AdamW master updates
    and the embedding-grad scatter this way."""
    from paddle_tpu.profiler.xplane import categorize

    adamw = ("%fusion.23 = (f32[32000,3072]{1,0}, f32[32000,3072]{1,0}) "
             "fusion(f32[32000,3072]{1,0} "
             "%opt_state__master____model_embed_tokens_weight__.1, "
             "f32[] %sub.427), kind=kLoop, calls=%fused_computation.9")
    assert categorize("fusion.23", "loop fusion", adamw) \
        == "optimizer update"
    scatter = ("%fusion.2 = bf16[32000,3072]{1,0} fusion(s32[8192]{0} "
               "%gte, bf16[8192,3072]{1,0} %b), kind=kCustom, "
               "calls=%scatter_computation")
    assert categorize("fusion.2", "custom fusion", scatter) \
        == "scatter/gather/slice"
    # an elementwise fusion CONSUMING an all-gather output (TP trace)
    # must not be booked as scatter/gather
    tp = ("%fusion.7 = bf16[4,2048,3072]{2,1,0} fusion(bf16[...] "
          "%all-gather.5, bf16[...] %model_embed_tokens_weight), "
          "kind=kLoop, calls=%fused_computation.3")
    assert categorize("fusion.7", "loop fusion", tp) == "other"
    # ...nor a fusion merely fed by a standalone %gather.12 output
    fed = ("%fusion.8 = bf16[4,2048]{1,0} fusion(bf16[8,2048]{1,0} "
           "%gather.12, bf16[4,2048]{1,0} %y), kind=kLoop, "
           "calls=%fused_computation.4")
    assert categorize("fusion.8", "loop fusion", fed) == "other"
    # a NAMED op never defers to long_name (its own tokens win)
    assert categorize("loop_add_fusion.3", "", adamw) == "elementwise"
    # anonymous fusion with uninformative long_name stays honest
    assert categorize("fusion.99", "loop fusion",
                      "%fusion.99 = f32[8,8] fusion(f32[8,8] %x)") \
        == "other"
