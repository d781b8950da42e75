"""Tests of the readers of the program's own spans and phase scopes
(chipbench/readers/program_spans.py and the three readers on it), on
the CPU: the wire-format decoder against ``jax.profiler.ProfileData``
on a real trace, and the reductions on hand-made intervals.
"""

import glob
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.readers import (idle_under_span, program_spans,
                               scope_device_ms, span_stat)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _metric(name: str) -> dict:
    with open(os.path.join(ROOT, "chipbench", "metrics",
                           name + ".json")) as f:
        return json.load(f)


NEW_TRAIN = ["optimizer_device_ms.train", "head_device_ms.train",
             "mlp_device_ms.train", "attention_device_ms.train",
             "unscoped_device_ms.train", "telemetry_idle_ms.train",
             "idle_attributed_share.train"]
NEW_SERVE = ["idle_attributed_share.serve", "engine_host_ms_per_tick.serve",
             "queue_wait_ms.serve", "kv_pages_used_share.serve",
             "tick_slots_active.serve"]


def test_the_decoder_reads_what_profiledata_reads(tmp_path, monkeypatch):
    """A real trace: the program's spans with their arguments (int,
    float, str, and those set at the span's end) and their thread come
    out of the wire format as ``ProfileData`` gives them, cut to the
    window span."""
    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((8, 8))).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    A = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with A("pt.engine.tick"):  # before the window: cut away
        pass
    with A("chipbench.window"):
        with jax.profiler.StepTraceAnnotation(
                "pt.train.step", step_num=7, tokens=4096):
            with A("pt.engine.dispatch", program="decode_chunk") as span:
                f(jnp.ones((8, 8))).block_until_ready()
                span.set_metadata(admitted=3, queue_wait_ms_sum=12.25)
        with A("other.span"):
            pass
    jax.profiler.stop_trace()
    trace = program_spans.load(str(tmp_path))
    assert program_spans.load(str(tmp_path)) is trace  # parsed once
    assert [s[2] for s in trace.spans] == ["pt.train.step",
                                          "pt.engine.dispatch"]
    step, dispatch = trace.spans
    assert step[3]["step_num"] == 7 and step[3]["tokens"] == 4096
    assert dispatch[3] == {"program": "decode_chunk", "admitted": 3,
                           "queue_wait_ms_sum": 12.25}
    assert step[0] <= dispatch[0] and dispatch[1] <= step[1]
    assert step[4] == dispatch[4]  # one thread
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    want = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("pt.train.step", "chipbench.window"):
                    want[ev.name] = (int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     line.name)
    assert abs(step[0] - want["pt.train.step"][0]) <= 1
    assert abs(step[1] - want["pt.train.step"][1]) <= 1
    assert step[4] == want["pt.train.step"][2]
    assert abs(trace.window[0] - want["chipbench.window"][0]) <= 1
    assert trace.devices == {}  # the CPU has no device plane
    monkeypatch.setattr(program_spans, "load", lambda: trace)
    for name in NEW_TRAIN:  # and a reader finds nothing to read
        spec = _metric(name)
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        assert reader.read(None, spec["args"], {}, {}) is None


# a step of 100 ns on the device, twice; op_names as the chip gives them
_STEP = [
    (0, 2, "%copy.1 = copy(...)", ""),
    (2, 12, "%fusion.1 = fusion(...)", "jit(step_fn)/jvp(embed)/gather:"),
    (12, 20, "%fusion.2 = fusion(...)", "jit(step_fn)/jvp(attn_in)/dot_general:"),
    (20, 26, "%jvp__.3 = (bf16[1]) custom-call(...), "
             "custom_call_target=\"tpu_custom_call\"",
     "jit(step_fn)/jvp()/pallas_call:"),
    (26, 28, "%fusion.3 = fusion(...)", "jit(step_fn)/jvp()/broadcast_in_dim:"),
    (28, 32, "%fusion.4 = fusion(...)", "jit(step_fn)/jvp(attn_out)/add:"),
    (32, 50, "%fusion.5 = fusion(...)", "jit(step_fn)/jvp(mlp)/dot_general:"),
    (50, 60, "%fusion.6 = fusion(...)",
     "jit(step_fn)/jvp(head_loss)/jit(log_softmax)/sub:"),
    # the backward: the scope sits inside the transforms
    (60, 70, "%fusion.7 = fusion(...)",
     "jit(step_fn)/transpose(jvp(mlp))/dot_general:"),
    (70, 76, "%transpose_jvp___.4 = (bf16[1]) custom-call(...), "
             "custom_call_target=\"tpu_custom_call\"",
     "jit(step_fn)/transpose(jvp())/pallas_call:"),
    # a parameter's own name holds "mlp" between dots: no scope
    (76, 78, "%copy.9 = copy(...)",
     "opt_state['slots']['model.layers.0.mlp.up_proj.weight']"),
    (78, 80, "%fusion.8 = fusion(...)", "jit(step_fn)/grad_norm/reduce_sum:"),
    # a while holds its body: 80-100 is the optimizer's, once
    (80, 100, "%while.1 = while(...)", "jit(step_fn)/optimizer/while:"),
    (82, 90, "%fusion.9 = fusion(...)", "jit(step_fn)/optimizer/mul:"),
    (90, 98, "%fusion.10 = fusion(...)",
     "jit(step_fn)/optimizer/convert_element_type:"),
]


def _two_steps(op_names=True) -> program_spans.ProgramTrace:
    ops = [(s + at, e + at, n, p if op_names else "")
           for at in (0, 150) for s, e, n, p in _STEP]
    mods = [(0, 100, "jit_step_fn(1)"), (100, 104, "jit__unstack(2)"),
            (150, 250, "jit_step_fn(1)")]
    ops.append((100, 104, "%slice.1 = slice(...)", "jit(_unstack)/squeeze:"))
    return program_spans.ProgramTrace(
        {"/device:TPU:0": {"ops": ops, "modules": mods}}, [], (0, 300))


def test_own_time_counts_an_instant_once():
    ops = [(0, 10, "a", ""), (20, 40, "while", ""), (25, 30, "b", ""),
           (30, 38, "c", ""), (32, 34, "d", ""), (50, 60, "e", ""),
           (55, 70, "outlasts its holder", "")]
    own = program_spans.self_ns(ops)
    assert own == [10, 7, 5, 6, 2, 5, 5]
    # the own times add up to the union of the intervals the way the
    # benchmark's busy time counts them (the last one cut to its holder)
    assert sum(own) == 10 + 20 + 10


@pytest.mark.parametrize("metric,want", [
    ("optimizer_device_ms.train", 20), ("head_device_ms.train", 20),
    ("mlp_device_ms.train", 28), ("attention_device_ms.train", 24),
    ("unscoped_device_ms.train", 8)])
def test_a_phase_is_the_own_time_of_its_scopes_per_run(
        metric, want, monkeypatch):
    """Forward and backward under one scope, a fusion in one phase
    only, the flash kernels by their roofline metrics' own patterns,
    the while once, and what no phase claims under unscoped."""
    monkeypatch.setattr(program_spans, "load", lambda: _two_steps())
    spec = _metric(metric)
    assert scope_device_ms.read(None, spec["args"], {}, {}) \
        == pytest.approx(want / 1e6)


def test_the_phases_add_up_to_the_module_and_need_a_scope(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: _two_steps())
    total = sum(scope_device_ms.read(None, _metric(m)["args"], {}, {})
                for m in NEW_TRAIN[:5])
    assert total == pytest.approx(100 / 1e6)  # the step's 100 ns, per run
    # a program without scopes (the parent): nothing to read, no number
    monkeypatch.setattr(program_spans, "load",
                        lambda: _two_steps(op_names=False))
    for m in NEW_TRAIN[:5]:
        assert scope_device_ms.read(None, _metric(m)["args"], {}, {}) is None
    table = dict(program_spans.phase_table(_two_steps()))
    assert table["optimizer"] == pytest.approx(20 / 1e6)
    assert table["grad_norm"] == pytest.approx(2 / 1e6)
    assert table["head_loss"] == pytest.approx(10 / 1e6)
    assert table["(no scope: pallas kernel)"] == pytest.approx(12 / 1e6)


def test_scopes_of_a_path():
    f = program_spans.scopes_of
    assert f("jit(step_fn)/transpose(jvp(mlp))/dot_general:") == "mlp"
    assert f("jit(step_fn)/jvp(head_loss)/jit(log_softmax)/sub:") \
        == "head_loss"
    assert f("jit(step_fn)/optimizer/jit(clip)/mul") == "optimizer"
    assert f("jit(step_fn)/jvp()/pallas_call:") == ""
    assert f("jit(step_fn)/transpose(jvp(jit(_take)))/scatter-add:") == ""
    assert f("") == ""


# host spans over 0-1000 ns on one thread, and one on another thread
_SPANS = [
    (100, 400, "pt.train.step", {"step_num": 1}, "main"),
    (110, 150, "pt.train.shard_batch", {}, "main"),
    (150, 300, "pt.train.dispatch", {}, "main"),
    (300, 380, "pt.train.sample_fetch", {"interval_steps": 10}, "main"),
    (380, 395, "pt.train.sync_to_model", {}, "main"),
    (500, 700, "pt.train.step", {"step_num": 2}, "main"),
    (510, 690, "pt.train.dispatch", {}, "main"),
]


def test_idle_goes_to_the_innermost_span_at_each_instant():
    spans = [s[:3] for s in _SPANS]
    # idle 90-120 (before and in the step, then shard_batch), 340-390
    # (the fetch, then sync_to_model), 450-520 (outside, step, dispatch)
    gaps = [(90, 120), (340, 390), (450, 520)]
    got = program_spans.idle_by_span(gaps, spans)
    assert got == {None: 10 + 50, "pt.train.step": 10 + 10,
                   "pt.train.shard_batch": 10,
                   "pt.train.sample_fetch": 40,
                   "pt.train.sync_to_model": 10,
                   "pt.train.dispatch": 10}
    assert sum(got.values()) == sum(e - s for s, e in gaps)
    under, total = idle_under_span.idle_ns(
        gaps, spans, r"^pt\.train\.sample_fetch$")
    assert (under, total) == (40, 150)
    assert idle_under_span.idle_ns(gaps, spans, r"^pt\.")[0] == 90


@pytest.mark.parametrize("metric,want", [
    ("telemetry_idle_ms.train", 40 / 2 / 1e6),  # ms per pt.train.step
    ("idle_attributed_share.train", 100 * 90 / 150),
    ("idle_attributed_share.serve", 100 * 90 / 150)])
def test_idle_readers_on_a_hand_made_trace(metric, want, monkeypatch):
    # the device is busy except in the three gaps above
    ops = [(0, 90, "a", ""), (120, 340, "b", ""), (390, 450, "c", ""),
           (520, 1000, "d", "")]
    trace = program_spans.ProgramTrace(
        {"/device:TPU:0": {"ops": ops, "modules": []}}, _SPANS, (0, 1000))
    monkeypatch.setattr(program_spans, "load", lambda: trace)
    assert idle_under_span.read(None, _metric(metric)["args"], {}, {}) \
        == pytest.approx(want)
    # no program span in the trace (the parent): nothing to read
    bare = program_spans.ProgramTrace(
        {"/device:TPU:0": {"ops": ops, "modules": []}}, [], (0, 1000))
    monkeypatch.setattr(program_spans, "load", lambda: bare)
    assert idle_under_span.read(None, _metric(metric)["args"], {}, {}) is None


_TICKS = [
    (0, 100, "pt.engine.tick", {"active": 4, "queued": 1, "pages_used": 10,
                                "pages_total": 100}, "driver"),
    (10, 20, "pt.engine.dispatch", {"program": "decode_chunk"}, "driver"),
    (20, 80, "pt.engine.sync", {}, "driver"),
    (80, 95, "pt.engine.emit", {"tokens": 32}, "driver"),
    (95, 99, "pt.engine.admit", {"admitted": 2,
                                 "queue_wait_ms_sum": 30.0}, "driver"),
    (100, 400, "pt.engine.tick", {"active": 8, "queued": 0,
                                  "pages_used": 30, "pages_total": 100},
     "driver"),
    (110, 390, "pt.engine.sync", {}, "driver"),
    (120, 380, "pt.engine.sync", {}, "another thread"),  # not a child
    (392, 398, "pt.engine.admit", {}, "driver"),  # dispatch only: no count
    (398, 399, "pt.engine.admit", {"admitted": 1,
                                   "queue_wait_ms_sum": 60.0}, "driver"),
]


@pytest.mark.parametrize("metric,want", [
    # (100 - 60) and (300 - 280) ns of host work, mean, in ms
    ("engine_host_ms_per_tick.serve", (40 + 20) / 2 / 1e6),
    ("queue_wait_ms.serve", 90.0 / 3),
    # each tick weighs its duration: 100 and 300
    ("kv_pages_used_share.serve", 100 * (0.10 * 100 + 0.30 * 300) / 400),
    ("tick_slots_active.serve", (4 * 100 + 8 * 300) / 400)])
def test_span_stat_on_hand_made_ticks(metric, want):
    args = _metric(metric)["args"]
    assert span_stat.stat(_TICKS, args) == pytest.approx(want)
    assert span_stat.stat(_SPANS, args) is None  # no engine span: nothing


def test_new_metric_files_name_what_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_TRAIN + NEW_SERVE:
        spec = _metric(name)
        assert spec["name"] == name
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "readers", spec["reader"] + ".py"))
        for other in spec["args"].get("kernels_of", []) + \
                spec["args"].get("unclaimed_by", []):
            assert "args" in _metric(other), other
        assert spec["source"] in ("device_trace", "program_span",
                                  "program_counter")
    # the train ones are entries of BENCHMARK.json, at the end of the
    # list and in the train cell only; the serve ones are files only
    assert [m["name"] for m in bench["per_layer"]][-7:] == NEW_TRAIN
    for name in NEW_TRAIN:
        assert listed[name]["workloads"] == ["mistral7b-train-2k"]
        assert listed[name]["moves"] == "train_tokens_per_s"
    assert not set(NEW_SERVE) & set(listed)
