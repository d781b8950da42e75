"""The program's own spans and phase scopes (observability/spans.py).

A real ``jax.profiler`` trace of a tiny ``TrainStep`` and of a tiny
engine holds the ``pt.*`` spans, nested on one thread, with their
arguments; the tiny step's program carries each scope in some
``op_name`` and is, metadata apart, the program a build without scopes
compiles; the persistent compile cache tells two builds apart that
differ only in a scope (two processes, one cache directory); the span
table covers every ``"pt.`` literal in the package.
"""

import contextlib
import gc
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distributed as dist, observability as obs
from paddle_tpu import optimizer as opt
from paddle_tpu.models import (Lfm2MoeConfig, Lfm2MoeForCausalLM,
                               LlamaConfig, LlamaForCausalLM,
                               NemotronHConfig, NemotronHForCausalLM,
                               Phi4FlashConfig, Phi4FlashForCausalLM)
from paddle_tpu.observability.spans import SCOPES, SPANS
from paddle_tpu.trainer import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_on():
    """conftest runs the suite with telemetry off; the sampled fetch
    and the grad-norm output exist only with it on."""
    prev = pt.flags.flag("telemetry")
    pt.flags.set_flags({"FLAGS_telemetry": True})
    yield
    pt.flags.set_flags({"FLAGS_telemetry": prev})


def _tiny_model(family="llama"):
    if family == "llama":
        return LlamaForCausalLM(LlamaConfig.tiny(use_flash_attention=False))
    if family == "phi4flash":  # all eight published layers: every kind
        return Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    if family == "lfm2":  # both operators, a dense and three sparse layers
        return Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
            num_dense_layers=1, use_flash_attention=False))
    return NemotronHForCausalLM(NemotronHConfig.tiny(
        use_flash_attention=False))


# the phases each family's step has: a dense decoder has no state-space,
# convolution or expert blocks, the Mamba-2 hybrid stack has no MLP, the
# Mamba-1 hybrid (S6 scans, gated memory units) has neither of the
# other's recurrent or expert phases, and the short-convolution stack
# has experts (none shared) and a dense MLP but no recurrence
_OWN = {"nemotron_h": ("ssm", "moe"), "phi4flash": ("s6", "gmu"),
        "lfm2": ("sconv", "moe")}


def _scopes_without(*families):
    gone = sum((_OWN[f] for f in families), ())
    return {s for s, (phase, _) in SCOPES.items() if phase not in gone}


FAMILY_SCOPES = {
    "llama": _scopes_without("nemotron_h", "phi4flash", "lfm2"),
    "nemotron_h": _scopes_without("phi4flash")
    - {"mlp", "sconv_in", "sconv_mix", "sconv_out"},
    "phi4flash": _scopes_without("nemotron_h", "lfm2"),
    "lfm2": {s for s, (phase, _) in SCOPES.items()
             if phase not in ("ssm", "s6", "gmu")} - {"moe_shared"},
}


def _tiny_step(telemetry=True, family="llama", **kw):
    pt.seed(0)
    model = _tiny_model(family)
    model.to(dtype="bfloat16")  # float32 masters beside bf16 parameters
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    optimizer = opt.AdamW(1e-3, multi_precision=True,
                          grad_clip=opt.ClipGradByGlobalNorm(1.0))
    return TrainStep(model, optimizer, mesh, telemetry=telemetry, **kw)


def _traced(tmp_path, body) -> list:
    """Run ``body`` under a profiler trace; the ``pt.*`` host events as
    (start, end, name, arguments, thread), in order of start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("pt."):
                    rows.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name, dict(ev.stats), line.name))
    return sorted(rows, key=lambda r: (r[0], -r[1]))


def _children(rows, parent):
    return [r for r in rows if r is not parent and r[4] == parent[4]
            and r[0] >= parent[0] and r[1] <= parent[1]]


def test_trainer_spans_nest_and_fetch_only_on_the_sampled_step(tmp_path):
    ts = _tiny_step(telemetry=obs.TrainTelemetry(
        sample_every=3, dump_dir=str(tmp_path / "dumps")))
    ids = np.random.default_rng(0).integers(0, 256, (2, 16), np.int32)
    batch = {"input_ids": ids, "labels": ids}
    ts.run(batch)  # step 1 compiles, outside the trace
    rows = _traced(tmp_path / "trace",
                   lambda: [ts.run(batch) for _ in range(3)])
    steps = [r for r in rows if r[2] == "pt.train.step"]
    assert [s[3]["step_num"] for s in steps] == [2, 3, 4]
    assert all(s[3]["tokens"] == 32 for s in steps)
    assert len({s[4] for s in steps}) == 1  # one thread
    for step in steps:
        # a collector's pause nests wherever it struck
        kids = [k[2] for k in _children(rows, step) if k[2] != "pt.gc"]
        want = ["pt.train.shard_batch", "pt.train.dispatch"]
        if step[3]["step_num"] == 3:  # the sampled one
            want.append("pt.train.sample_fetch")
        assert kids == want + ["pt.train.sync_to_model"], kids
    fetch, = [r for r in rows if r[2] == "pt.train.sample_fetch"]
    assert fetch[3]["interval_steps"] == 3
    assert {r[2] for r in rows} <= set(SPANS)


def _profile_start_ns(trace_dir) -> int:
    """The wall-clock ns the trace's host times count from: the plane
    ``Task Environment`` holds it as ``profile_start_time``."""
    path, = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    env, = [p for p in data.planes if p.name == "Task Environment"]
    return dict(env.stats)["profile_start_time"]


def _tiny_batch(seq=16):
    ids = np.random.default_rng(0).integers(0, 256, (2, seq), np.int32)
    return {"input_ids": ids, "labels": ids}


def test_a_collection_is_a_pt_gc_span_that_the_sampled_fetch_counts(
        tmp_path):
    ts = _tiny_step(telemetry=obs.TrainTelemetry(
        sample_every=2, dump_dir=str(tmp_path / "dumps")))
    batch = _tiny_batch()
    ts.run(batch)
    ts.run(batch)  # compiled; step 2's sample opens a fresh interval

    def loop():
        ts.run(batch)
        gc.collect()  # a full collection between two steps ...
        ts.run(batch)  # ... which step 4's sampled fetch reports

    rows = _traced(tmp_path / "trace", loop)
    pauses = [r for r in rows if r[2] == "pt.gc"]
    full = [r for r in pauses if r[3]["generation"] == 2]
    assert full and all(isinstance(r[3]["collected"], int) for r in full)
    step_thread = {r[4] for r in rows if r[2] == "pt.train.step"}
    assert {r[4] for r in full} == step_thread  # the loop's own thread
    fetch, = [r for r in rows if r[2] == "pt.train.sample_fetch"]
    assert fetch[3]["interval_steps"] == 2
    # the interval's pauses cover every pause the trace holds in it
    assert fetch[3]["gc_count"] >= len(pauses)
    assert fetch[3]["gc_ms"] >= sum(r[1] - r[0] for r in pauses) / 1e6
    assert fetch[3]["compiles"] == 0 and fetch[3]["compile_ms"] == 0
    rec = ts.telemetry.recorder.records()[-1]
    assert rec["step"] == 4 and rec["gc_ms"] > 0


def test_a_new_shape_compiles_on_that_steps_dispatch_alone(tmp_path):
    ts = _tiny_step(telemetry=obs.TrainTelemetry(
        sample_every=100, dump_dir=str(tmp_path / "dumps")))
    short, longer = _tiny_batch(16), _tiny_batch(32)
    ts.run(short)  # compiles, outside the trace
    rows = _traced(tmp_path / "trace", lambda: [
        ts.run(b) for b in (short, longer, longer, short)])
    dispatch = [r[3] for r in rows if r[2] == "pt.train.dispatch"]
    assert len(dispatch) == 4
    assert dispatch[1]["compiles"] >= 1 and dispatch[1]["compile_ms"] > 0
    for steady in (dispatch[0], dispatch[2], dispatch[3]):
        assert "compiles" not in steady and "compile_ms" not in steady
    recs = ts.telemetry.recorder.records()[-4:]
    assert [r["compile_ms"] > 0 for r in recs] == [False, True, False,
                                                   False]


def test_a_records_t_ns_lies_inside_its_own_step_span(tmp_path):
    ts = _tiny_step(telemetry=obs.TrainTelemetry(
        sample_every=2, dump_dir=str(tmp_path / "dumps")))
    batch = _tiny_batch()
    ts.run(batch)
    rows = _traced(tmp_path / "trace",
                   lambda: [ts.run(batch) for _ in range(4)])
    start = _profile_start_ns(tmp_path / "trace")
    steps = {r[3]["step_num"]: r for r in rows if r[2] == "pt.train.step"}
    recs = ts.telemetry.recorder.records()[-4:]
    assert sorted(steps) == [r["step"] for r in recs] == [2, 3, 4, 5]
    for rec in recs:
        s, e = steps[rec["step"]][:2]
        assert s <= rec["t_ns"] - start <= e, (rec, s, e)
        assert 0 <= rec["shard_ms"] + rec["dispatch_ms"] <= rec["wall_ms"]


def test_engine_tick_holds_dispatch_sync_emit_with_integer_arguments(
        tmp_path):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serving_utils

    model, cfg = serving_utils.tiny_model()
    eng = serving_utils.ContinuousBatchingEngine(
        model, serving_utils.tiny_ecfg(paged=True))
    rng = np.random.default_rng(0)

    def drive():
        for _ in range(3):  # two slots: the third request queues
            eng.add_request(rng.integers(1, cfg.vocab_size, 12),
                            max_new_tokens=5)
        serving_utils.drain(eng, lambda: eng.step_chunk(4))

    drive()  # compiles, outside the trace
    rows = _traced(tmp_path, drive)
    ticks = [r for r in rows if r[2] == "pt.engine.tick"]
    decoding = [t for t in ticks if any(
        k[3].get("program") == "decode_chunk"
        for k in _children(rows, t))]
    assert decoding
    for tick in ticks:
        assert {"active", "queued", "pages_used", "pages_total"} \
            <= set(tick[3])
        assert all(isinstance(tick[3][k], int) for k in
                   ("active", "queued", "pages_used", "pages_total"))
        assert 0 <= tick[3]["pages_used"] <= tick[3]["pages_total"]
    # the arguments are what the tick found: the first one finds the
    # three requests queued and no slot active yet
    assert (ticks[0][3]["active"], ticks[0][3]["queued"]) == (0, 3)
    for tick in decoding:
        names = [k[2] for k in _children(rows, tick)]
        # a decode chunk, its wait, the per-slot loop: in that order
        # (a first token's read, in an admission, is a sync too)
        chunk = next(i for i, k in enumerate(_children(rows, tick))
                     if k[3].get("program") == "decode_chunk")
        sync = names.index("pt.engine.sync", chunk)
        emit = names.index("pt.engine.emit", sync)
        tokens = _children(rows, tick)[emit][3]["tokens"]
        assert isinstance(tokens, int) and tokens > 0
    programs = {r[3]["program"] for r in rows
                if r[2] == "pt.engine.dispatch"}
    assert programs == {"decode_chunk", "prefill_chunk"}
    admitted = [r[3] for r in rows if r[2] == "pt.engine.admit"
                and "admitted" in r[3]]
    assert sum(a["admitted"] for a in admitted) == 3
    assert sum(a["queue_wait_ms_sum"] for a in admitted) > 0
    assert sum(r[3]["tokens"] for r in rows
               if r[2] == "pt.engine.emit") == 3 * 5 - 3
    assert {r[2] for r in rows} <= set(SPANS)


# ---- device phases: metadata, and nothing but metadata
def _lowered(scoped: bool, monkeypatch, family="llama"):
    if not scoped:  # the test's own null context: no switch in the program
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    # a rematerialised function's trace is cached with its scopes
    jax.clear_caches()
    ts = _tiny_step(master_residency="master_only", family=family)
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    low = ts.lower({"input_ids": ids, "labels": ids})
    monkeypatch.undo()
    return low


def _without_metadata(hlo_text: str) -> list:
    """Compiled HLO text less ``metadata={...}`` and the tables of
    files and stack frames that the metadata points into."""
    keep = [ln for ln in hlo_text.splitlines() if not re.match(
        r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames|$)",
        ln)]
    return [re.sub(r",? ?metadata=\{[^}]*\}", "", ln) for ln in keep]


def test_the_families_cover_the_scope_table():
    assert set().union(*FAMILY_SCOPES.values()) == set(SCOPES)


@pytest.mark.parametrize("family,backward", [
    ("llama", ("mlp",)), ("nemotron_h", ("ssm_scan", "moe_experts")),
    ("phi4flash", ("s6_scan", "gmu")),
    ("lfm2", ("sconv_mix", "moe_experts"))],
    ids=["llama", "nemotron_h", "phi4flash", "lfm2"])
def test_every_scope_is_in_some_op_name_and_changes_nothing_else(
        family, backward, monkeypatch):
    scopes = FAMILY_SCOPES[family]
    scoped = _lowered(True, monkeypatch, family)
    plain = _lowered(False, monkeypatch, family)
    # as traced, every scope is on some operation ...
    traced = scoped.as_text(debug_info=True)
    untraced = plain.as_text(debug_info=True)
    for scope in scopes:
        on_a_path = r'loc\("jit\([^"]*[/(]%s[/)][^"]*"' % scope
        assert re.search(on_a_path, traced), scope
        assert not re.search(on_a_path, untraced), scope
    # ... and the program is the same program
    assert scoped.as_text() == plain.as_text()
    compiled = scoped.compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    # XLA merges telemetry's norm with the clip's own: grad_norm may be
    # gone from the compiled program, the others have to be there
    for scope in scopes - {"grad_norm"}:
        assert any(re.search(r"[/(]%s[/)]" % scope, n) for n in names), \
            scope
    # a backward operation carries its scope inside the transforms (the
    # SSD's and the selective scan's backward rules under their mixers'
    # ``jax.checkpoint`` and the experts' hand-written backward too)
    for scope in backward:
        assert any(re.search(r"transpose\(jvp\((.*[/(])?%s[/)]" % scope, n)
                   for n in names), scope
    assert _without_metadata(compiled) == _without_metadata(
        plain.compile().as_text())


_TWO_BUILDS = """
import sys
import jax
import jax.numpy as jnp
import paddle_tpu  # noqa: F401  (keys the cache on metadata too)

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def f(w, x):
    if sys.argv[2] == "scoped":
        with jax.named_scope("optimizer"):
            return w - 0.1 * (x @ w)
    return w - 0.1 * (x @ w)


text = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile(
    ).as_text()
print("HAS_SCOPE" if "optimizer" in text else "NO_SCOPE")
"""


def test_the_compile_cache_does_not_serve_another_builds_names(tmp_path):
    """Hazard: by default the persistent cache's key leaves ``op_name``
    out, so a build that only adds a scope got the older build's
    executable and a profile of it showed no scope. Two processes, one
    cache directory, the plain build first."""
    script = tmp_path / "two_builds.py"
    script.write_text(_TWO_BUILDS)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    said = []
    for build in ("plain", "scoped"):
        out = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "cache"), build],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append(out.stdout.strip().splitlines()[-1])
    assert said == ["NO_SCOPE", "HAS_SCOPE"]
    assert glob.glob(str(tmp_path / "cache" / "jit_f-*"))  # it was used


def test_the_span_table_covers_every_pt_literal_in_the_package():
    used = {}
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("observability", "spans.py")):
            continue
        with open(path) as f:
            for name in re.findall(r'"(pt\.[A-Za-z0-9_.]*)', f.read()):
                used.setdefault(name, path)
    assert used, "no span literal found: the pattern is stale"
    assert set(used) <= set(SPANS), {
        n: p for n, p in used.items() if n not in SPANS}
    assert set(SPANS) <= set(used), set(SPANS) - set(used)
    for name, (layer, covers, args, metrics) in SPANS.items():
        assert layer and covers and metrics, name
        assert isinstance(args, tuple), name
    # every metric a span is for is a file of the benchmark
    for name, row in SPANS.items():
        for metric in row[3]:
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", "metrics", metric + ".json")), metric


def test_the_table_lists_the_host_events_and_their_arguments():
    layer, _, args, metrics = SPANS["pt.gc"]
    assert layer == "trainer host" and args == ("generation", "collected")
    assert "gc_idle_ms.train" in metrics
    assert set(SPANS["pt.train.dispatch"][2]) == {"compiles", "compile_ms"}
    fetch = SPANS["pt.train.sample_fetch"]
    assert {"gc_ms", "gc_count", "compile_ms", "compiles"} <= set(fetch[2])
    assert {"host_gc_ms.train", "compile_ms.train"} <= set(fetch[3])
