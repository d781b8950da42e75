"""Benchmark-suite contract tests.

Round-3 postmortem: the SD-UNet config shipped with an NHWC sample fed
to an NCHW model and crashed on every backend, and the driver-facing
JSON line ballooned past parseability. These tests pin both contracts:
every BASELINE config must execute end-to-end on CPU, and the printed
line must stay small and parseable no matter how much diagnostic bloat
the run accumulates (reference: Paddle's benchmark suite smoke jobs,
test/legacy_test pattern of running each trainer config tiny on CPU).
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CONFIGS = ["moe", "vit", "unet", "mamba", "infer", "serve7b"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_runs_on_cpu(name):
    """Each BASELINE secondary config must run end-to-end (model
    construction, data layout, train/infer step) on the CPU smoke size —
    so benchmark/model input contracts cannot drift silently."""
    from benchmarks.suite import run_config

    r = run_config(name)
    assert r["unit"] not in ("error", "skipped"), r
    assert r["value"] > 0, r
    assert isinstance(r["metric"], str) and r["metric"]
    # every result must be one JSON-serializable dict
    json.dumps(r)


def test_headline_cpu_smoke():
    """The headline llama bench body itself (not via subprocess)."""
    import bench

    r = bench.bench_llama_train()
    assert r["value"] > 0
    assert r["unit"] == "tokens/s/chip"


def _fat_result():
    """A worst-case result dict shaped like round 3's failure: embedded
    tracebacks and duplicated probe diagnostics in every secondary."""
    probe = {"tpu_unavailable": True,
             "attempts": [{"attempt": i, "rc": "timeout",
                           "stderr_tail": "x" * 800} for i in range(2)]}
    sec = {}
    for name in CONFIGS:
        sec[name] = {
            "metric": f"bench_{name}_failed", "value": 0.0,
            "unit": "error", "vs_baseline": 0.0,
            "extra": {"error": "E" * 500, "traceback": "T" * 1500,
                      "tpu_probe": probe},
        }
    return {
        "metric": "llama_train_cpu_smoke_tokens_per_sec",
        "value": 1234.5, "unit": "tokens/s/chip", "vs_baseline": 1.0,
        "extra": {"platform": "cpu", "n_chips": 1, "params": 10 ** 9,
                  "step_ms": 10.0, "loss": 2.5, "tpu_probe": probe,
                  "op_summary": {"top_ops": [{"name": "o" * 60}] * 8},
                  "secondary": sec},
    }


def test_compact_line_contract(tmp_path, monkeypatch):
    """The driver-facing line must stay < 2KB and parseable even when
    every secondary fails with a full traceback; full diagnostics land
    in BENCH_DETAILS.json."""
    import bench

    details = tmp_path / "BENCH_DETAILS.json"
    monkeypatch.setattr(bench, "DETAILS_PATH", str(details))
    line = bench._compact_line(_fat_result())
    assert len(line) < 2048, len(line)
    parsed = json.loads(line)
    assert parsed["metric"] == "llama_train_cpu_smoke_tokens_per_sec"
    assert parsed["value"] == 1234.5
    # secondaries survive compaction with truncated errors
    sec = parsed["extra"]["secondary"]
    assert set(sec) == set(CONFIGS)
    for row in sec.values():
        assert len(row.get("error", "")) <= 120
    # full diagnostics preserved in the side file
    full = json.loads(details.read_text())
    assert full["extra"]["secondary"]["moe"]["extra"]["traceback"] == \
        "T" * 1500


@pytest.mark.parametrize("jax_platforms,exits", [(None, True),
                                                 ("cpu", False)])
def test_child_without_chip_exits_nonzero(jax_platforms, exits,
                                          monkeypatch):
    """A bench process that finds no TPU ends non-zero before it builds
    a model — no probe, no retry, no rerun on the CPU. Only
    ``JAX_PLATFORMS=cpu`` GIVEN BY THE CALLER is the labelled CPU smoke
    (this suite's own lane)."""
    import bench

    if jax_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", jax_platforms)
    if exits:
        with pytest.raises(SystemExit) as e:
            bench._require_chip()
        assert e.value.code not in (0, None)
        assert "no TPU" in str(e.value.code)
    else:
        bench._require_chip()


def test_failed_headline_exits_nonzero_without_a_result_line(
        monkeypatch, capsys):
    """A headline child that raised (no result line, rc != 0) makes the
    parent exit non-zero and print NO JSON line — an exception is never
    turned into a result and exit 0."""
    import bench

    monkeypatch.setattr(sys, "argv", ["bench.py", "--no-secondary"])
    monkeypatch.setattr(
        bench, "_run_one_config",
        lambda name, env, timeout: {
            "metric": f"bench_{name}_failed", "value": 0.0,
            "unit": "error", "vs_baseline": 0.0,
            "extra": {"rc": 1, "stderr": "Traceback ... boom"}})
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_compact_line_headline_error(tmp_path, monkeypatch):
    """A failed headline must carry its own truncated diagnostics on the
    printed line (round-3 regression: only secondaries kept errors)."""
    import bench

    monkeypatch.setattr(bench, "DETAILS_PATH",
                        str(tmp_path / "BENCH_DETAILS.json"))
    r = {"metric": "bench_llama_failed", "value": 0.0, "unit": "error",
         "vs_baseline": 0.0,
         "extra": {"rc": 1, "stderr": "S" * 900,
                   "secondary": {"mamba": {
                       "metric": "bench_mamba_timeout", "value": 0.0,
                       "unit": "error", "extra": {"timeout_s": 420}}}}}
    parsed = json.loads(bench._compact_line(r))
    assert parsed["extra"]["error"] == "S" * 120
    assert parsed["extra"]["secondary"]["mamba"]["error"] == \
        "timeout after 420s"


def test_compact_line_healthy_result(tmp_path, monkeypatch):
    """A green TPU-shaped result keeps its headline scalars."""
    import bench

    monkeypatch.setattr(bench, "DETAILS_PATH",
                        str(tmp_path / "BENCH_DETAILS.json"))
    r = {"metric": "llama876m_train_tokens_per_sec_per_chip",
         "value": 21083.0, "unit": "tokens/s/chip", "vs_baseline": 1.0,
         "extra": {"platform": "tpu", "n_chips": 1, "mfu_est": 0.563,
                   "step_ms": 388.0,
                   "secondary": {"infer": {"metric": "infer_p50_ttft_ms",
                                           "value": 12.0, "unit": "ms",
                                           "vs_baseline": 1.0,
                                           "extra": {"platform": "tpu"}}}}}
    parsed = json.loads(bench._compact_line(r))
    assert parsed["extra"]["mfu_est"] == 0.563
    assert parsed["extra"]["secondary"]["infer"]["value"] == 12.0
    assert "error" not in parsed["extra"]["secondary"]["infer"]


def test_compact_line_carries_audit_verdict(tmp_path, monkeypatch):
    """The serve7b ptaudit verdict rides the ledger line (programs /
    op_counts_ok / violations — compact, never the full report) and
    is shed with the other secondary detail when the line must
    shrink."""
    import bench

    monkeypatch.setattr(bench, "DETAILS_PATH",
                        str(tmp_path / "BENCH_DETAILS.json"))
    r = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
         "extra": {"platform": "tpu", "n_chips": 1, "secondary": {
             "serve7b": {
                 "metric": "serve7b_tokens_per_sec", "value": 100.0,
                 "unit": "tokens/s", "vs_baseline": 1.0,
                 "extra": {"audit": {
                     "programs": 20, "op_counts_ok": True,
                     "violations": 0, "rules": [],
                     "wall_s": 3.2}}}}}}
    row = json.loads(bench._compact_line(r))["extra"]["secondary"][
        "serve7b"]
    # compact triple only — rules/wall stay in BENCH_DETAILS.json
    assert row["audit"] == {"programs": 20, "op_counts_ok": True,
                            "violations": 0}
    monkeypatch.setattr(bench, "MAX_LINE_BYTES", 200)
    shed = json.loads(bench._compact_line(r))
    sec = shed["extra"].get("secondary", {}).get("serve7b", {})
    assert "audit" not in sec


def test_compact_line_carries_flight_scalars(tmp_path, monkeypatch):
    """The serve7b flight-data summary rides the ledger line
    (burn_rate_peak / req_device_ms_p50 / alerts_fired, plus the
    mid-QPS row's burn_rate) and is shed with the other secondary
    detail when the line must shrink."""
    import bench

    monkeypatch.setattr(bench, "DETAILS_PATH",
                        str(tmp_path / "BENCH_DETAILS.json"))
    r = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
         "extra": {"platform": "tpu", "n_chips": 1, "secondary": {
             "serve7b": {
                 "metric": "serve7b_tokens_per_sec", "value": 100.0,
                 "unit": "tokens/s", "vs_baseline": 1.0,
                 "extra": {"goodput_under_slo": {
                     "sweep": [
                         {"qps": 2.0, "goodput": 1.0,
                          "p99_ttft_ms": 30.0, "p99_tpot_ms": 8.0,
                          "burn_rate": 0.0},
                         {"qps": 8.0, "goodput": 0.5,
                          "p99_ttft_ms": 90.0, "p99_tpot_ms": 20.0,
                          "burn_rate": 5.0},
                     ],
                     "flight": {"burn_rate_peak": 5.0,
                                "req_device_ms_p50": 1.25,
                                "alerts_fired": 2}}}}}}}
    row = json.loads(bench._compact_line(r))["extra"]["secondary"][
        "serve7b"]
    assert row["flight"] == {"burn_rate_peak": 5.0,
                             "req_device_ms_p50": 1.25,
                             "alerts_fired": 2}
    assert row["goodput"]["burn_rate"] == 0.0  # mid row of 2 = first
    monkeypatch.setattr(bench, "MAX_LINE_BYTES", 400)
    shed = json.loads(bench._compact_line(r))
    sec = shed["extra"].get("secondary", {}).get("serve7b", {})
    assert "flight" not in sec and "goodput" not in sec


def test_chip_smoke_refuses_to_run_without_a_chip():
    """``chip_smoke.py`` with no argument on a machine without a TPU
    exits non-zero before it builds a model and prints no result — the
    driver runs exactly this in the sandbox and it must fail."""
    import subprocess
    import time

    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, None), r.stdout
    assert '"ok"' not in r.stdout and "model:" not in r.stdout
    assert "needs 1 tpu device" in r.stderr
    assert time.monotonic() - t0 < 60


@pytest.mark.parametrize("env_dir", [None, "/some/where/else"])
def test_compile_cache_directory_rule(env_dir, monkeypatch):
    """One rule for chip_smoke.py and bench.py: with
    JAX_COMPILATION_CACHE_DIR set, no directory is set in code (JAX
    reads the variable itself); unset, the cache is the one fixed
    directory inside the checkout."""
    import jax

    from benchmarks import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == \
            compile_cache.DEFAULT_DIR
        assert updates == [("jax_compilation_cache_dir",
                            compile_cache.DEFAULT_DIR)]
        assert compile_cache.DEFAULT_DIR == os.path.join(
            compile_cache.REPO_ROOT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert updates == []
