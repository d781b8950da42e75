"""Test env: force CPU backend with 8 virtual devices so every multi-chip
sharding path runs on CI hardware (parity with the reference's
Gloo-on-CPU + fake-mesh test strategy, SURVEY.md §4). The chip is never
touched from here: tests/test_chip_compile.py only DESCRIBES one.
"""

import os

# telemetry defaults ON for real runs, but the suite's hundreds of
# tiny-model TrainStep compilations would each pay the instrumented
# step's extra grad-norm output for no assertion value — keep the CI
# session un-instrumented; tests/test_observability.py flips the flag
# on (set_flags) for the paths that actually assert on telemetry
os.environ.setdefault("PT_FLAGS_telemetry", "off")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# numerics tests compare against float64/float32 numpy references; pin
# matmul precision (prod default stays bf16-on-MXU, the TPU analog of the
# reference's TF32-on-A100 default)
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture
def compile_counter():
    """Compile-count guard for engine tests: returns a callable giving
    the number of jit SPECIALIZATIONS of a named serving program since
    the fixture was set up (trace-time counters in
    ``paddle_tpu.inference.serving.TRACE_COUNTS``). Called with NO
    argument it returns the full {program: delta} dict (zero deltas
    omitted) so a test can pin the EXACT compiled-program set of a
    workload — e.g. spec-decode-off must compile precisely the PR-4
    set, spec-on at most verify + fallback on top. The regression this
    exists to prevent: a serving program silently re-specializing per
    prompt length / seq bucket / scheduler mode."""
    from paddle_tpu.inference import serving

    base = serving.TRACE_COUNTS.copy()

    def counter(key=None):
        if key is None:
            return {k: v - base[k]
                    for k, v in serving.TRACE_COUNTS.items()
                    if v - base[k]}
        return serving.TRACE_COUNTS[key] - base[key]

    def assert_programs(allowed):
        """Pin the compiled-program set: fail on any specialization
        outside ``allowed`` since the fixture (or the last snapshot
        the caller diffs against). The recovery/replay guard calls
        this to prove that quarantine + deterministic replay adds
        ZERO new compiled programs — replay must reuse the existing
        ``prefill_chunk``/``decode_chunk`` programs."""
        got = counter()
        extra = {k: v for k, v in got.items() if k not in set(allowed)}
        assert not extra, (
            f"unexpected compiled-program specializations: {extra} "
            f"(allowed: {sorted(allowed)})")

    counter.assert_programs = assert_programs
    return counter


@pytest.fixture
def serving_flags():
    """set_flags with restore for the serving knobs the engine suites
    flip (spec decode, prefix cache, prefill chunking, fused decode,
    KV/weight dtypes). Shared by test_spec_decode and
    test_quant_serving — yield the setter, restore on teardown."""
    from paddle_tpu import flags as F

    keys = ("spec_decode", "prefix_cache", "prefill_chunk",
            "fused_decode", "kv_cache_dtype", "serve_weight_dtype",
            "serve_recovery")
    saved = {k: F.flag(k) for k in keys}
    yield F.set_flags
    F.set_flags(saved)


@pytest.fixture(autouse=True)
def _sanitize_chaos_lane(request):
    """The chaos lane runs SANITIZED: every ``-m chaos`` storm
    executes with ``PT_FLAGS_sanitize=on``, so a fault-recovery bug
    that corrupts pool/slot/scale bookkeeping trips the invariant
    checker (analysis/sanitizer.py) at the tick that caused it,
    instead of shipping a poisoned trace the parity oracle flags
    hundreds of tokens later."""
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    from paddle_tpu import flags as F

    saved = F.flag("sanitize")
    F.set_flags({"sanitize": True})
    yield
    F.set_flags({"sanitize": saved})


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt

    pt.seed(2024)
    yield
    # don't leak the global mesh/HCG between tests
    from paddle_tpu.distributed import topology

    topology._global_hcg = None
