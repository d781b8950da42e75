"""Benchmark timing from the profiler's device plane.

This module derives the benchmark's numbers from:

1. ``traced_step_ms`` — run N steps inside a ``jax.profiler`` trace,
   wait with ``block_until_ready``, and read the device-plane op total
   from the xplane/chrome trace (``profiler/xplane.py``). The wall time
   of the same window is kept beside it, never in its place.
2. ``compiled_flops`` — XLA's own ``cost_analysis()['flops']`` for the
   exact compiled program (includes remat re-forward FLOPs, attention,
   everything the 6*N*T estimate misses).
3. ``check_plausible`` — a hard guard: computed FLOP/s above 95% of the
   chip's spec-sheet peak is a measurement artifact by definition and
   MUST NOT be reported as a result (the reference's op-benchmark CI
   refuses regressions; ours first refuses impossibilities).

Parity: reference perf-gate tooling (upstream ``tools/`` op-benchmark
CI) + profiler statistics (``paddle/fluid/platform/profiler/``).
"""

from __future__ import annotations

import tempfile
from dataclasses import dataclass
from typing import Callable, Optional

import jax

from paddle_tpu.profiler import xplane

# ``device_kind`` as JAX reports it -> published peak per chip. A
# device that is not in a table is an error, never a default: a
# utilization against somebody else's peak is not a measurement. The
# "cpu" rows are nominal and exist only so the CPU smoke the tests run
# can exercise the same code; nothing computed from them is a result.
PEAK_BF16_FLOPS = {
    # device_kind -> peak bf16 FLOP/s per chip (public spec sheets)
    "cpu": 1e12,             # nominal: CPU smoke only
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}

PEAK_HBM_BYTES = {
    # device_kind -> HBM bandwidth B/s per chip (public spec sheets)
    "cpu": 100e9,            # nominal: CPU smoke only
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,    # v5e
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,        # v5p
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,   # v6e / Trillium
    "TPU v6e": 1640e9,
}

# computed-FLOP/s above this fraction of spec-sheet peak is treated as a
# measurement artifact, not a result
MFU_PLAUSIBILITY_CEILING = 0.95


def _peak(table: dict, what: str, device) -> float:
    device = device or jax.devices()[0]
    kind = device.device_kind
    # longest key first: "TPU v5 lite" must not read "TPU v5"'s row
    for k in sorted(table, key=len, reverse=True):
        if kind.startswith(k):
            return table[k]
    raise KeyError(
        f"no published {what} for device_kind {kind!r}: add it, with its "
        f"source, to benchmarks/devtime.py")


def peak_flops(device=None) -> float:
    return _peak(PEAK_BF16_FLOPS, "bf16 FLOP/s peak", device)


def peak_hbm_bandwidth(device=None) -> float:
    return _peak(PEAK_HBM_BYTES, "HBM bandwidth", device)


@dataclass
class DeviceTiming:
    device_step_ms: Optional[float]   # None when trace has no device plane
    wall_step_ms: float
    n_steps: int
    op_summary: Optional[xplane.DeviceOpSummary]

    @property
    def step_ms(self) -> float:
        """Device-plane step time. The CPU backend writes no device
        plane, so only there — the labelled CPU smoke — is the wall time
        of the same window returned; on any other backend a trace
        without a device plane is an error, not a wall-clock number."""
        if self.device_step_ms:
            return self.device_step_ms
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "the profiler trace carried no device plane: no device "
                "time was measured")
        return self.wall_step_ms


def traced_step_ms(run_step: Callable[[], object], n_steps: int = 5,
                   trace_dir: Optional[str] = None) -> DeviceTiming:
    """Execute ``run_step`` n times inside a profiler trace; return the
    per-step device time from the trace's device plane.

    ``run_step`` must return a jax value (what the window waits on).
    Call sites should warm up/compile before calling this."""
    import time

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    try:
        out = None
        for _ in range(n_steps):
            out = run_step()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    wall_ms = 1e3 * (time.perf_counter() - t0) / n_steps
    ops = xplane.device_op_summary(trace_dir)
    dev_ms = None
    if ops is not None and ops.rows:
        # total_ms sums ALL device planes; per-chip step time divides by
        # the plane count (SPMD: every chip runs the same step)
        dev_ms = ops.total_ms / n_steps / max(ops.n_planes, 1)
    return DeviceTiming(dev_ms, wall_ms, n_steps, ops)


def compiled_flops(lowered_or_jitted, *args, **kw) -> Optional[float]:
    """FLOPs of the compiled program via XLA cost analysis.

    Pass a ``jax.stages.Lowered`` (e.g. from ``TrainStep.lower()``,
    which lowers under the right mesh context), or a jitted callable
    plus its args — retracing cost only (compilation of an identical
    program hits the executable cache on most backends; worst case it
    recompiles once, which a benchmark can afford for an honest FLOPs
    denominator)."""
    try:
        lowered = (lowered_or_jitted if hasattr(lowered_or_jitted,
                                                "compile")
                   and not args and not kw
                   else lowered_or_jitted.lower(*args, **kw))
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:
        return None


def check_plausible(flops_per_step: Optional[float], step_ms: float,
                    device=None) -> dict:
    """-> {"mfu_est": float|None, "implausible": bool, "reason": str?}.

    A computed FLOP/s above MFU_PLAUSIBILITY_CEILING x peak means the
    timing is broken (dispatch measured instead of execution) — callers
    must refuse to report the number as a result."""
    if not flops_per_step or step_ms <= 0:
        return {"mfu_est": None, "implausible": False}
    peak = peak_flops(device)
    mfu = flops_per_step / (step_ms / 1e3) / peak
    out = {"mfu_est": round(mfu, 4)}
    if mfu > MFU_PLAUSIBILITY_CEILING:
        out["implausible"] = True
        out["reason"] = (
            f"computed {flops_per_step / (step_ms / 1e3) / 1e12:.1f} "
            f"TFLOP/s exceeds {MFU_PLAUSIBILITY_CEILING:.0%} of chip peak "
            f"({peak / 1e12:.0f} TFLOP/s) — measurement artifact, refused")
    else:
        out["implausible"] = False
    return out
