"""Trainer telemetry: per-step instrumentation for ``TrainStep.run``.

Sampling discipline (the acceptance-critical part): every step records
only host-side wall time into the ring buffer — cheap python, no device
traffic. On a sample-every-N cadence the loss / grad-norm device
scalars (which the compiled step already produced) are fetched, gauges
update, ``device.memory_stats()`` is read, and the anomaly watchdog
runs. Non-sampled steps perform NO ``device_get``/host sync beyond what
the caller does with the returned loss.

The rate metric (tokens/s) is averaged over the SAMPLING INTERVAL,
measured between post-fetch sync points: per-step wall clock only times
the async *dispatch*, which can run orders of magnitude ahead of the
device and would report impossible throughput. The interval
endpoints sit right after ``float(loss)`` — a real completion fence —
so the rate is device-true in steady state. The first interval includes
compile time and undershoots; that is the honest direction.

Two host events stop the loop's thread wherever they strike: a pause of
Python's cyclic garbage collector and a trace, compile or cache load of
a JAX program. The first ``TrainTelemetry`` installs one hook for each,
for the process: a collection is a ``pt.gc`` span on the profiler's
clock, and both add to the process counters ``HOST_EVENTS``, which the
sampled fetch and the flight recorder report by interval.
"""

from __future__ import annotations

import gc
import time
from typing import Optional

import jax

from .. import flags
from .recorder import AnomalyWatchdog, FlightRecorder
from .registry import exp_buckets, get_registry


# process totals of the host events that stop the loop's thread
HOST_EVENTS = {"gc_ms": 0.0, "gc_count": 0, "compile_ms": 0.0,
               "compiles": 0}
# JAX's durations of a program's trace, lowering and backend compile.
# The backend compile's event wraps the persistent cache's read, so a
# cache load is one backend compile whose time holds the load; its own
# ``/jax/compilation_cache/cache_retrieval_time_sec`` would count it twice
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_gc_open = None  # (span, start) of the collection under way
_hooked = False


def _on_gc(phase: str, info: dict):
    """A collection as a ``pt.gc`` span on the thread that triggered it
    (inside whichever ``pt.*`` span was open there), and its pause: the
    span's own cost included, so that the pause covers the span."""
    global _gc_open
    if phase == "start":
        t0 = time.perf_counter()
        span = jax.profiler.TraceAnnotation(
            "pt.gc", generation=info["generation"])
        span.__enter__()
        _gc_open = (span, t0)
    elif _gc_open is not None:
        span, t0 = _gc_open
        _gc_open = None
        span.set_metadata(collected=info["collected"])
        span.__exit__(None, None, None)
        HOST_EVENTS["gc_ms"] += (time.perf_counter() - t0) * 1e3
        HOST_EVENTS["gc_count"] += 1


def _on_duration(event: str, secs: float, **_):
    if event in _COMPILE_EVENTS:
        HOST_EVENTS["compile_ms"] += secs * 1e3
        if event == _COMPILE_EVENTS[-1]:
            HOST_EVENTS["compiles"] += 1


def install_host_hooks():
    """Once a process: the collector's callback and JAX's duration
    listener (chipbench's own cache counter is another listener)."""
    global _hooked
    if not _hooked:
        _hooked = True
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _since(last: dict) -> dict:
    """The host events since ``last``, which moves to now."""
    out = {k: v - last[k] for k, v in HOST_EVENTS.items()}
    last.update(HOST_EVENTS)
    return out


def _memory_stats() -> Optional[dict]:
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


class TrainTelemetry:
    """One instance per TrainStep; holds its metrics, flight recorder
    and watchdog. Construct only when telemetry is enabled — callers
    keep ``None`` otherwise so the off path is a single identity
    check."""

    def __init__(self, sample_every: Optional[int] = None,
                 flight_window: Optional[int] = None,
                 dump_dir: Optional[str] = None,
                 spike_factor: Optional[float] = None):
        self.sample_every = max(1, int(
            sample_every if sample_every is not None
            else flags.flag("telemetry_sample_every")))
        reg = get_registry()
        self.recorder = FlightRecorder(
            capacity=(flight_window if flight_window is not None
                      else flags.flag("telemetry_flight_window")),
            dump_dir=(dump_dir if dump_dir is not None
                      else flags.flag("telemetry_dump_dir")))
        self.watchdog = AnomalyWatchdog(
            self.recorder,
            spike_factor=(spike_factor if spike_factor is not None
                          else flags.flag("telemetry_grad_spike_factor")))
        self._steps = reg.counter(
            "pt_train_steps_total", "optimizer steps executed")
        self._tokens = reg.counter(
            "pt_train_tokens_total", "tokens consumed by training")
        self._step_ms = reg.histogram(
            "pt_train_step_ms", "time per train step over each sampled "
            "interval, between two reads of the loss (ms)",
            buckets=exp_buckets(0.5, 2.0, 20))
        self._loss = reg.gauge("pt_train_loss", "last sampled loss")
        self._gnorm = reg.gauge(
            "pt_train_grad_norm", "last sampled global gradient norm")
        self._tps = reg.gauge(
            "pt_train_tokens_per_sec", "sampled-step token throughput")
        self._mem = reg.gauge(
            "pt_device_memory_bytes", "device memory_stats()",
            labels=("stat",))
        install_host_hooks()
        # the host events' totals at the last record and the last sample
        self._at_record = dict(HOST_EVENTS)
        self._at_sample = dict(HOST_EVENTS)
        # sampling-interval accumulators (rates are computed between
        # post-fetch sync points, not from per-step dispatch wall time)
        self._interval_t0 = time.perf_counter()
        self._interval_tokens = 0
        self._interval_steps = 0
        self.samples = 0
        self.last_sample: dict = {}

    # ------------------------------------------------------------------
    def should_sample(self, step: int) -> bool:
        return step % self.sample_every == 0

    def on_step(self, step: int, loss, grad_norm, tokens: int,
                wall_s: float, counters: Optional[dict] = None,
                t_ns: int = 0, shard_s: float = 0.0,
                dispatch_s: float = 0.0):
        """``loss``/``grad_norm`` are device scalars (async futures) —
        they are fetched ONLY on sampled steps. ``counters`` are more
        such scalars the compiled step returned (an expert model's
        routing counts); a sampled step reads them and they ride as
        arguments of its ``pt.train.sample_fetch`` span. ``t_ns`` is
        the step's start by ``time.time_ns()``, the clock the profiler
        stamps host events with (a trace stores them less its
        ``profile_start_time``); ``shard_s`` and ``dispatch_s`` are the
        host phases of ``TrainStep.run``."""
        self._steps.inc()
        if tokens:
            self._tokens.inc(tokens)
        self._interval_tokens += int(tokens)
        self._interval_steps += 1
        host = _since(self._at_record)
        rec = {"step": step, "t_ns": t_ns, "wall_ms": round(wall_s * 1e3, 3),
               "shard_ms": round(shard_s * 1e3, 3),
               "dispatch_ms": round(dispatch_s * 1e3, 3),
               "gc_ms": round(host["gc_ms"], 3),
               "compile_ms": round(host["compile_ms"], 3),
               "tokens": int(tokens)}
        if not self.should_sample(step):
            self.recorder.record(**rec)
            return None
        # ---- sampled step: one host read of the step's scalars ----
        # (a span on the profiler's clock: the fetch waits for every
        # step dispatched ahead, and a trace should say so by name)
        with jax.profiler.TraceAnnotation(
                "pt.train.sample_fetch",
                interval_steps=self._interval_steps) as span:
            loss, grad_norm, counters = jax.device_get(
                (loss, grad_norm, counters))
            if counters:
                rec.update({k: int(v) for k, v in counters.items()})
                span.set_metadata(**{k: rec[k] for k in counters})
            out = self._sample(step, loss, grad_norm, rec)
            # the interval's pauses and compiles, after the read that
            # closed it
            span.set_metadata(**_since(self._at_sample))
            return out

    def _sample(self, step: int, loss, grad_norm, rec: dict):
        loss_f = float(loss) if loss is not None else None
        gnorm_f = float(grad_norm) if grad_norm is not None else None
        # the read above fenced this step's completion: NOW is a
        # device-true interval endpoint for the rate metrics
        now = time.perf_counter()
        interval_s = now - self._interval_t0
        step_s = interval_s / self._interval_steps
        self._step_ms.observe(step_s * 1e3)
        if loss_f is not None:
            self._loss.set(loss_f)
            rec["loss"] = loss_f
        if gnorm_f is not None:
            self._gnorm.set(gnorm_f)
            rec["grad_norm"] = gnorm_f
        if self._interval_tokens and interval_s > 0:
            tps = self._interval_tokens / interval_s
            self._tps.set(tps)
            rec["tokens_per_sec"] = round(tps, 1)
        self._interval_t0 = now
        self._interval_tokens = 0
        self._interval_steps = 0
        if flags.flag("log_memory_stats"):
            mem = _memory_stats()
            if mem:
                for k, v in mem.items():
                    self._mem.set(v, stat=k)
                rec["memory"] = mem
        self.recorder.record(**rec)
        self.samples += 1
        self.last_sample = rec
        return self.watchdog.check(step, loss_f, gnorm_f, step_s=step_s)


def record_scalars(prefix: str, logs: Optional[dict], step=None):
    """Publish a dict of scalar logs as ``pt_<prefix>_<key>`` gauges —
    the shared funnel the hapi callbacks (ProgBarLogger / VisualDL /
    MetricsLogger) emit through. Non-numeric values are skipped."""
    if not logs:
        return
    reg = get_registry()
    for k, v in logs.items():
        try:
            f = float(v[0] if isinstance(v, (list, tuple)) else v)
        except (TypeError, ValueError, IndexError):
            continue
        name = "pt_" + "".join(
            c if c.isalnum() or c == "_" else "_"
            for c in f"{prefix}_{k}".lower())
        reg.gauge(name, f"hapi scalar {prefix}/{k}").set(f)
