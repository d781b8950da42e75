"""Global flag registry.

Parity: the FLAGS_* system (paddle/utils/flags/ vendored gflags-workalike
+ paddle.set_flags/get_flags): process-level knobs settable via env
(``PT_FLAGS_xxx=``) or at runtime.

TPU-native: most reference flags configure the CUDA allocator/cudnn/NCCL
and are subsumed by XLA; the registry carries the framework-level knobs
that remain meaningful and passes xla_* entries through to XLA_FLAGS at
first-use time.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Dict[str, Any]] = {}


def define_flag(name: str, default, help_: str = ""):
    env = os.environ.get(f"PT_FLAGS_{name}")
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _REGISTRY[name] = {"value": value, "default": default, "help": help_}
    return value


def set_flags(flags: Dict[str, Any]):
    """Parity: paddle.set_flags({"FLAGS_x": v})."""
    for name, value in flags.items():
        key = name.removeprefix("FLAGS_")
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {name!r}")
        _REGISTRY[key]["value"] = value


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for name in names:
        key = name.removeprefix("FLAGS_")
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {name!r}")
        out[name] = _REGISTRY[key]["value"]
    return out


def flag(name: str):
    return _REGISTRY[name]["value"]


def all_flags():
    return {k: v["value"] for k, v in _REGISTRY.items()}


def registry():
    """The CANONICAL flag registry view: name -> {value, default,
    help}. This is the single source the static lint's flags-hygiene
    rules (``paddle_tpu.analysis.lint``, FL001–FL003) and the
    registry-consistency tests check against — every ``PT_FLAGS_*``
    read anywhere in the repo must resolve here (flags defined in
    other modules, e.g. ``nn/layout.py``'s ``conv_layout``, register
    through the same ``define_flag`` and appear too). Returns copies;
    mutate flags through ``set_flags``."""
    return {k: dict(v) for k, v in _REGISTRY.items()}


# ---------------------------------------------------------------------------
# built-in flags (the meaningful survivors of the reference's ~hundreds)
# ---------------------------------------------------------------------------
define_flag("benchmark", False,
            "print per-step wall timing + loss from TrainStep.run "
            "(blocks on the step's outputs each step — a debug/bench "
            "knob, not a production setting)")
define_flag("check_nan_inf", False,
            "debug-check each TrainStep's loss/grad-norm for NaN/Inf "
            "and raise FloatingPointError at the offending step "
            "(forces a per-step host sync; read at TrainStep build "
            "time, where it also forces the grad-norm output on even "
            "with telemetry off)")
define_flag("default_matmul_precision", "",
            "process-wide jax matmul precision override, applied at "
            "import: bfloat16|tensorfloat32|float32|highest; empty = "
            "jax's default (bf16 on the MXU)")
define_flag("log_memory_stats", False,
            "record device bytes_in_use/peak_bytes_in_use through the "
            "telemetry registry on sampled steps")
define_flag("telemetry", True,
            "always-on runtime telemetry (observability.MetricsRegistry); "
            "off = every instrumented path is a no-op")
define_flag("telemetry_sample_every", 10,
            "fetch loss/grad-norm/memory host-side every N train steps "
            "(non-sampled steps never force a device sync)")
define_flag("telemetry_flight_window", 64,
            "flight-recorder ring buffer size (last K step records)")
define_flag("telemetry_dump_dir", "flight_records",
            "directory for flight-recorder JSON dumps")
define_flag("telemetry_grad_spike_factor", 10.0,
            "anomaly watchdog trips when grad norm exceeds this factor "
            "times the running median")
define_flag("trace_sample", 1.0,
            "serving lifecycle tracer sample rate in (0, 1]: the "
            "fraction of requests and engine steps recorded "
            "(deterministic — every round(1/rate)-th request id / step "
            "sequence number, so a sampled request's events are "
            "complete, never a torn subset). 0 disables the tracer "
            "entirely; PT_FLAGS_telemetry=off disables it regardless")
define_flag("trace_buffer", 8192,
            "ring capacity (events) of each serving tracer — old events "
            "fall off; bounds host memory no matter how long the engine "
            "runs")
define_flag("rng_use_global_seed", True,
            "derive the eager rng stream (core.random.default_key) "
            "from the global paddle_tpu.seed; off = draw the stream's "
            "base from OS entropy once per thread (non-reproducible "
            "by request)")
define_flag("fused_group_norm", True,
            "dispatch NHWC GroupNorm to the fused Pallas kernel")
define_flag("fused_decode", "auto",
            "fused single-pass decode attention (in-kernel RoPE + KV "
            "append + length-pruned streaming): auto = compiled kernel "
            "on TPU when shapes tile, lax reference elsewhere; "
            "on = force (Pallas interpret mode off-TPU); off = unfused")
define_flag("prefix_cache", True,
            "serving prefix KV reuse: admission looks up the longest "
            "cached block-aligned prompt prefix and prefills only the "
            "suffix (paged mode shares pages copy-on-write; contiguous "
            "mode copies cached token blocks into the slot). off = "
            "every request recomputes its full prompt")
define_flag("prefill_chunk", 256,
            "serving prefill chunk length: ONE compiled fixed-size-chunk "
            "program (clamped to [2, max_len] — a 1-token chunk would "
            "fall into the decode step's clamped append) drives prefill "
            "in a host loop — compute ∝ suffix rounded up to the chunk, "
            "not the seq bucket, and compile count drops from "
            "len(seq_buckets) to 1. 0 = legacy per-bucket prefill (the "
            "parity oracle)")
define_flag("spec_decode", "off",
            "speculative decoding in the serving engine: draft K "
            "candidate tokens per slot per step (host-side n-gram "
            "prompt-lookup — no draft model weights) and score them in "
            "ONE fixed [slots, K+1] target-model pass with in-jit "
            "greedy acceptance, amortizing the per-step weight stream "
            "over accepted+1 tokens. ngram = draft whenever the slot's "
            "history matches; auto = ngram with a per-request throttle "
            "that stops drafting traffic that never accepts; off = "
            "today's one-token-per-pass decode (the parity oracle — "
            "greedy outputs are identical in every mode)")
define_flag("fault_inject", "",
            "serving fault injector (chaos testing): comma-separated "
            "site:rate entries over the engine's dispatch seams — "
            "step (dispatch exception), nan (NaN-logits storm), "
            "latency (stall before dispatch), pool (simulated KV-pool "
            "exhaustion at admission) — plus seed:<int> and "
            "latency_ms:<float>, e.g. 'step:0.1,nan:0.05,seed:7'. "
            "Each site draws from its own seeded RNG stream, so chaos "
            "runs are deterministic and CPU-runnable. Empty = off "
            "(zero overhead)")
define_flag("serve_recovery", "auto",
            "step-level crash recovery in the serving engine: catch a "
            "failed decode/verify/prefill dispatch, quarantine the "
            "step and re-queue its in-flight requests for "
            "deterministic replay (prompt+history re-prefilled "
            "through the existing chunked-prefill program; greedy "
            "outputs stay bit-identical), with bounded per-request "
            "retries (EngineConfig.max_retries). auto = recover "
            "injected faults and XLA runtime errors, propagate host "
            "logic errors; all = recover any Exception; off = every "
            "fault propagates")
define_flag("degradation", True,
            "graceful-degradation ladder in the serving engine: "
            "sustained admission saturation sheds batch-class "
            "admissions then throttles admission; repeated step "
            "faults additionally disable speculative decoding and "
            "prefix-cache adoption (min_service). Surfaced through "
            "backpressure()/healthz/the tracer; never changes greedy "
            "outputs. off = the controller is not constructed")
define_flag("kv_cache_dtype", "auto",
            "serving KV-cache dtype when EngineConfig.cache_dtype is "
            "'auto': auto = bfloat16 on TPU (halves decode KV traffic), "
            "float32 elsewhere; or explicit "
            "bfloat16|float16|float32|int8. int8 stores per-row f32 "
            "scales alongside the pools (per page-row paged, per block "
            "row contiguous), quantizes on append and dequantizes "
            "inside the fused decode kernels — KV stream bytes halve "
            "again vs bf16; greedy outputs may differ from the fp "
            "cache (the serve7b 'quant' bench scenario MEASURES that "
            "delta, outputs_match + first-divergence index)")
define_flag("serve_weight_dtype", "bf16",
            "serving weight stream when EngineConfig.weight_dtype is "
            "'auto': bf16 = serve the model's own weights; int8/int4 = "
            "group-wise weight-only quantization at engine init "
            "(quantize_model_weight_only), weights + scales ride every "
            "compiled serving program as jit arguments and dequantize "
            "in-kernel (weight_only_matmul_pallas on TPU, the XLA "
            "dequant reference elsewhere) — weight HBM traffic drops "
            "2x/4x, the decode roofline's other half. Single-chip "
            "serving only (no mesh); quality delta is measured, not "
            "asserted away, by the serve7b 'quant' scenario")
define_flag("sanitize", False,
            "serving-engine runtime invariant sanitizer "
            "(analysis/sanitizer.py): once per scheduler tick, check "
            "page/refcount conservation, slot-heap + block-table + "
            "int8-scale-pool agreement and seq_len bounds against the "
            "host token ledger, plus thread-ownership of scrape-"
            "thread reads (only the registered copy-on-read snapshot "
            "methods may be called from a foreign thread). Violations "
            "raise SanitizerError naming the invariant and site. "
            "off = every hook is a single identity check (the "
            "telemetry=off pattern); `pytest -m chaos` runs with it "
            "on. Host bookkeeping only — zero compiled programs, "
            "zero device syncs")
define_flag("profile_programs", False,
            "serving per-program device-time profiler "
            "(observability/profiling.py): cadence-sampled "
            "block-until-ready timing around every compiled serving "
            "dispatch (prefill_chunk/prefill_bucket/decode_step/"
            "decode_chunk/spec_verify/page_copy). Sampled dispatches "
            "record MEASURED device ms into "
            "pt_serve_program_ms{engine,program} plus a host-schedule/"
            "dispatch/device decomposition on the tracer's step "
            "events; unsampled dispatches stay fully async (no host "
            "sync — the PR-2 cadence discipline). off = the engine "
            "holds no profiler, one identity check per seam, zero new "
            "compiled programs")
define_flag("profile_sample_every", 16,
            "profile_programs sample cadence: measure every Nth "
            "dispatch of each program (per-program counters, "
            "deterministic). 1 = measure every dispatch — full "
            "attribution at the cost of one device sync per dispatch; "
            "note a program's FIRST dispatch (its compile) is only "
            "sampled at cadence 1")
define_flag("recompile_watchdog", True,
            "runtime recompile watchdog: after "
            "recompile_warmup_ticks scheduler ticks (or an explicit "
            "engine.seal_programs()) the engine's expected "
            "compiled-program set is SEALED; any later TRACE_COUNTS "
            "growth during one of this engine's own ticks counts "
            "pt_serve_recompiles_total{engine,program} and (telemetry "
            "on) dumps a FlightRecorder artifact carrying the "
            "offending specialization's arg shapes — the production "
            "complement to ptlint TS003 and the test-only "
            "compile-count guards. A program whose FIRST legitimate "
            "use lands after the seal (e.g. page_copy on the first "
            "copy-on-write) counts once — size the warmup, or seal "
            "explicitly after real warmup traffic. One artifact per "
            "program per engine; counters keep counting. Never "
            "raises; off = no watchdog, one identity check per tick")
define_flag("audit_on_seal", False,
            "run the ptaudit jaxpr contract audit "
            "(analysis/program_audit.py: donation/aliasing, dtype "
            "discipline, transfer bans, dead operands) over the "
            "engine's OWN compiled programs at its real shapes when "
            "seal_programs() seals the set — a trace-only self-audit "
            "(no compile, no dispatch, TRACE_COUNTS restored so the "
            "watchdog and compile-count guards never see it); the "
            "verdict surfaces in metrics_snapshot()['audit']. Off = "
            "one identity check at seal. Size budgets (SZ) stay with "
            "the CLI's canonical tiny arms")
define_flag("timeseries", False,
            "serving flight-data recorder "
            "(observability/timeseries.py): a bounded ring of "
            "fixed-cadence windowed samples over the engine's/"
            "router's metrics — counter deltas become per-window "
            "rates, gauges are point-sampled, histogram window-"
            "percentiles ride along (telemetry on). Tick-driven and "
            "wall-clock-free in every decision, scrape-thread-safe "
            "copy-on-read; read via engine.timeline_snapshot(), the "
            "/timeline endpoint and `dump --timeline`. off = no "
            "store is constructed (one identity check per tick, zero "
            "new compiled programs, outputs bit-identical)")
define_flag("timeseries_cadence", 16,
            "scheduler ticks per time-series window: every Nth tick "
            "closes a window and appends one sample (counter deltas "
            "over exactly N ticks — deterministic)")
define_flag("timeseries_retention", 256,
            "time-series ring capacity (windows): old samples fall "
            "off, bounding host memory no matter how long the engine "
            "runs; at the default cadence x retention this is the "
            "last ~4k scheduler ticks of history")
define_flag("alerts", True,
            "rule-based detectors over the serving time-series "
            "(observability/alerts.py): multi-window SLO burn-rate, "
            "queue-depth growth, prefix-hit / spec-acceptance "
            "collapse, post-seal recompiles, HBM residency — each "
            "with hysteresis (no flapping), firing structured "
            "`alert` tracer events + a FlightRecorder artifact "
            "carrying the triggering window, surfaced in "
            "metrics_snapshot()['alerts'] and the fleet snapshot. "
            "Evaluated only when PT_FLAGS_timeseries is on (the "
            "rules read the series); off = no detectors constructed")
define_flag("cost_attribution", True,
            "per-request device-cost attribution: each step's "
            "measured program-ms (profiler-sampled; sync-wall "
            "estimate on unsampled steps) is split across the "
            "requests the step advanced, proportional to tokens "
            "advanced, accumulated on the request and recorded at "
            "finish into pt_serve_request_device_ms{engine,slo} and "
            "the request ledger (cost survives failover/drain "
            "handoffs); read via engine.cost_snapshot(). Pure host "
            "arithmetic — zero device syncs, zero new compiled "
            "programs. off = requests carry device_ms 0 (one "
            "identity check per seam, outputs bit-identical)")
define_flag("slo_degradation", False,
            "let the degradation ladder consume the SLO burn-rate "
            "alert (read-only AlertManager.is_active hook): an "
            "active slo_burn_rate counts as saturation pressure, so "
            "sustained burn climbs the CAPACITY rungs (shed batch-"
            "class admissions, throttle) even before the queue "
            "backs up — never the fault jump. Requires timeseries + "
            "alerts on to have any effect; off (default) leaves the "
            "ladder's inputs untouched (outputs pinned identical)")
define_flag("tenant_prefix_namespace", True,
            "multi-tenant prefix-cache isolation: tenant-tagged "
            "requests hash their prompt blocks under a per-tenant "
            "namespace seed, so tenants can neither probe for nor "
            "borrow each other's cached KV, and pool-pressure "
            "eviction spends the requesting tenant's own cold "
            "entries first. Untagged requests (tenant=None) always "
            "share the default chain — single-tenant traffic is "
            "bit-identical either way. off = all tenants share one "
            "namespace (maximum reuse, zero isolation)")
define_flag("sched_policy", "fifo",
            "serving front door's default admission scheduler when "
            "none is passed to start_api_server: fifo = the engine's "
            "native submission-order admission; slo_fair = "
            "serving_api.SLOFairScheduler (per-tenant weighted fair "
            "share + TTFT-deadline urgency decide admission order, "
            "chunk split and preemption). An explicit scheduler= "
            "argument always wins")
define_flag("api_max_tenants", 256,
            "serving front door: maximum DISTINCT tenant ids accepted "
            "over the server's lifetime — tenant strings are "
            "client-controlled and each unique value mints permanent "
            "per-tenant metric series, accounting buckets and "
            "fair-share ledger entries, so unbounded cardinality is a "
            "memory/scrape DoS; past the cap, requests carrying a NEW "
            "tenant are rejected with HTTP 429 (known tenants and "
            "untagged requests always pass; 0 rejects every "
            "tenant-tagged API request)")
define_flag("sched_preempt", True,
            "allow the SLO-fair scheduler to PREEMPT an active "
            "batch-class slot (release slot/pages, re-queue with "
            "history for deterministic replay through the existing "
            "prefill program — zero new compiled programs) when an "
            "interactive request is about to miss its TTFT target "
            "and no slot is free; bounded per request. off = "
            "admission reordering and quotas only")
define_flag("recompile_warmup_ticks", 64,
            "scheduler ticks before the recompile watchdog auto-seals "
            "the program set (warmup compiles are expected; "
            "engine.seal_programs() seals immediately, e.g. right "
            "after a bench warmup)")
define_flag("router_breaker_window", 16,
            "multi-engine router: sliding window (fleet ticks) the "
            "per-replica circuit breaker counts faults over — "
            "router_breaker_trip faults inside it open the breaker "
            "(the replica stops receiving traffic and its in-flight "
            "requests fail over to survivors)")
define_flag("router_breaker_trip", 3,
            "multi-engine router: replica faults (failed ticks, hung "
            "health probes, flaky probe verdicts) within the breaker "
            "window that OPEN a replica's circuit breaker; a whole-"
            "replica crash opens it immediately regardless")
define_flag("router_breaker_cooldown", 8,
            "multi-engine router: base open-state duration (fleet "
            "ticks) before an open breaker admits a half-open canary "
            "probe; successive opens multiply it by the "
            "router_retry_schedule entries plus a seeded jitter "
            "(deterministic per router seed + replica)")
define_flag("router_retry_schedule", "1,2,4",
            "multi-engine router: comma-separated cooldown "
            "multipliers for successive breaker opens (the last entry "
            "repeats) — with cooldown 8 the default backs off "
            "8/16/32/32/... ticks. Deterministic: the only randomness "
            "is a per-replica jitter drawn from a stream seeded on "
            "(router seed, replica index)")
define_flag("moe_capacity_factor", 1.25,
            "default MoE expert capacity factor when a layer doesn't "
            "pass one explicitly (capacity = factor * tokens * top_k "
            "/ num_experts)")
define_flag("io_prefetch_depth", 2,
            "host→device prefetch buffers (io.prefetch_to_device "
            "default queue depth)")
