"""Benchmark: Llama pretraining step on the available TPU chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric: tokens/sec/chip for a causal-LM train step (fwd+bwd+AdamW update,
bf16 compute / fp32 master, ZeRO-3-equivalent sharding when >1 chip).
``vs_baseline`` is 1.0: no baseline measured on the local chip is on file
(ROADMAP.md S1 builds the ledger that will hold one).

The benchmark needs a TPU. A process that finds none, or whose benchmark
raises, exits non-zero and prints no result line: there is no probe, no
retry and no rerun on the CPU. ``JAX_PLATFORMS=cpu`` given by the caller
is the labelled CPU smoke the tests use — its metric names say
``cpu_smoke`` and nothing it prints is a speed.

Usage:
  python bench.py            # headline + the secondary configs
  python bench.py --config moe|vit|mamba|infer   # one benchmark, in-process
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _llama_cfg(platform):
    import os

    from paddle_tpu.models import LlamaConfig

    if platform == "tpu":
        # ~880M-param Llama, remat OFF. Tuned on the v5e chip (round 3
        # sweep): wider beats deeper — the MXU runs the h×(8/3·h) MLP
        # GEMMs at higher utilization than many small ones, and remat
        # on a model that fits costs ~1/3 extra FLOPs the 6·N·tok MFU
        # formula doesn't credit (round 2's 36% was mostly that tax).
        # Measured: h1536/L16 47.7%, h2048/L12 50.8%, h2560/L8 52.0%,
        # h3072/L6 56.3% MFU. Params bf16 + fp32 master + AdamW moments
        # ≈ 14 B/param ≈ 12.3 GB; batch 4×2048 no-remat activations fit
        # the 16 GB HBM.
        hid = int(os.environ.get("BENCH_HID", "3072"))
        inter = int(os.environ.get("BENCH_INTER", str(int(hid * 8 // 3 // 128 * 128))))
        layers = int(os.environ.get("BENCH_LAYERS", "6"))
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        remat = os.environ.get("BENCH_REMAT", "0") == "1"
        return LlamaConfig(
            vocab_size=32000,
            hidden_size=hid,
            intermediate_size=inter,
            num_hidden_layers=layers,
            num_attention_heads=hid // 128,  # head_dim 128 → flash kernel
            num_key_value_heads=hid // 128,
            max_position_embeddings=2048,
            use_flash_attention=True,
            use_recompute=remat,
            dtype="bfloat16",
        ), batch, 2048, 10
    # CPU smoke: tiny but same code path
    return LlamaConfig(
        vocab_size=512,
        hidden_size=256,
        intermediate_size=512,
        num_hidden_layers=2,
        num_attention_heads=2,
        num_key_value_heads=2,
        max_position_embeddings=256,
        use_flash_attention=False,
        dtype="float32",
    ), 2, 256, 3


def bench_llama_train():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from benchmarks.devtime import (
        check_plausible,
        compiled_flops,
        peak_flops,
        traced_step_ms,
    )
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.distributed.strategy import (
        DistributedStrategy,
        HybridConfig,
    )
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.trainer import TrainStep

    devices = jax.devices()
    n = len(devices)
    platform = devices[0].platform

    cfg, batch, seq, iters = _llama_cfg(platform)
    if batch % n:
        # batch must divide the dp×fsdp sharding (multi-device CPU smoke)
        batch = n * max(1, batch // n)

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.to(pt.bfloat16)

    # BENCH_MOMENT_DTYPE=bfloat16: halve Adam moment storage — the
    # update step is HBM-roofline (10% of the b4 headline), so this is
    # a direct ~3% step-time lever; measure against the fp32 default
    moment_dtype = _norm_moment_dtype(os.environ.get("BENCH_MOMENT_DTYPE"))
    optimizer = opt.AdamW(
        learning_rate=3e-4, weight_decay=0.01,
        multi_precision=(cfg.dtype == "bfloat16"),
        grad_clip=opt.ClipGradByGlobalNorm(1.0),
        moment_dtype=moment_dtype,
    )
    strategy = DistributedStrategy()
    if n > 1:
        strategy.hybrid_configs = HybridConfig(sharding_degree=n)
        strategy.sharding = True
        strategy.sharding_configs.stage = 3
        mesh = dist.build_mesh(fsdp=n, devices=devices)
    else:
        mesh = dist.build_mesh(devices=devices)

    # master_only drops the persistent bf16 param copies (the fp32
    # master is the single resident form; compute views are cast in-step)
    # — saves 2 B/param ≈ 1.75 GB on the 876M headline, bit-identical
    # numerics. That headroom is what admits batch 6.
    residency = os.environ.get(
        "BENCH_RESIDENCY",
        "master_only" if cfg.dtype == "bfloat16" else "paired")
    ts = TrainStep(model, optimizer, mesh, strategy,
                   master_residency=residency)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))
    data = {"input_ids": ids, "labels": ids}

    # warmup / compile
    ts.run(data)
    jax.block_until_ready(ts.run(data))

    # N steps inside a profiler trace: the reported throughput comes
    # from the trace's device plane (it excludes host and idle time —
    # ROADMAP.md S1)
    n_steps = min(iters, 5) if platform == "tpu" else 2
    timing = traced_step_ms(lambda: ts.run(data), n_steps=n_steps)
    loss = ts.run(data)

    step_s = timing.step_ms / 1e3
    tokens_per_sec_chip = batch * seq / step_s / n

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    peak = peak_flops(devices[0])
    # MFU denominator: XLA's own cost analysis of the compiled step
    # (includes attention + remat); fall back to the 6*N*T estimate
    # (per-chip: global-batch tokens divided over n chips, matching
    # the per-device step time the guard compares against)
    flops = compiled_flops(ts.lower(data))
    flops_src = "xla_cost_analysis"
    if flops is None:
        flops = 6.0 * n_params * batch * seq / n
        flops_src = "6NT_estimate"
    plaus = check_plausible(flops, timing.step_ms, devices[0])
    mfu = plaus.get("mfu_est")

    vs = 1.0

    extra = {
        "n_chips": n,
        "platform": platform,
        "device_kind": getattr(devices[0], "device_kind", "?"),
        "peak_flops": peak,
        "params": n_params,
        "batch": batch,
        "seq": seq,
        "remat": cfg.use_recompute,
        "residency": residency,
        "moment_dtype": str(moment_dtype or "float32"),
        "step_ms": round(timing.step_ms, 2),
        "device_step_ms": (round(timing.device_step_ms, 2)
                           if timing.device_step_ms else None),
        "wall_step_ms": round(timing.wall_step_ms, 2),
        "timed_steps": timing.n_steps,
        "flops_per_step": flops,
        "flops_source": flops_src,
        "mfu_est": mfu,
        "loss": float(loss),
    }
    if timing.op_summary is not None and timing.op_summary.rows:
        ops = timing.op_summary
        total = ops.total_ms
        extra["op_summary"] = {
            "total_device_ms": round(total, 2),
            "timed_steps": timing.n_steps,
            "categories": {
                k: round(100.0 * v / total, 1)
                for k, v in ops.by_category().items()
            },
            "top_ops": [
                {"name": r.name[:60], "ms": round(r.total_ms, 2),
                 "count": r.count}
                for r in ops.rows[:8]
            ],
        }
    if plaus.get("implausible"):
        # computed FLOP/s above chip peak: refuse to report (round-4
        # lesson — 4 of 5 secondary numbers were dispatch-time artifacts)
        extra["refused_value"] = round(tokens_per_sec_chip, 1)
        extra["error"] = plaus.get("reason")
        return {
            "metric": "llama_train_implausible",
            "value": 0.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "extra": extra,
        }
    name = (f"llama{n_params // 10**6}m_train_tokens_per_sec_per_chip"
            if platform == "tpu"
            else "llama_train_cpu_smoke_tokens_per_sec")
    return {
        "metric": name,
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs, 3),
        "extra": extra,
    }


DETAILS_PATH = os.path.join(os.path.dirname(__file__),
                            "BENCH_DETAILS.json")
MAX_LINE_BYTES = 2000


def _compact_line(result):
    """Build the driver-facing JSON line: always parseable, < 2KB.

    Round 3 lost its headline because the printed line carried full
    tracebacks + per-secondary probe diagnostics and defeated the
    driver's tail parse. Full diagnostics now go to BENCH_DETAILS.json;
    the printed line keeps scalars only, with errors truncated hard.
    """
    details_error = None
    try:
        with open(DETAILS_PATH, "w") as f:
            json.dump(result, f, indent=1, default=str)
    except Exception as e:
        details_error = repr(e)[:120]

    def _err_msg(e):
        e = e or {}
        msg = (e.get("error") or e.get("stderr") or e.get("reason")
               or e.get("traceback")
               or (f"timeout after {e['timeout_s']}s"
                   if "timeout_s" in e else ""))
        return str(msg).strip()[-120:]

    out = {k: result.get(k)
           for k in ("metric", "value", "unit", "vs_baseline")}
    extra = result.get("extra", {}) or {}
    keep = {k: extra[k] for k in
            ("platform", "n_chips", "device_kind", "params", "batch",
             "seq", "remat", "residency", "moment_dtype", "step_ms",
             "device_step_ms", "mfu_est", "loss") if k in extra}
    if result.get("unit") == "error":
        keep["error"] = _err_msg(extra)
    if details_error:
        keep["details_error"] = details_error
    sec = extra.get("secondary")
    if sec:
        keep["secondary"] = {}
        for name, r in sec.items():
            row = {"metric": r.get("metric"), "value": r.get("value"),
                   "unit": r.get("unit")}
            if "vs_baseline" in r:
                row["vs_baseline"] = r["vs_baseline"]
            if r.get("unit") in ("error", "skipped"):
                row["error"] = _err_msg(r.get("extra"))
            # goodput-under-SLO headline (serve7b): the mid-QPS row's
            # scalars ride the ledger line — the engine's metrics_
            # snapshot() is one document now, no stitching here
            gp = (r.get("extra") or {}).get("goodput_under_slo") or {}
            sweep = gp.get("sweep") or []
            if sweep:
                # (n-1)//2: the true middle row — n//2 would pick the
                # LAST (worst-goodput) row of an even-length sweep
                mid = sweep[(len(sweep) - 1) // 2]
                row["goodput"] = {
                    k: mid.get(k) for k in
                    ("qps", "goodput", "p99_ttft_ms", "p99_tpot_ms",
                     "burn_rate")}
            # flight-data scalars (serve7b): peak SLO burn across the
            # sweep, p50 attributed request device-ms, alert firings —
            # the trend-shaped numbers the ledger trajectory
            # accumulates (shed-path included below)
            fl = gp.get("flight") or {}
            if fl:
                row["flight"] = {
                    k: fl.get(k) for k in
                    ("burn_rate_peak", "req_device_ms_p50",
                     "alerts_fired")}
            # scheduler A/B scalars (serve7b): FIFO-vs-SLO-fair
            # goodput at the saturated burst plus the starvation
            # adversary's worst-small-tenant TTFT bound — the numbers
            # that rank admission policies on the ledger
            sa = (r.get("extra") or {}).get("sched_ab") or {}
            if sa:
                row["sched_ab"] = {
                    "fifo_goodput": (sa.get("fifo") or {}).get(
                        "goodput"),
                    "slo_fair_goodput": (sa.get("slo_fair") or {})
                    .get("goodput"),
                    "preemptions": (sa.get("slo_fair") or {}).get(
                        "preemptions"),
                    "starve_bound_x": (sa.get("starvation") or {})
                    .get("bound_factor"),
                }
            # HTTP front-door overhead (serve7b): server-path tok/s
            # beside the library path — the wire tax, measured over a
            # real loopback socket
            hf = (r.get("extra") or {}).get("http_front_door") or {}
            if hf:
                row["http_front_door"] = {
                    k: hf.get(k) for k in
                    ("library_tokens_per_sec", "http_tokens_per_sec",
                     "overhead_pct")}
            # quantized-serving scalars (serve7b): the MODELED compound
            # ×-factor names the expected win on the ledger before the
            # TPU window, and outputs_match/first_divergence carry the
            # measured quality delta with it
            qs = (r.get("extra") or {}).get("quant") or {}
            if qs:
                row["quant"] = {
                    k: qs.get(k) for k in
                    ("modeled_int8_w_x", "modeled_compound_x",
                     "outputs_match", "first_divergence")}
            # replicated-serving scalars (serve7b): the failover count
            # plus the outputs_match bit carry the fleet's determinism
            # claim on the ledger with the storm's wall overhead
            rf = (r.get("extra") or {}).get("replica_failover") or {}
            if rf:
                row["replica_failover"] = {
                    k: rf.get(k) for k in
                    ("failovers", "outputs_match",
                     "failover_overhead_pct")}
            # contract-audit verdict (serve7b): the repo program
            # set's ptaudit result rides the ledger — programs
            # audited, op-counts-ok bit, violation count — so a
            # donation/dtype/size regression is visible on the same
            # line as the perf numbers it would silently rot
            au = (r.get("extra") or {}).get("audit") or {}
            if au:
                row["audit"] = {
                    k: au.get(k) for k in
                    ("programs", "op_counts_ok", "violations")}
            # measured-vs-modeled step breakdown (serve7b): the
            # decode-chunk measured p50 beside its HBM floor, plus
            # the recompile-watchdog verdict, ride the ledger so the
            # driver sees MEASUREMENTS next to the models
            sb = (r.get("extra") or {}).get("step_breakdown") or {}
            sb_rows = {x.get("program"): x for x in sb.get("rows", [])}
            dc = sb_rows.get("decode_chunk")
            if dc:
                row["step_breakdown"] = {
                    "decode_chunk_ms": dc.get("measured_p50_ms"),
                    "decode_floor_ms": dc.get("modeled_floor_ms"),
                    "prefill_chunk_ms": (sb_rows.get("prefill_chunk")
                                         or {}).get("measured_p50_ms"),
                    "recompiles": sum(
                        (sb.get("recompiles_post_seal") or {})
                        .values()),
                }
            keep["secondary"][name] = row
    out["extra"] = keep

    line = json.dumps(out)
    # belt-and-braces: progressively shed detail until the line fits
    if len(line) > MAX_LINE_BYTES and "secondary" in keep:
        for row in keep["secondary"].values():
            row.pop("error", None)
            row.pop("goodput", None)
            row.pop("flight", None)
            row.pop("sched_ab", None)
            row.pop("http_front_door", None)
            row.pop("quant", None)
            row.pop("replica_failover", None)
            row.pop("audit", None)
            row.pop("step_breakdown", None)
        line = json.dumps(out)
    if len(line) > MAX_LINE_BYTES:
        out["extra"] = {k: keep[k] for k in ("platform", "n_chips")
                        if k in keep}
        line = json.dumps(out)
    return line


SECONDARY_TIMEOUT = 560   # per config; each compiles its own programs
SERVE7B_TIMEOUT = 700     # 32-layer decode program compiles are slower
SECONDARY_BUDGET = 2400   # total wall-clock for all secondaries
HEADLINE_TIMEOUT = 1200


def _run_one_config(name, env, timeout):
    """Run ``bench.py --config name`` in a subprocess. The parent process
    NEVER initializes jax: a chip belongs to one process at a time, so
    the device must be free for every child (headline included). A
    child that exits without a result line comes back as an error row
    carrying its exit code and the end of its stderr."""
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--config", name],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
        lines = [l for l in r.stdout.strip().splitlines()
                 if l.startswith("{")]
        if lines:
            return json.loads(lines[-1])
        return {"metric": f"bench_{name}_failed", "value": 0.0,
                "unit": "error", "vs_baseline": 0.0,
                "extra": {"rc": r.returncode, "stderr": r.stderr[-800:]}}
    except subprocess.TimeoutExpired:
        return {"metric": f"bench_{name}_timeout", "value": 0.0,
                "unit": "error", "vs_baseline": 0.0,
                "extra": {"timeout_s": timeout}}
    except Exception as e:
        return {"metric": f"bench_{name}_failed", "value": 0.0,
                "unit": "error", "vs_baseline": 0.0,
                "extra": {"error": repr(e)}}


def _run_secondary_configs(env):
    """Capture the remaining BASELINE.json configs (infer/moe/vit/mamba
    + unet) — one subprocess each (clean device state) under a global
    budget. A failed config is an error row on the line and a non-zero
    exit of the parent."""
    out = {}
    t_start = time.time()
    for name in ("infer", "moe", "vit", "mamba", "unet", "serve7b"):
        if time.time() - t_start > SECONDARY_BUDGET:
            out[name] = {"metric": f"bench_{name}_skipped", "value": 0.0,
                         "unit": "skipped",
                         "extra": {"reason": "secondary budget exhausted"}}
            continue
        tmo = SERVE7B_TIMEOUT if name == "serve7b" else SECONDARY_TIMEOUT
        out[name] = _run_one_config(name, env, tmo)
    return out


def _norm_moment_dtype(s):
    """Validate/normalize BENCH_MOMENT_DTYPE up front — a typo must die
    in milliseconds, not after an 876M model build."""
    s = (s or "").strip().lower()
    if s in ("", "float32", "fp32", "f32"):
        return None
    if s in ("bfloat16", "bf16"):
        return "bfloat16"
    raise ValueError(
        f"BENCH_MOMENT_DTYPE={s!r}: use 'float32' or 'bfloat16'")


def _require_chip():
    """The benchmark runs on a TPU, or — only where the caller asked for
    it with ``JAX_PLATFORMS=cpu`` — as the labelled CPU smoke. Anything
    else ends the process non-zero before a model is built."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: no TPU (JAX found {platform!r}); set "
            "JAX_PLATFORMS=cpu for the labelled CPU smoke")


def _child_main(config):
    """Child mode (--config X): this process is the one that holds the
    device; run the requested benchmark in it. No failure is caught: an
    exception is a traceback and a non-zero exit."""
    from benchmarks.compile_cache import enable_compile_cache

    _require_chip()
    enable_compile_cache()
    if config == "llama":
        result = bench_llama_train()
    else:
        from benchmarks.suite import run_config

        result = run_config(config)
    print(json.dumps(result))


def main():
    argv = sys.argv[1:]
    if "--config" in argv:
        _child_main(argv[argv.index("--config") + 1])
        return

    # ---- parent: orchestration only, jax is never imported here, so
    # every child finds the chip free ----
    _norm_moment_dtype(os.environ.get("BENCH_MOMENT_DTYPE"))  # fail fast
    env = dict(os.environ)
    result = _run_one_config("llama", env, HEADLINE_TIMEOUT)
    if result.get("unit") == "error":
        sys.exit(f"bench.py: the headline benchmark failed: "
                 f"{json.dumps(result.get('extra'))}")
    secondary = {}
    if "--no-secondary" not in argv:
        secondary = _run_secondary_configs(env)
        result.setdefault("extra", {})["secondary"] = secondary
    print(_compact_line(result))
    failed = sorted(n for n, r in secondary.items()
                    if r.get("unit") == "error")
    if failed:
        sys.exit(f"bench.py: failed configs: {', '.join(failed)}")


if __name__ == "__main__":
    main()
