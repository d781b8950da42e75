"""AOT scale-proof for the SURVEY §6 north star (7B / 70B).

Parity: the memory-estimation + partitioning pass of the reference's
static auto-parallel engine (upstream:
python/paddle/distributed/auto_parallel/static/engine.py) — answer
"does this config FIT, with these shardings, before buying the pods?".

TPU-native design: build the model under ``core.meta.meta_init`` (zero
parameter bytes), construct the full sharded train step abstractly
(``TrainStep(abstract=True)`` / ``PipelineTrainStep(abstract=True)``),
AOT-lower and compile it on a *virtual* CPU mesh of the target size
(``--xla_force_host_platform_device_count``), and read the per-device
byte plan from ``compiled.memory_analysis()`` plus an analytic
per-parameter shard table. Catches vocab/optimizer replication blowups
that an 876M single-chip run never would.

Configs:
  7b  — Llama-2-7B,  8 devices,  ZeRO-3 x tp2 x sep2, seq 4096
  70b — Llama-3-70B, 128 devices, ZeRO-3(fsdp4) x tp8 x pp4 (1F1B),
        seq 8192
Both must fit v5p HBM (95 GB/chip) with bf16 params + fp32 master +
AdamW moments (~14 B/param total, sharded).

Usage:
  python benchmarks/memplan.py            # both, writes MEMPLAN.md
  python benchmarks/memplan.py 7b|70b     # one config, prints JSON
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

V5P_HBM_BYTES = 95 * 1024**3

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _force_cpu(n_devices):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) >= n_devices, (
        f"{len(jax.devices())} devices < {n_devices}"
    )


def _gb(x):
    return round(x / 1024**3, 3)


def _analytic_table(shardings, shapes_dtypes):
    """Per-device bytes per tensor from NamedSharding.shard_shape —
    the replication detector (a tensor whose per-device bytes equal its
    full bytes while axes were available is a blowup)."""
    import numpy as np

    rows = []
    for name, sh in shardings.items():
        shape, dtype = shapes_dtypes[name]
        full = int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
        per_dev = (int(np.prod(sh.shard_shape(tuple(shape))
                               or (1,))) * np.dtype(dtype).itemsize
                   if len(shape) else full)
        rows.append({"name": name, "shape": list(shape),
                     "dtype": str(np.dtype(dtype).name),
                     "full_mb": round(full / 2**20, 1),
                     "per_device_mb": round(per_dev / 2**20, 1),
                     "spec": str(sh.spec)})
    rows.sort(key=lambda r: -r["per_device_mb"])
    return rows


def plan_7b():
    _force_cpu(8)
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core.meta import meta_init
    from paddle_tpu.distributed.strategy import (
        DistributedStrategy,
        HybridConfig,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.trainer import TrainStep

    cfg = LlamaConfig.llama2_7b(
        max_position_embeddings=4096,
        use_flash_attention=False,   # CPU lowering; memory story identical
        use_recompute=True,
    )
    with meta_init():
        model = LlamaForCausalLM(cfg)
    model.to(pt.bfloat16)

    fsdp, tp, sep = 2, 2, 2
    mesh = dist.build_mesh(fsdp=fsdp, tp=tp, sep=sep)
    strategy = DistributedStrategy()
    strategy.hybrid_configs = HybridConfig(
        sharding_degree=fsdp, mp_degree=tp, sep_degree=sep)
    strategy.sharding = True
    strategy.sharding_configs.stage = 3

    optimizer = opt.AdamW(3e-4, weight_decay=0.01, multi_precision=True,
                          grad_clip=opt.ClipGradByGlobalNorm(1.0))
    ts = TrainStep(model, optimizer, mesh, strategy, abstract=True)

    batch, seq = 2, 4096
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lowered = ts.lower({"input_ids": ids, "labels": ids})
    compiled = lowered.compile()
    ma = compiled.memory_analysis()

    shapes = {n: (tuple(v.shape), v.dtype) for n, v in ts.params.items()}
    table = _analytic_table(ts.param_shardings, shapes)
    n_params = sum(math.prod(v.shape or (1,)) for v in ts.params.values())
    return _report("7b", mesh, n_params, ma, table,
                   {"fsdp": fsdp, "tp": tp, "sep": sep},
                   batch=batch, seq=seq)


def plan_70b():
    _force_cpu(128)
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core.meta import meta_init
    from paddle_tpu.distributed.pipeline import PipelineTrainStep
    from paddle_tpu.distributed.strategy import (
        DistributedStrategy,
        HybridConfig,
    )
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import llama_pipeline_module

    cfg = LlamaConfig.llama3_70b(
        max_position_embeddings=8192,
        use_flash_attention=False,
        use_recompute=True,
    )
    pp, tp, fsdp = 4, 8, 4
    n_micro = 8
    with meta_init():
        module = llama_pipeline_module(cfg, num_stages=pp)
    module.to(pt.bfloat16)

    mesh = dist.build_mesh(fsdp=fsdp, pp=pp, tp=tp)
    strategy = DistributedStrategy()
    strategy.hybrid_configs = HybridConfig(
        sharding_degree=fsdp, mp_degree=tp, pp_degree=pp)
    strategy.sharding = True
    strategy.sharding_configs.stage = 3
    strategy.pipeline = True
    strategy.pipeline_configs.schedule_mode = "1F1B"
    strategy.pipeline_configs.accumulate_steps = n_micro
    strategy.pipeline_configs.vpp_degree = 1
    strategy.recompute = True   # per-layer remat inside each stage chunk

    def loss_fn(logits, labels):
        return pt.nn.functional.cross_entropy(
            logits.reshape(-1, cfg.vocab_size), labels.reshape(-1)).mean()

    ts = PipelineTrainStep(
        module, opt.AdamW(3e-4, multi_precision=True), mesh, strategy,
        loss_fn, abstract=True)

    batch, seq = n_micro, 8192
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lowered = ts.lower(ids, ids)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()

    shapes = {n: (tuple(v.shape), v.dtype) for n, v in ts.params.items()}
    table = _analytic_table(ts.param_shardings, shapes)
    n_params = sum(math.prod(v.shape or (1,)) for v in ts.params.values())
    return _report("70b", mesh, n_params, ma, table,
                   {"fsdp": fsdp, "tp": tp, "pp": pp,
                    "schedule": "1F1B", "n_micro": n_micro},
                   batch=batch, seq=seq)


def _report(name, mesh, n_params, ma, table, degrees, batch, seq):
    args_b = getattr(ma, "argument_size_in_bytes", 0)
    temp_b = getattr(ma, "temp_size_in_bytes", 0)
    out_b = getattr(ma, "output_size_in_bytes", 0)
    # donation aliases outputs onto arguments, so args+temp is the
    # resident plan; outputs reported for completeness
    per_dev = args_b + temp_b
    replicated_big = [r for r in table
                      if r["per_device_mb"] == r["full_mb"]
                      and r["full_mb"] > 64]
    return {
        "config": name,
        "n_devices": int(len(mesh.devices.flatten())),
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "degrees": degrees,
        "batch": batch, "seq": seq,
        "params_b": int(n_params),
        "xla_argument_gb_per_device": _gb(args_b),
        "xla_temp_gb_per_device": _gb(temp_b),
        "xla_output_gb_per_device": _gb(out_b),
        "resident_gb_per_device": _gb(per_dev),
        "hbm_budget_gb": _gb(V5P_HBM_BYTES),
        "fits_v5p": bool(per_dev < V5P_HBM_BYTES),
        "replicated_over_64mb": replicated_big,
        "top_tensors": table[:10],
    }


_PLANS = {"7b": (plan_7b, 8), "70b": (plan_70b, 128)}


def run_child(name):
    fn, _ = _PLANS[name]
    print(json.dumps(fn()))


def run_all():
    """Spawn one clean subprocess per config (each needs its own
    --xla_force_host_platform_device_count before backend init)."""
    results = {}
    for name, (_, n_dev) in _PLANS.items():
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), name],
            capture_output=True, text=True, timeout=3600, env=env,
            cwd=REPO,
        )
        lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
        if r.returncode != 0 or not lines:
            results[name] = {"config": name, "error": r.stderr[-2000:]}
        else:
            results[name] = json.loads(lines[-1])
    return results


def write_md(results, path=os.path.join(REPO, "MEMPLAN.md")):
    lines = [
        "# MEMPLAN — AOT scale-proof for the north star",
        "",
        "Generated by `python benchmarks/memplan.py` (see its docstring "
        "for method). The full sharded train step for each config is "
        "built abstractly (`core.meta.meta_init` + "
        "`TrainStep/PipelineTrainStep(abstract=True)`), AOT-compiled on "
        "a virtual CPU mesh of the target size, and the per-device plan "
        "read from `compiled.memory_analysis()`. No parameter memory is "
        "ever allocated; XLA's SPMD partitioner sees exactly the "
        "shardings the real run would use.",
        "",
        "Note: XLA:CPU reports temp (activation) bytes as 0; the "
        "argument column — params + optimizer state + batch, the "
        "dominant resident term under ZeRO-3 + remat — is exact. "
        "Re-running on a TPU backend adds the temp column.",
        "",
        "| config | devices | mesh | params | XLA args/dev | temp/dev | "
        "resident/dev | v5p budget | fits |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for name, r in results.items():
        if "error" in r:
            lines.append(f"| {name} | — | — | — | — | — | — | — | "
                         f"ERROR (see below) |")
            continue
        mesh = "x".join(f"{k}{v}" for k, v in r["degrees"].items()
                        if isinstance(v, int))
        lines.append(
            f"| {name} | {r['n_devices']} | {mesh} | "
            f"{r['params_b'] / 1e9:.2f}B | "
            f"{r['xla_argument_gb_per_device']} GB | "
            f"{r['xla_temp_gb_per_device']} GB | "
            f"{r['resident_gb_per_device']} GB | "
            f"{r['hbm_budget_gb']} GB | "
            f"{'YES' if r['fits_v5p'] else 'NO'} |")
    for name, r in results.items():
        lines += ["", f"## {name}", ""]
        if "error" in r:
            lines += ["```", r["error"], "```"]
            continue
        lines.append(f"batch={r['batch']} seq={r['seq']} "
                     f"degrees={r['degrees']}")
        lines.append("")
        if r["replicated_over_64mb"]:
            lines.append("**Replicated tensors > 64 MB (review!):**")
            for t in r["replicated_over_64mb"]:
                lines.append(f"- `{t['name']}` {t['shape']} "
                             f"{t['full_mb']} MB spec={t['spec']}")
        else:
            lines.append("No parameter > 64 MB is fully replicated.")
        lines += ["", "Top per-device tensors:", "",
                  "| tensor | shape | dtype | full MB | per-dev MB | "
                  "spec |", "|---|---|---|---|---|---|"]
        for t in r["top_tensors"]:
            lines.append(
                f"| `{t['name']}` | {t['shape']} | {t['dtype']} | "
                f"{t['full_mb']} | {t['per_device_mb']} | "
                f"`{t['spec']}` |")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


if __name__ == "__main__":
    if len(sys.argv) > 1:
        run_child(sys.argv[1])
    else:
        res = run_all()
        p = write_md(res)
        print(json.dumps({n: {k: v for k, v in r.items()
                              if k != "top_tensors"}
                          for n, r in res.items()}, indent=1))
        print("wrote", p)
