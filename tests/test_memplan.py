"""AOT scale-proof tests (SURVEY §6 north star).

The 7B plan runs for real in a subprocess (own
--xla_force_host_platform_device_count=8); the meta-init machinery it
rides on is unit-tested here directly. The 70B/128-device plan is too
slow for the suite — `python benchmarks/memplan.py` produces it into
MEMPLAN.md (committed artifact).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_meta_init_builds_abstract_params():
    import paddle_tpu as pt
    from paddle_tpu.core.meta import materialize, meta_init
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    with meta_init():
        model = LlamaForCausalLM(LlamaConfig.tiny())
    vals = [p.value for _, p in model.named_parameters()]
    assert vals and all(isinstance(v, jax.ShapeDtypeStruct) for v in vals)
    # to(dtype) recasts abstract placeholders
    model.to(pt.bfloat16)
    assert all(p.value.dtype == jnp.bfloat16
               for _, p in model.named_parameters())
    model.to(pt.float32)
    # materialize runs the kept init_fns → a runnable model
    materialize(model, seed=0)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)))
    loss = model(ids, labels=ids)
    assert bool(jnp.isfinite(loss))


def test_abstract_train_step_lowers_and_fits():
    """TrainStep(abstract=True) lowers/compiles the full ZeRO-3 step from
    a meta model; memory_analysis is readable and run() refuses."""
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core.meta import meta_init
    from paddle_tpu.distributed.strategy import (
        DistributedStrategy,
        HybridConfig,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.trainer import TrainStep

    with meta_init():
        model = LlamaForCausalLM(
            LlamaConfig.tiny(use_flash_attention=False))
    mesh = dist.build_mesh(fsdp=2, tp=2)
    strategy = DistributedStrategy()
    strategy.hybrid_configs = HybridConfig(sharding_degree=2, mp_degree=2)
    strategy.sharding = True
    strategy.sharding_configs.stage = 3
    ts = TrainStep(model, opt.AdamW(1e-3, multi_precision=True), mesh,
                   strategy, abstract=True)
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    compiled = ts.lower({"input_ids": ids, "labels": ids}).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 0
    with pytest.raises(RuntimeError, match="abstract"):
        ts.run({"input_ids": None, "labels": None})


def test_memplan_7b_fits_v5p():
    """The real 7B plan: ZeRO-3 x tp2 x sep2 on a virtual 8-device mesh
    must fit v5p HBM with nothing large replicated."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "memplan.py"), "7b"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    plan = json.loads(
        [l for l in r.stdout.splitlines() if l.startswith("{")][-1])
    assert plan["fits_v5p"], plan
    assert plan["params_b"] > 6.5e9
    assert plan["replicated_over_64mb"] == [], plan["replicated_over_64mb"]
    # ZeRO-3: per-device argument bytes must be well under params*14/n —
    # replication of params or moments would push it over
    full_state_gb = plan["params_b"] * 14 / 1024**3
    assert plan["xla_argument_gb_per_device"] < full_state_gb / 2
