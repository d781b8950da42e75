"""The yardstick of the Nemotron-H cell, on the CPU: the required
operations against a hand count, the weights module the train kind
reads through its one seam (every leaf named in full by ``top_shapes``,
``layer_shapes`` empty), and a whole run of the cell at the rehearsal
size, sound and with the timed path broken underneath.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

W = {"hidden_size": 8, "mamba_num_heads": 2, "mamba_head_dim": 4,
     "n_groups": 1, "ssm_state_size": 4, "conv_kernel": 4,
     "head_dim": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
     "router_num_experts": 8, "n_routed_experts": 2,
     "num_experts_per_tok": 2, "moe_intermediate_size": 6,
     "moe_shared_expert_intermediate_size": 10, "vocab_size": 32,
     "hybrid_override_pattern": "ME*", "num_hidden_layers": 3,
     "time_step_min": 0.001, "time_step_max": 0.1,
     "time_step_floor": 1e-4}


def test_required_flops_against_a_hand_count():
    from chipbench.opsbytes import nemotron_h_flops as f

    # M: in_proj 8 x (8 z + 8 x + 4 B + 4 C + 2 dt), out_proj 8 x 8
    mamba = 8 * 26 + 64
    # *: q 8x8, k and v 8x4 each, o 8x8
    attn = 64 + 32 + 32 + 64
    # E: router 8x8, shared 2 x 8x10, a routed expert 2 x 8x6 met by
    # 2 of 8 x 2 held = half a token
    moe = 64 + 160 + 0.5 * 96
    assert f.matmul_params(W, 3) == mamba + attn + moe + 8 * 32
    # the recurrence: 4 * heads * p * n operations a token
    assert f.recurrence_flops(W, 3, 10) == 10 * 4 * 2 * 4 * 4
    assert f.attention_flops(W, 3, 10, 5.5) == 2 * 2 * 10 * 5.5 * 8
    assert f.forward(W, 3, 10, 5.5) == 2 * f.matmul_params(W, 3) * 10 \
        + 1280 + 1760
    assert f.train_step(W, 3, 2, 5) == 3 * f.forward(W, 3, 10, 3.0)
    # a cut pattern counts the blocks it keeps
    assert f.matmul_params(W, 1) == mamba + 8 * 32


def test_weights_name_every_leaf_and_repeat_from_the_seed():
    from chipbench.weights import nemotron_h as weights

    shapes = weights.top_shapes(W)
    assert weights.layer_shapes(W) == {} and \
        weights.make_layer(W, 5, 0) == {}
    assert shapes["backbone.layers.0.mixer.in_proj.weight"] == (8, 26)
    assert shapes["backbone.layers.0.mixer.conv_weight"] == (16, 4)
    assert shapes["backbone.layers.1.mixer.gate_weight"] == (8, 8)
    assert shapes["backbone.layers.1.mixer.experts.w1"] == (2, 8, 6)
    assert shapes["backbone.layers.1.mixer.shared_experts.w2"] == (1, 10, 8)
    assert shapes["backbone.layers.2.mixer.k_proj.weight"] == (8, 4)
    assert not any("correction_bias" in n for n in shapes)
    assert weights.n_params(W, 3) == sum(
        int(np.prod(s)) for s in shapes.values())
    big = (1 << 31) + 12345  # the driver's seeds pass 2**31
    a, b = weights.make_all(W, big, 3), weights.make_all(W, big, 3)
    other = weights.make_all(W, big + 1, 3)
    assert set(a) == set(shapes)
    for n, v in a.items():
        assert v.dtype == jnp.bfloat16 and v.shape == shapes[n]
        assert bool(jnp.all(v == b[n])), n
    n = "backbone.layers.0.mixer.in_proj.weight"
    assert not bool(jnp.all(a[n] == other[n]))
    # the Mamba-2 leaves as the configuration's `assumed` states them
    f32 = jnp.float32
    A_log = a["backbone.layers.0.mixer.A_log"].astype(f32)
    assert bool(jnp.all((A_log >= 0) & (A_log <= np.log(16) + 0.02)))
    dt = jax.nn.softplus(a["backbone.layers.0.mixer.dt_bias"].astype(f32))
    assert bool(jnp.all((dt > 5e-5) & (dt < 0.11)))
    assert bool(jnp.all(a["backbone.layers.0.mixer.D"] == 1))
    taps = a["backbone.layers.0.mixer.conv_weight"].astype(f32)
    assert float(jnp.abs(taps).max()) <= 0.5
    with pytest.raises(ValueError):
        weights.make_all(W, 1, 2)


def _rehearse(fault):
    from chipbench import run as harness

    args = types.SimpleNamespace(
        seed=(1 << 31) + 11, seconds=0.3, trace=0, rehearse_cpu=True,
        mode="run", fault=fault)
    workload = "nemotron3-nano-train-8k"
    _, cell, config, traffic, limits = harness.find_cell(workload)
    ctx = harness.Ctx(args, cell, config, traffic, limits,
                      jax.devices()[:1])
    return ctx.part("kind").run(ctx)


@pytest.mark.parametrize("fault", [None, "frozen_state", "half_batch"])
def test_the_cell_rehearses_and_a_broken_path_is_not_correct(
        fault, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    res = _rehearse(fault)
    assert res["correct"] is (fault is None), res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
