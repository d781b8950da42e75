"""The yardstick of the Phi-4-mini-flash cell, on the CPU: the required
operations against a hand count at the rehearsal size, the weights
module the train kind reads through its one seam (every leaf named in
full by ``top_shapes``, ``layer_shapes`` empty, one tied leaf), the new
metric files against the readers and ``opsbytes`` modules that exist,
and whole runs of the cell at the rehearsal size: sound, with the timed
path broken underneath, and the int8 control in the program's place.
"""

import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as harness

WORKLOAD = "phi4-mini-flash-train-8k"
NEW_METRICS = ("s6_device_ms.train", "s6_scan_device_ms.train",
               "gmu_device_ms.train", "diff_attn_device_ms.train",
               "s6_scan_fwd_roofline.train", "s6_scan_bwd_roofline.train")


def _rehearsal_widths():
    w = dict(harness.load_json(
        "configs", "phi-4-mini-flash-reasoning-train.json")["rehearsal"])
    assert w["head_dim"] == w["hidden_size"] // w["num_attention_heads"]
    return w


def test_required_flops_against_a_hand_count():
    from chipbench.opsbytes import phi4flash_flops as f

    w = _rehearsal_widths()
    # hidden 64, MLP 96, d_inner 128, dt_rank 4, state 8, 8/4 heads of 8
    mamba = 64 * 256 + 128 * (4 + 16) + 4 * 128 + 128 * 64
    attn = 64 * (64 + 2 * 32) + 64 * 64
    gmu = 64 * 128 + 128 * 64
    cross = 64 * 64 + 64 * 64
    mlp = 64 * 192 + 96 * 64
    # layers 0, 1, 4, 5, 6, 7 of 8: mamba, window, mamba, full, gmu, cross
    assert f.matmul_params(w, 6) == 2 * mamba + 2 * attn + gmu + cross \
        + 6 * mlp + 64 * 256
    assert f.matmul_params(w, 1) == mamba + mlp + 64 * 256
    # the recurrence: 4 * d_inner * n operations a token, two layers
    assert f.recurrence_flops(w, 6, 10) == 2 * 10 * 4 * 128 * 8
    # what the masks let through in a row of 128: the window's band (32
    # wide) once, the causal half twice (full, cross)
    band = 32 * 33 / 2 + (128 - 32) * 32
    half = 128 * 129 / 2
    assert f.visible_pairs(128, 32) == band
    assert f.visible_pairs(128, 0) == f.visible_pairs(128, 128) == half
    # a query pair: two maps, each scores at 8 and values at 16
    per_pair = 2 * (2 * 8 + 2 * 16)
    assert f.attention_flops(w, 6, 2, 128) == 2 * (band + 2 * half) \
        * 4 * per_pair
    assert f.forward(w, 6, 2, 128) == 2 * f.matmul_params(w, 6) * 256 \
        + f.recurrence_flops(w, 6, 256) + f.attention_flops(w, 6, 2, 128)
    assert f.train_step(w, 6, 2, 128) == 3 * f.forward(w, 6, 2, 128)


def test_the_cut_is_697m_parameters_and_14_bytes_each():
    from chipbench.weights import phi4flash as weights

    config = harness.load_json(
        "configs", "phi-4-mini-flash-reasoning-train.json")
    w = dict(config, head_dim=64)
    assert weights.n_params(w, 6) == config["bytes_reckoned"][
        "parameters"] == 697_094_272
    assert config["bytes_reckoned"]["state_bytes"] == 14 * 697_094_272
    by_kind = {kind: sum(int(np.prod(s)) for s, _ in
                         weights.layer_specs(w, kind).values())
               for kind in ("mamba", "attention", "gmu", "cross")}
    # ISSUE 36's table, a layer with its MLP and its two LayerNorms
    assert by_kind == {"mamba": 119_895_040, "attention": 98_322_304,
                       "gmu": 104_867_840, "cross": 91_766_144}


def test_weights_name_every_leaf_and_repeat_from_the_seed():
    from chipbench.weights import phi4flash as weights

    w = _rehearsal_widths()
    shapes = weights.top_shapes(w)
    assert weights.layer_shapes(w) == {} and \
        weights.make_layer(w, 5, 0) == {}
    assert shapes["model.embed_tokens.weight"] == (256, 64)
    assert not any("lm_head" in n for n in shapes)  # tied: one leaf
    assert shapes["model.layers.0.mixer.in_proj.weight"] == (64, 256)
    assert shapes["model.layers.0.mixer.x_proj.weight"] == (128, 20)
    assert shapes["model.layers.0.mixer.A_log"] == (128, 8)
    assert shapes["model.layers.1.mixer.Wqkv.weight"] == (64, 128)
    assert shapes["model.layers.3.mixer.subln.weight"] == (16,)
    assert shapes["model.layers.4.mixer.in_proj.weight"] == (64, 128)
    assert shapes["model.layers.5.mixer.Wq.bias"] == (64,)
    assert "model.layers.5.mixer.Wqkv.weight" not in shapes
    assert weights.n_params(w, 6) == sum(
        int(np.prod(s)) for s in shapes.values())
    big = (1 << 31) + 12345  # the driver's seeds pass 2**31
    a, b = weights.make_all(w, big, 6), weights.make_all(w, big, 6)
    other = weights.make_all(w, big + 1, 6)
    assert set(a) == set(shapes)
    for n, v in a.items():
        assert v.dtype == jnp.bfloat16 and v.shape == shapes[n]
        assert bool(jnp.all(v == b[n])), n
    n = "model.layers.0.mixer.in_proj.weight"
    assert not bool(jnp.all(a[n] == other[n]))
    # the Mamba-1 leaves as the configuration's `assumed` states them
    f32 = jnp.float32
    A_log = a["model.layers.0.mixer.A_log"].astype(f32)
    np.testing.assert_allclose(
        A_log, np.broadcast_to(np.log(np.arange(1, 9)), (128, 8)),
        rtol=1e-2)
    dt = jax.nn.softplus(a["model.layers.0.mixer.dt_proj.bias"].astype(f32))
    assert bool(jnp.all((dt > 5e-5) & (dt < 0.11)))
    assert bool(jnp.all(a["model.layers.0.mixer.D"] == 1))
    taps = a["model.layers.0.mixer.conv_weight"].astype(f32)
    assert float(jnp.abs(taps).max()) <= 0.5
    lam = a["model.layers.1.mixer.lambda_q1"].astype(f32)
    assert 0 < float(jnp.abs(lam).max()) < 0.6
    with pytest.raises(ValueError):
        weights.make_all(w, 1, 5)


def test_every_new_metric_file_names_a_reader_and_opsbytes_that_exist():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    reported = {m["name"] for m in harness.cell_metrics(
        bench, WORKLOAD, "per_layer")}
    from paddle_tpu.observability.spans import SCOPES

    for name in NEW_METRICS:
        spec = harness.load_json("metrics", name + ".json")
        assert name in reported and listed[name]["workloads"] == [WORKLOAD]
        for key in ("name", "layer", "unit", "better", "source", "moves",
                    "workloads"):
            assert spec[key] == listed[name][key], (name, key)
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        assert callable(reader.read)
        if "opsbytes" in spec["args"]:
            fn = importlib.import_module(
                "chipbench.opsbytes." + spec["args"]["opsbytes"])
            shapes = dict(_rehearsal_widths(), batch=2, sequence=128)
            ops, byts = fn.ops_bytes(shapes, 3)
            assert ops > 0 and byts > 0
            assert spec["args"]["bound"] == "bytes"
        for scope in spec["args"].get("scopes", ()):
            assert scope in SCOPES, (name, scope)
    # the cell reports the whole-step metrics too, and no other cell's
    assert {"mfu.train", "device_idle_share.train", "step_wall_ms.train",
            "train_step_device_ms.train"} <= reported
    assert not any(n.startswith(("ssm_", "moe_", "flash_"))
                   for n in reported)


def test_s6_scan_bytes_against_a_hand_count():
    from chipbench.opsbytes import s6_scan_bwd, s6_scan_fwd

    shapes = dict(_rehearsal_widths(), batch=2, sequence=128)
    # d_inner 128, n 8, chunk 32: u bf16, delta f32, y bf16; B, C bf16;
    # the float32 states before each of 4 chunks
    fwd = 2 * 128 * (128 * 8 + 2 * 8 * 2) + 4 * 2 * 4 * 8 * 128
    assert s6_scan_fwd.ops_bytes(shapes, 1) == (4.0 * 2 * 128 * 128 * 8, fwd)
    # the same again in, the cotangent of y in; those of u, delta, B, C
    # and A out
    bwd = fwd + 2 * 128 * (128 * 6 + 2 * 8 * 2) + 4 * 128 * 8
    assert s6_scan_bwd.ops_bytes(shapes, 2) == (
        2 * 12.0 * 2 * 128 * 128 * 8, 2 * bwd)


def _rehearse(fault=None, mode="run"):
    args = types.SimpleNamespace(
        seed=(1 << 31) + 11, seconds=0.3, trace=0, rehearse_cpu=True,
        mode=mode, fault=fault)
    _, cell, config, traffic, limits = harness.find_cell(WORKLOAD)
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


def test_the_int8_control_reads_over_the_rehearsal_limits():
    res = _rehearse(mode="control")
    assert res["correct"] is False, res["compared"]
    over = [n for n, c in res["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over == ["grad_weighted_gap"], res["compared"]
