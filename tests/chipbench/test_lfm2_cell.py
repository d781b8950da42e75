"""The yardstick of the LFM2-8B-A1B cell, on the CPU: the required
operations against a hand count, the configuration's cut against the
published catalog row, the weights module the train kind reads through
its one seam (every leaf named in full by ``top_shapes``,
``layer_shapes`` empty, one tied leaf), the metric files against the
readers and scopes that exist, and whole runs of the cell at the
rehearsal size: sound, with the timed path broken underneath, and the
int8 control in the program's place.

At the cell's own size none of ``kinds/train.py:compare``'s four numbers
separates the int8 control (PERF.md sections 2 and 7): the limits file
holds ``delta_gap`` alone there; the rehearsal's limits, held here, do
separate it at the toy size.
"""

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as harness

WORKLOAD = "lfm2-8b-a1b-train-8k"
CONFIG = "lfm2-8b-a1b-train.json"
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"]
NEW_METRICS = ("shortconv_device_ms.train", "shortconv_mix_device_ms.train",
               "swiglu_experts_device_ms.train",
               "swiglu_experts_rows_share.train",
               "swiglu_experts_load_max_over_mean.train",
               "qknorm_attn_device_ms.train")


def _rehearsal_widths():
    w = dict(harness.load_json("configs", CONFIG)["rehearsal"])
    assert w["head_dim"] == w["hidden_size"] // w["num_attention_heads"]
    return w


def test_required_flops_against_a_hand_count():
    from chipbench.opsbytes import lfm2_moe_flops as f

    w = _rehearsal_widths()
    # hidden 64, dense MLP 96, experts of 32, 4/2 heads of 16
    conv = 64 * 192 + 64 * 64
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64
    dense = 3 * 64 * 96
    # router 64 x 8; top-2, 4 of 8 held: one expert's three matrices
    sparse = 64 * 8 + 1.0 * 3 * 64 * 32
    head = 64 * 256
    # conv+dense, attention+sparse, three conv+sparse
    assert f.matmul_params(w, 5) == 4 * conv + attn + dense + 4 * sparse \
        + head
    assert f.matmul_params(w, 1) == conv + dense + head
    assert f.attention_flops(w, 5, 10, 5.5) == 2 * 2 * 10 * 5.5 * 64
    assert f.attention_flops(w, 1, 10, 5.5) == 0
    assert f.forward(w, 5, 10, 5.5) == 2 * f.matmul_params(w, 5) * 10 + 14080
    assert f.train_step(w, 5, 2, 5) == 3 * f.forward(w, 5, 10, 3.0)
    # at the published widths: 216.3M multiply-adds a token at mean
    # context 4096 (ISSUE 38's count), 121.9M of them, 56%, in the four
    # sparse layers (ISSUE 38's own parts add up to that, not to the
    # 149.5M it states: experts 44.0, convolutions 50.3, attention 27.3)
    full = dict(harness.load_json("configs", CONFIG))
    a_token = f.forward(full, 5, 1, 4096) / 2
    assert round(a_token / 1e6, 1) == 216.3
    sparse_layers = a_token - (2048 * 4 * 2048 + 3 * 2048 * 7168
                               + 2048 * 16384)
    assert round(sparse_layers / 1e6, 1) == 121.9
    assert round(f.train_step(full, 5, 1, 8192) / 1e12, 1) == 10.6


def test_the_cut_keeps_every_published_width_and_507_8m_parameters():
    from chipbench.weights import lfm2_moe as weights

    bench, cell, config, _, _ = harness.find_cell(WORKLOAD)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "chipbench/configs/" + CONFIG
    assert (cell["traffic"], cell["chips"]) == ("train-8k", 1)
    assert list(config["published"]) == list(config["reduced_why"]) \
        == entry["reduced"] == REDUCED
    # the catalog row's numbers, every one as published but the reduced
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    for key, value in published.items():
        if key in REDUCED:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    pub_types = config["published"]["layer_types"]
    assert len(pub_types) == 24 and pub_types.count("full_attention") == 6
    assert config["layer_types"] == pub_types[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["router_num_experts"],
            config["held_experts_first"], config["vocab_size"]) == (
        5, 1, 8, 32, 0, 16384)
    assert "4 chips share each layer" in config["deployment"]
    w = dict(config)
    reckoned = config["bytes_reckoned"]
    assert weights.n_params(w, 5) == reckoned["parameters"] == 507_820_160
    assert reckoned["state_bytes"] == 14 * 507_820_160
    by_layer = [sum(int(np.prod(s)) for s, _ in
                    weights.layer_specs(w, i).values()) for i in range(5)]
    # ISSUE 38's table: dense conv layer, sparse attention, sparse conv x 3
    assert by_layer == [60_827_648, 98_635_904] + [104_933_376] * 3
    assert 2048 * 16384 + sum(by_layer) + 2048 == 507_820_160


def test_weights_name_every_leaf_and_repeat_from_the_seed():
    from chipbench.weights import lfm2_moe as weights

    w = _rehearsal_widths()
    shapes = weights.top_shapes(w)
    assert weights.layer_shapes(w) == {} and \
        weights.make_layer(w, 5, 0) == {}
    assert shapes["model.embed_tokens.weight"] == (256, 64)
    assert not any("lm_head" in n or "bias" in n for n in shapes)
    assert shapes["model.layers.0.conv.in_proj.weight"] == (64, 192)
    assert shapes["model.layers.0.conv.conv_weight"] == (64, 3)
    assert shapes["model.layers.0.feed_forward.w1.weight"] == (64, 96)
    assert shapes["model.layers.1.self_attn.k_proj.weight"] == (64, 32)
    assert shapes["model.layers.1.self_attn.q_layernorm.weight"] == (16,)
    assert shapes["model.layers.1.feed_forward.gate_weight"] == (64, 8)
    assert shapes["model.layers.4.feed_forward.experts.w3"] == (4, 64, 32)
    assert "model.layers.1.conv.in_proj.weight" not in shapes
    assert "model.layers.1.feed_forward.w1.weight" not in shapes
    assert weights.n_params(w, 5) == sum(
        int(np.prod(s)) for s in shapes.values())
    big = (1 << 31) + 12345  # the driver's seeds pass 2**31
    a, b = weights.make_all(w, big, 5), weights.make_all(w, big, 5)
    other = weights.make_all(w, big + 1, 5)
    assert set(a) == set(shapes)
    for n, v in a.items():
        assert v.dtype == jnp.bfloat16 and v.shape == shapes[n]
        assert bool(jnp.all(v == b[n])), n
    n = "model.layers.0.conv.in_proj.weight"
    assert not bool(jnp.all(a[n] == other[n]))
    taps = a["model.layers.0.conv.conv_weight"].astype(jnp.float32)
    assert 0.3 < float(jnp.abs(taps).max()) <= 3 ** -0.5 + 1e-2
    assert bool(jnp.all(a["model.layers.1.self_attn.k_layernorm.weight"]
                        == 1))
    with pytest.raises(ValueError):
        weights.make_all(w, 1, 4)


def test_every_metric_file_names_a_reader_and_scopes_that_exist():
    bench = harness.find_cell(WORKLOAD)[0]
    from paddle_tpu.observability.spans import SCOPES, SPANS

    listed = {m["name"]: m for m in
              harness.cell_metrics(bench, WORKLOAD, "per_layer")}
    # the cell's own six and the four with no list (idle, mfu, two steps)
    assert set(NEW_METRICS) <= set(listed) and len(listed) == 10
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW_METRICS)
    for name in NEW_METRICS:
        spec = harness.load_json("metrics", name + ".json")
        assert {k: spec[k] for k in listed[name]} == listed[name]
        assert spec["name"] == name and spec["workloads"] == [WORKLOAD]
        assert (spec["layer"], spec["moves"]) == (
            "model step", "train_tokens_per_s")
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        assert callable(reader.read)
        for scope in spec["args"].get("scopes", ()):
            assert scope in SCOPES, (name, scope)
        for other in spec["args"].get("kernels_of", ()):
            assert "kernel" in harness.load_json(
                "metrics", other + ".json")["args"]
        if "ratio" in spec["args"]:
            assert set(spec["args"]["ratio"]) <= set(
                SPANS["pt.train.sample_fetch"][2])
            assert name in SPANS["pt.train.sample_fetch"][3]


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


# ---- the routing's collapse on uniform ids (PERF.md section 6, PR 38):
# on the chip the cell's own TrainStep at AdamW 1e-4 sends every token
# of a layer to the same four experts from about step 230. Uniform ids
# give nothing to learn but a constant output, AdamW moves every weight
# by its rate a step towards it whatever the gradient's size, and once
# the hidden states of all tokens are one vector the router gives one
# answer. The second witness that this is the mathematics and not the
# program: the plain float32 reference, which imports nothing of
# paddle_tpu, does the same. The toy needs a larger dose (rate x steps
# about 0.1, against 0.02 at the cell's size: 256 tokens a step give
# AdamW a noisier direction), so the rates here are ten times the
# cell's: 1e-3 collapses by step 100 where 1e-4 holds (and collapses by
# step 1550, my sandbox, PR 38), as 1e-4 against 1e-5 on the chip.
COLLAPSE_STEPS = 150


def _fullest_over_mean_reference(rate):
    """Fullest expert over the mean, over ALL the router's experts and
    by sparse layer, after COLLAPSE_STEPS AdamW steps of the plain
    reference on fresh uniform ids."""
    from chipbench.reference import lfm2_moe as ref
    from chipbench.weights import lfm2_moe as weights

    config = harness.load_json("configs", CONFIG)
    w, hp = _rehearsal_widths(), dict(config["trainer"], learning_rate=rate)
    layers = w["num_hidden_layers"]
    p = {n: v.astype(jnp.float32)
         for n, v in weights.make_all(w, 11, layers).items()}

    @jax.jit
    def step(p, hist_m, hist_v, ids, t):
        g = jax.grad(lambda p: ref.lm_loss(p, ids, w, layers))(p)
        scale = ref.clip_scale({n: jnp.sum(v * v) for n, v in g.items()},
                               hp["clip_global_norm"])
        b1, b2 = hp["beta1"], hp["beta2"]
        m = {n: b1 * hist_m[n] + (1 - b1) * scale * g[n] for n in p}
        v = {n: b2 * hist_v[n] + (1 - b2) * (scale * g[n]) ** 2 for n in p}
        new = {n: p[n] - rate * (
            m[n] / (1 - b1 ** t) / (jnp.sqrt(v[n] / (1 - b2 ** t))
                                    + hp["epsilon"])
            + hp["weight_decay"] * p[n]) for n in p}
        return new, m, v

    @jax.jit
    def fullest(p, ids):
        top, per = ref.split_params(p, layers)
        x, out = top["model.embed_tokens.weight"][ids], []
        for i, lp in enumerate(per):
            if i >= w["num_dense_layers"]:
                op = ref.attention if w["layer_types"][i] == \
                    "full_attention" else ref.short_conv
                h = x + op(ref._rms(x, lp["operator_norm.weight"],
                                    w["norm_eps"]), lp, w)
                idx, _ = ref.route(ref._rms(h, lp["ffn_norm.weight"],
                                            w["norm_eps"]), lp, w)
                counts = jnp.bincount(idx.ravel(),
                                      length=w["router_num_experts"])
                out.append(jnp.max(counts) / jnp.mean(counts))
            x = ref.decoder_layer(x, lp, w, i)
        return jnp.stack(out)

    rng = np.random.default_rng(11)
    zeros = {n: jnp.zeros_like(v) for n, v in p.items()}
    m, v = zeros, zeros
    for t in range(1, COLLAPSE_STEPS + 1):
        ids = jnp.asarray(rng.integers(0, w["vocab_size"], (2, 128)),
                          jnp.int32)
        p, m, v = step(p, m, v, ids, float(t))
    return np.asarray(fullest(p, ids))


def _fullest_over_mean_program(rate):
    """The same from the program's own counters (``step_counters()``
    through telemetry): fullest HELD expert over the held experts' mean,
    worst layer; None where the held experts got no row at all."""
    from paddle_tpu import distributed as dist, observability as obs, \
        optimizer as opt
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.trainer import TrainStep

    args = types.SimpleNamespace(
        seed=11, seconds=0.3, trace=0, rehearse_cpu=True, mode="run",
        fault=None)
    _, cell, config, traffic, limits = harness.find_cell(WORKLOAD)
    ctx = harness.Ctx(args, cell, config, traffic, limits,
                      jax.devices()[:1])
    w, hp = ctx.widths(), config["trainer"]
    model = ctx.part("program").build_model(ctx, w["num_hidden_layers"])
    ts = TrainStep(
        model, opt.AdamW(
            learning_rate=rate, beta1=hp["beta1"], beta2=hp["beta2"],
            epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
            multi_precision=True,
            grad_clip=opt.ClipGradByGlobalNorm(hp["clip_global_norm"])),
        dist.build_mesh(devices=ctx.devices), DistributedStrategy(),
        telemetry=obs.TrainTelemetry(sample_every=COLLAPSE_STEPS))
    batches = ctx.generator().Batches(ctx.sizes(), w["vocab_size"], 11)
    for _ in range(COLLAPSE_STEPS):
        ids = batches.next()
        ts.run({"input_ids": ids, "labels": ids})
    s = ts.telemetry.last_sample
    if not s["moe_rows_held"]:
        return None
    sparse = w["num_hidden_layers"] - w["num_dense_layers"]
    return w["num_experts"] * sparse * s["moe_rows_max"] \
        / s["moe_rows_held"]


def test_the_plain_reference_collapses_its_routing_on_uniform_ids():
    # 8 experts, top-2: every token on the same two reads 8 / 2 = 4
    e, k = 8, 2
    held = _fullest_over_mean_reference(1e-4)
    assert held.shape == (4,) and held.max() < 2.0, held
    gone = _fullest_over_mean_reference(1e-3)
    assert gone.min() > 0.9 * e / k, gone


def test_the_program_collapses_its_routing_as_the_reference_does(
        monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert _fullest_over_mean_program(1e-4) < 1.6
    # all rows on one or two of the 4 held experts of a layer (4 or 2
    # times their mean), or on none of them
    gone = _fullest_over_mean_program(1e-3)
    assert gone is None or gone > 1.9, gone
