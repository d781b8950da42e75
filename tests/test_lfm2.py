"""``Lfm2MoeForCausalLM`` against the plain reference
(``chipbench/reference/lfm2_moe.py``).

Seeded weights from the benchmark's own generator on both sides;
conftest pins matmul precision ``highest``. In float32 the two differ by
the order of float32 sums only (grouped products against a masked loop,
one softmax against a head at a time), so the tolerances are a few
float32 roundings of sums over 64 tokens: 2e-5 relative on the loss,
2e-4 of a leaf's largest entry on its gradient. A routing choice flipped
by such a rounding would show as a gap a thousand times that. In bf16
the program rounds every activation to 8 bits of mantissa where the
reference, given the same bf16-rounded weights, keeps float32: the
tolerances there are set from that rounding (the bf16 test says how).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench.reference import lfm2_moe as ref
from chipbench.weights import lfm2_moe as weights
from paddle_tpu import distributed as dist, optimizer as opt
from paddle_tpu.core.functional import functional_call
from paddle_tpu.distributed import moe
from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.models import lfm2
from paddle_tpu.trainer import TrainStep

CUT = ("conv", "full_attention", "conv", "conv", "conv")


def _widths(layer_types=CUT, dense=1, held=(2, 4)):
    return {
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16, "layer_types": list(layer_types),
        "num_hidden_layers": len(layer_types), "num_dense_layers": dense,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1e6,
        "router_num_experts": 8, "num_experts": held[1],
        "held_experts_first": held[0], "num_experts_per_tok": 3,
        "routed_scaling_factor": 1}


def _model(w):
    pt.seed(0)
    return Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        intermediate_size=w["intermediate_size"],
        moe_intermediate_size=w["moe_intermediate_size"],
        layer_types=tuple(w["layer_types"]),
        num_dense_layers=w["num_dense_layers"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        num_experts=w["router_num_experts"],
        held_experts=(w["held_experts_first"], w["num_experts"]),
        num_experts_per_tok=w["num_experts_per_tok"],
        rope_theta=w["rope_theta"], use_flash_attention=False))


def _params(w, seed=7):
    """The benchmark's leaves in float32; matrices five times their
    0.02, so that at toy widths every layer matters to the loss."""
    return {n: 5.0 * v.astype(jnp.float32) if v.ndim > 1
            else v.astype(jnp.float32)
            for n, v in weights.make_all(
                w, seed, w["num_hidden_layers"]).items()}


def _both(w, params, ids, model=None):
    model = model or _model(w)
    prog = jax.jit(jax.value_and_grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=ids)))
    plain = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(
        {n: v.astype(jnp.float32) for n, v in p.items()}, ids, w,
        w["num_hidden_layers"])))
    return prog(params), plain(params)


@pytest.mark.parametrize("layer_types,dense", [
    (("conv",), 1), (("full_attention",), 1), (("conv",), 0),
    (("full_attention",), 0), (CUT, 1)],
    ids=["conv_dense", "attention_dense", "conv_sparse",
         "attention_sparse", "the_cut"])
def test_loss_and_gradients_match_the_reference(layer_types, dense):
    w = _widths(layer_types, dense)
    model = _model(w)
    params = _params(w)
    assert set(params) == {n for n, _ in model.named_parameters()}
    assert weights.layer_shapes(w) == {} and weights.n_params(
        w, len(layer_types)) == sum(v.size for v in params.values())
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                             w["vocab_size"])
    (got_l, got), (want_l, want) = _both(w, params, ids, model)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-5)
    for n in params:
        assert float(jnp.abs(want[n]).max()) > 0, n
        np.testing.assert_allclose(
            got[n], want[n], rtol=0, err_msg=n,
            atol=2e-4 * float(jnp.abs(want[n]).max()) + 1e-9)


def test_bf16_program_stays_near_the_float32_reference():
    """The program on bf16 leaves and activations against the reference
    in float32 on the same bf16-rounded leaves, at the benchmark's own
    0.02. A bf16 rounding is 2^-9 relative: the loss, within a percent
    of ln(128) whatever the weights, agrees to 1e-5 (tolerance 1e-4),
    and a leaf's gradient, a sum over 128 tokens of products of rounded
    activations, to 2% of its norm on this seed (tolerance 5%). The seed
    is one on which no top-3 choice flips between the two precisions:
    seeds 11 and 12 flip a few and read 8% to 14% on a router's
    ``gate_weight``, whose gradient jumps with a choice. That is the
    tolerance's reason, and why the cell on the chip reads its routed
    leaves higher than its dense ones (PERF.md section 2)."""
    w = _widths()
    params = weights.make_all(w, 13, 5)
    assert all(v.dtype == jnp.bfloat16 for v in params.values())
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                             w["vocab_size"])
    (got_l, got), (want_l, want) = _both(w, params, ids)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-4)
    for n in params:
        g, r = got[n].astype(jnp.float32), want[n].astype(jnp.float32)
        gap = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert gap < 0.05, (n, gap)


def test_short_convolution_against_a_step_by_step_loop():
    """``Lfm2ShortConv`` against a Python loop over time that keeps the
    last two products in a cache, as a decoder would: the taps are
    causal, tap 2 weighs the current step, zeros stand before the
    sequence, and nothing is activated."""
    pt.seed(1)
    conv = lfm2.Lfm2ShortConv(Lfm2MoeConfig.tiny())
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 10, 64))
    w_in, w_out = conv.in_proj.weight.value, conv.out_proj.weight.value
    taps = conv.conv_weight.value
    cache = [jnp.zeros((2, 64))] * 2  # (B * x) at t - 2 and t - 1
    want = []
    for t in range(10):
        B, C, x = jnp.split(u[:, t] @ w_in, 3, axis=-1)
        cache.append(B * x)
        z = sum(taps[:, j] * cache[-3 + j] for j in range(3))
        want.append((C * z) @ w_out)
    np.testing.assert_allclose(conv(u), jnp.stack(want, axis=1), atol=1e-5)
    # an input at step 6 moves the outputs of steps 6, 7, 8 and no other
    moved = np.abs(np.asarray(conv(u.at[:, 6].add(1.0)) - conv(u)))
    assert (moved.max(axis=(0, 2)) > 0).tolist() == [
        t in (6, 7, 8) for t in range(10)]


def test_qk_norm_comes_before_rope_and_a_planted_swap_fails(monkeypatch):
    """Norm weights that differ along the head make the order matter:
    RoPE mixes entry j with entry j + d/2, the norm's weight scales them
    apart. The program agrees with the reference; with RoPE moved in
    front of the norm it does not."""
    w = dict(_widths(("full_attention",), 1), rope_theta=10.0)
    params = _params(w)
    for n in list(params):
        if "layernorm" in n:
            params[n] = jnp.linspace(0.5, 2.0, params[n].size)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0,
                             w["vocab_size"])
    (got_l, got), (want_l, want) = _both(w, params, ids)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-5)
    n = "model.layers.0.self_attn.q_layernorm.weight"
    np.testing.assert_allclose(got[n], want[n], rtol=0,
                               atol=2e-4 * float(jnp.abs(want[n]).max()))

    def rotate_then_norm(self, q, k):  # the planted swap
        _, s, _, d = q.shape
        q, k = lfm2.apply_rope(q, k, *lfm2.rope_frequencies(
            d, s, self.config.rope_theta))
        return self.q_layernorm(q), self.k_layernorm(k)

    monkeypatch.setattr(lfm2.Lfm2Attention, "norm_then_rotate",
                        rotate_then_norm)
    (bad_l, bad), _ = _both(w, params, ids)
    # a hundred times what the sound comparison allows
    assert abs(float(bad_l) - float(want_l)) > 100 * 2e-5 * float(want_l)
    assert float(jnp.abs(bad[n] - want[n]).max()) \
        > 100 * 2e-4 * float(jnp.abs(want[n]).max())


def test_the_four_shares_of_a_sparse_layer_add_up_to_the_uncut_reference():
    """One sparse layer cut as the deployment cuts it, 32 experts four
    ways: the parts that the shares holding experts 0-7, 8-15, 16-23 and
    24-31 give add up to what the reference gives for the whole layer
    (nothing is computed on every chip alike: no shared expert), and
    each share is the reference given the same share."""
    E, K, M, H = 32, 4, 32, 24
    pt.seed(0)

    def layer(held):
        return moe.HeldExpertsMoE(M, E, H, K, held=held, activation="silu",
                                  init_std=0.3, gated=True, norm_eps=1e-6)

    whole = layer((0, E))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, M))
    widths = {"num_experts_per_tok": K, "routed_scaling_factor": 1}

    def plain(part, first):
        lp = {"feed_forward." + n: v.value
              for n, v in part.named_parameters()}
        return ref.sparse_mlp(x, lp, {**widths,
                                      "held_experts_first": first})

    want = plain(whole, 0)
    total = jnp.zeros_like(want)
    for first in range(0, E, 8):
        part = layer((first, 8))
        part.gate_weight.value = whole.gate_weight.value
        for n in ("w1", "w2", "w3"):
            getattr(part.experts, n).value = getattr(
                whole.experts, n).value[first:first + 8]
        y = part(x)
        np.testing.assert_allclose(y, plain(part, first), atol=2e-5)
        assert 0 < int(part.last_counts["rows_held"]) < 48 * K
        total = total + y
    # sums of 4 experts' outputs of order 1: 2e-5 is float32 rounding
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(whole(x), want, atol=2e-5)
    assert int(whole.last_counts["rows_held"]) == 48 * K


def test_trains_through_train_step_and_reports_routing_counts():
    """bf16 parameters, fp32 masters, through ``TrainStep.run`` as the
    benchmark drives it; the tied embedding is one leaf, the expert bias
    none, and the step's routing counts reach telemetry."""
    from paddle_tpu import observability as obs

    prev = pt.flags.flag("telemetry")
    pt.flags.set_flags({"FLAGS_telemetry": True})
    try:
        pt.seed(0)
        model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
            layer_types=CUT, num_dense_layers=1,
            use_flash_attention=False))
        model.to(pt.bfloat16)
        names = [n for n, _ in model.named_parameters()]
        assert not any("bias" in n or "lm_head" in n for n in names)
        assert sum("embed_tokens" in n for n in names) == 1
        mesh = dist.build_mesh(devices=jax.devices()[:1])
        ts = TrainStep(
            model, opt.AdamW(3e-3, multi_precision=True,
                             grad_clip=opt.ClipGradByGlobalNorm(1.0)),
            mesh, telemetry=obs.TrainTelemetry(sample_every=2))
        ids = np.random.default_rng(0).integers(
            0, 256, (2, 64), dtype=np.int32)
        losses = [float(ts.run({"input_ids": ids, "labels": ids}))
                  for _ in range(6)]
        assert losses[-1] < losses[0]
        sample = ts.telemetry.last_sample
        # 4 sparse layers x 128 tokens x top-2
        assert sample["moe_rows_routed"] == 4 * 128 * 2
        assert 0 < sample["moe_rows_held"] < sample["moe_rows_routed"]
        assert sample["moe_rows_max"] * 16 >= sample["moe_rows_held"]
        # an even share is 32 rows and the floor one block of 256 an
        # expert: 4 sparse layers x 4 held experts each walk one
        assert sample["moe_rows_walked"] == 4 * 4 * 256
    finally:
        pt.flags.set_flags({"FLAGS_telemetry": prev})


def test_config_names_its_layers_and_refuses_what_it_cannot_build():
    cfg = Lfm2MoeConfig()
    assert (cfg.num_hidden_layers, cfg.head_dim, cfg.held_experts) == (
        24, 64, (0, 32))
    assert cfg.layer_types.count("full_attention") == 6
    assert cfg.layer_types[1:6] == CUT
    with pytest.raises(ValueError, match="conv or full_attention"):
        Lfm2MoeConfig(layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="num_dense_layers"):
        Lfm2MoeConfig(layer_types=("conv",), num_dense_layers=2)
