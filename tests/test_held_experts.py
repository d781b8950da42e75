"""One chip's share of an expert layer (distributed/moe.py).

``HeldExpertsMoE`` is told which experts it holds, routes over all of
them and computes its own experts' part. Everything is float32 here
(conftest pins matmul precision ``highest``), so the tolerances are a
few float32 roundings; the plain reference is
``chipbench/reference/nemotron_h.py``, which loops over the held experts
with a mask.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench.reference import nemotron_h as ref
from paddle_tpu.distributed import moe

E, K, M, H, SH = 16, 3, 32, 24, 40   # experts, top-k, widths
WIDTHS = {"num_experts_per_tok": K, "norm_topk_prob": True,
          "routed_scaling_factor": 2.5}


def _layer(held, seed=0):
    pt.seed(seed)
    return moe.HeldExpertsMoE(M, E, H, K, held=held, shared_hidden=SH,
                              routed_scale=2.5, init_std=0.3)


def _ref_params(layer, first):
    p = {n: v.value for n, v in layer.named_parameters()}
    lp = {"mixer." + n: v for n, v in p.items()}
    lp["mixer.e_score_correction_bias"] = \
        layer._buffers["e_score_correction_bias"]
    return lp, {**WIDTHS, "held_experts_first": first}


def _x(t=40, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, M))


def test_shares_add_up_to_the_uncut_layer():
    """The 4 shares' routed parts plus the shared expert counted once
    are the uncut 16-expert layer of the reference."""
    whole = _layer((0, E))
    x = _x()
    lp, w = _ref_params(whole, 0)
    want = ref.moe_mixer(x, lp, w)
    shared = whole.shared_experts(x.reshape(1, -1, M))[0].reshape(x.shape)
    total = shared
    for first in range(0, E, 4):
        part = _layer((first, 4))
        part.gate_weight.value = whole.gate_weight.value
        part.experts.w1.value = whole.experts.w1.value[first:first + 4]
        part.experts.w2.value = whole.experts.w2.value[first:first + 4]
        part.shared_experts.w1.value = whole.shared_experts.w1.value
        part.shared_experts.w2.value = whole.shared_experts.w2.value
        total = total + part(x) - shared
        # and each share is the reference given the same share
        lp_s, w_s = _ref_params(part, first)
        np.testing.assert_allclose(part(x), ref.moe_mixer(x, lp_s, w_s),
                                   atol=2e-5)
    # sums of 3 experts' outputs of order 1: 2e-5 is float32 rounding
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(whole(x), want, atol=2e-5)


@pytest.mark.parametrize("block_rows", [8, 16, 256],
                         ids=["many_blocks", "few_blocks", "one_block"])
def test_gradients_match_the_reference(block_rows):
    """Loss and every leaf's gradient, whatever the number of blocks an
    expert's rows are walked in."""
    layer = _layer((4, 4))
    layer.block_rows = block_rows
    x = _x(t=48)
    params = {n: v.value for n, v in layer.named_parameters()}
    names = {"gate_weight": "mixer.gate_weight",
             "experts.w1": "mixer.experts.w1",
             "experts.w2": "mixer.experts.w2",
             "shared_experts.w1": "mixer.shared_experts.w1",
             "shared_experts.w2": "mixer.shared_experts.w2"}
    probe = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def prog(p, x):
        from paddle_tpu.core.functional import functional_call

        return jnp.sum(functional_call(layer, p, x) * probe)

    def plain(p, x):
        lp = {names[n]: v for n, v in p.items()}
        return jnp.sum(ref.moe_mixer(
            x, lp, {**WIDTHS, "held_experts_first": 4}) * probe)

    got_l, (got_p, got_x) = jax.value_and_grad(prog, (0, 1))(params, x)
    want_l, (want_p, want_x) = jax.value_and_grad(plain, (0, 1))(params, x)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-5)
    np.testing.assert_allclose(got_x, want_x, atol=1e-4)
    for n in params:
        # float32 sums over 48 tokens: 1e-5 of the largest entry
        np.testing.assert_allclose(
            got_p[n], want_p[n], rtol=0,
            atol=1e-5 * float(jnp.abs(want_p[n]).max()) + 1e-7, err_msg=n)


def test_correction_bias_moves_the_choice_not_the_weight():
    scores = jnp.array([[2.0, 1.0, 0.5, -1.0, -2.0]])
    s = jax.nn.sigmoid(scores)[0]
    idx0, g0 = moe.sigmoid_topk_routing(scores, jnp.zeros(5), 2, 2.5)
    assert sorted(idx0[0].tolist()) == [0, 1]
    # renormalised over the chosen two, then the factor
    np.testing.assert_allclose(
        jnp.sort(g0[0]), jnp.sort(2.5 * s[:2] / (s[0] + s[1])), rtol=1e-6)
    np.testing.assert_allclose(g0.sum(), 2.5, rtol=1e-6)
    # a bias lifts expert 4 into the choice; its weight is its own
    # sigmoid, without the bias
    bias = jnp.array([0.0, 0.0, 0.0, 0.0, 5.0])
    idx1, g1 = moe.sigmoid_topk_routing(scores, bias, 2, 2.5)
    assert sorted(idx1[0].tolist()) == [0, 4]
    want = {0: 2.5 * s[0] / (s[0] + s[4]), 4: 2.5 * s[4] / (s[0] + s[4])}
    for e, g in zip(idx1[0].tolist(), g1[0].tolist()):
        # the weight is (sigmoid + bias) - bias in float32: one rounding
        # of a number of the bias's size (5 here, where a real one is
        # well under 1)
        np.testing.assert_allclose(g, want[e], rtol=1e-5)


def test_bias_is_a_buffer_and_an_empty_expert_costs_nothing():
    layer = _layer((0, 4))
    assert "e_score_correction_bias" not in dict(layer.named_parameters())
    assert "e_score_correction_bias" in dict(layer.named_buffers())
    # push every token away from held expert 2: it gets no row
    bias = jnp.zeros(E).at[2].set(-10.0)
    layer._buffers["e_score_correction_bias"] = bias
    x = _x()
    y = layer(x)
    idx, _ = layer.route(x.reshape(-1, M))
    assert not bool(jnp.any(idx == 2))
    lp, w = _ref_params(layer, 0)
    np.testing.assert_allclose(y, ref.moe_mixer(x, lp, w), atol=2e-5)
    counts = layer.last_counts
    assert int(counts["rows_routed"]) == 40 * K
    assert int(counts["rows_held"]) == int(jnp.sum(idx < 4))
    # the gradient of the empty expert's matrices is exactly zero
    g = jax.grad(lambda w1: jnp.sum(moe.held_experts_apply(
        x.reshape(-1, M), *layer.route(x.reshape(-1, M)),
        {"w1": w1, "w2": layer.experts.w2.value}, moe.relu2, 0)[0]))(
        layer.experts.w1.value)
    assert float(jnp.abs(g[2]).max()) == 0.0
    assert float(jnp.abs(g[0]).max()) > 0.0


def test_gated_experts_stay_expressible():
    """The three-matrix shape: act(x w3) * (x w1) before w2."""
    pt.seed(3)
    ffn = moe.ExpertFFN(2, M, H, activation="silu", bias=False, gated=True)
    assert ffn.b1 is None and ffn.w3 is not None
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, M))
    w = {"w1": ffn.w1.value, "w2": ffn.w2.value, "w3": ffn.w3.value}
    want = jnp.stack([
        (jax.nn.silu(x[e] @ w["w3"][e]) * (x[e] @ w["w1"][e])) @ w["w2"][e]
        for e in range(2)])
    np.testing.assert_allclose(ffn(x), want, atol=1e-5)


def test_ep_error_names_the_condition():
    from paddle_tpu import distributed as dist

    mesh = dist.build_mesh(ep=4, devices=jax.devices()[:4])
    w = jnp.zeros((6, 4, 4))
    with pytest.raises(ValueError, match="ep degree 4 must divide "
                                         "num_experts 6"):
        moe.dropless_moe_ep_apply(jnp.zeros((8, 4)), jnp.zeros((4, 6)),
                                  w, jnp.zeros((6, 4)), w,
                                  jnp.zeros((6, 4)), jax.nn.relu, 2, mesh)
