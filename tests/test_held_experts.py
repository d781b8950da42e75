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


# ---- the gated (three-matrix) form: (act(x w3) * (x w1)) w2
def _gated_case(t=80, k=2, first=2, n_held=4, block_rows=16):
    """Uneven routing over E experts with held ones ``first ..``: held
    expert 1 gets no row, held expert 2 more than a block of 16."""
    keys = jax.random.split(jax.random.PRNGKey(38), 6)
    x = jax.random.normal(keys[0], (t, M))
    w = {n: 0.3 * jax.random.normal(kk, (n_held, *shape))
         for n, kk, shape in (("w1", keys[1], (M, H)), ("w2", keys[2], (H, M)),
                              ("w3", keys[3], (M, H)))}
    # choices: column 0 mostly held expert 2 (id first + 2), column 1
    # spread over every expert but held expert 1 (id first + 1)
    col0 = jnp.where(jnp.arange(t) % 5 == 0, 0, first + 2)
    others = jnp.array([e for e in range(E) if e not in (first + 1,
                                                         first + 2)])
    col1 = others[jax.random.randint(keys[4], (t,), 0, others.size)]
    idx = jnp.stack([col0, col1], axis=1).astype(jnp.int32)
    gates = jax.random.uniform(keys[5], (t, k), minval=0.2, maxval=1.0)
    return x, idx, gates, w, first, block_rows


def _dense_gated(x, idx, gates, w, first):
    """Every held expert over every token by einsum, masked to the
    (token, choice) pairs that fall on it."""
    h = jax.nn.silu(jnp.einsum("tm,emh->eth", x, w["w3"])) \
        * jnp.einsum("tm,emh->eth", x, w["w1"])
    y = jnp.einsum("eth,ehm->etm", h, w["w2"])
    held = first + jnp.arange(w["w1"].shape[0])
    weight = jnp.sum(jnp.where(idx[None] == held[:, None, None],
                               gates[None], 0.0), axis=-1)  # [e, t]
    return jnp.einsum("et,etm->tm", weight, y)


@pytest.mark.parametrize("floor", [0, 3], ids=["bare", "floor_of_3"])
def test_gated_held_experts_forward_and_backward_match_a_dense_einsum(floor):
    """``floor``: every held expert walks at least three blocks of 16,
    the empty one and the one with 16 rows or fewer too; the blocks past
    an expert's last row add nothing, forward or backward."""
    x, idx, gates, w, first, rows = _gated_case()
    y, counts = moe.held_experts_apply(x, idx, gates, w, jax.nn.silu,
                                       first, rows, floor)
    assert int(counts[1]) == 0 and int(counts[2]) > rows  # 0 rows; blocks
    assert int(counts.sum()) == int(jnp.sum(
        (idx >= first) & (idx < first + 4)))
    np.testing.assert_allclose(y, _dense_gated(x, idx, gates, w, first),
                               atol=2e-5)
    probe = jax.random.normal(jax.random.PRNGKey(9), y.shape)

    def held(x, gates, w):
        return jnp.sum(moe.held_experts_apply(
            x, idx, gates, w, jax.nn.silu, first, rows, floor)[0] * probe)

    def dense(x, gates, w):
        return jnp.sum(_dense_gated(x, idx, gates, w, first) * probe)

    got = jax.grad(held, (0, 1, 2))(x, gates, w)
    want = jax.grad(dense, (0, 1, 2))(x, gates, w)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, g), (_, r) in zip(leaves(got), leaves(want)):
        # float32 sums over 80 tokens: 1e-5 of the largest entry
        np.testing.assert_allclose(
            g, r, rtol=0, err_msg=str(path),
            atol=1e-5 * float(jnp.abs(r).max()) + 1e-7)
    # the third matrix has a cotangent of its own, and the empty
    # expert's is exactly zero in all three
    assert float(jnp.abs(got[2]["w3"][2]).max()) > 0
    for n in ("w1", "w2", "w3"):
        assert float(jnp.abs(got[2][n][1]).max()) == 0.0, n


def test_two_matrix_path_does_not_see_the_third_matrix():
    """Without ``w3`` the walk computes act(x w1) w2 as it always did:
    the jaxpr of the two-matrix call holds two products a block, the
    gated one three."""
    x, idx, gates, w, first, rows = _gated_case()
    two = {"w1": w["w1"], "w2": w["w2"]}

    def dots(w):
        text = str(jax.make_jaxpr(lambda x: moe.held_experts_apply(
            x, idx, gates, w, moe.relu2, first, rows)[0])(x))
        return text.count("dot_general")

    assert (dots(two), dots(w)) == (2, 3)


def test_router_epsilon_is_an_argument_and_defaults_to_1e_20():
    scores = jnp.array([[-40.0, -41.0, -50.0]])  # sigmoids near 1e-18
    s = jax.nn.sigmoid(scores)[0]
    _, g0 = moe.sigmoid_topk_routing(scores, jnp.zeros(3), 2)
    np.testing.assert_allclose(g0[0], s[:2] / (s[0] + s[1] + 1e-20),
                               rtol=1e-5)
    _, g6 = moe.sigmoid_topk_routing(scores, jnp.zeros(3), 2, eps=1e-6)
    np.testing.assert_allclose(g6[0], s[:2] / (s[0] + s[1] + 1e-6),
                               rtol=1e-5)
    assert float(g0.sum()) > 0.9 and float(g6.sum()) < 1e-9
    layer = moe.HeldExpertsMoE(M, E, H, K, held=(0, 4), activation="silu",
                               gated=True, norm_eps=1e-6)
    assert layer.experts.w3.value.shape == (4, M, H)
    assert layer.shared_experts is None and layer.norm_eps == 1e-6


def test_the_walks_floor_is_a_slack_over_the_even_share():
    """Off by default (the two-matrix cell's walk is as it was); with a
    slack, the blocks of 256 that slack x tokens x top_k / experts rows
    fill: 8192 tokens, top-4 of 32 and 1.25 give 1280 rows, 5 blocks."""
    def layer(**kw):
        return moe.HeldExpertsMoE(M, 32, H, 4, held=(0, 8),
                                  activation="silu", gated=True, **kw)

    assert layer().min_blocks(8192) == 0
    slack = layer(even_share_slack=1.25)
    assert slack.block_rows == 256
    assert [slack.min_blocks(t) for t in (8192, 16384, 256, 1)] == [
        5, 10, 1, 1]
    assert layer(even_share_slack=1.0).min_blocks(8192) == 4
