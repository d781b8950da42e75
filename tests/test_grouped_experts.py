"""The held SwiGLU experts through the grouped kernels
(``kernels/grouped_experts.py``, interpreted on the CPU under
``PADDLE_TPU_FORCE_PALLAS``) against the walk and against a dense float32
sum: outputs and every gradient, whatever the routing gives an expert."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed import moe
from paddle_tpu.kernels import grouped_experts

T, K, E, FIRST, HELD, M, H, TILE = 48, 2, 8, 2, 4, 128, 256, 16

# rows of the T * K = 96 assignments that each of the four held experts
# gets; the rest go to the four absent ones
ROUTINGS = {
    "even": (12, 12, 12, 12),
    "an_expert_without_a_row": (12, 0, 20, 12),
    "an_expert_over_the_floor": (12, 40, 12, 8),  # the floor is 32 rows
    "every_row_on_one_expert": (0, 96, 0, 0),
    "no_held_row": (0, 0, 0, 0),
    "whole_tiles": (16, 32, 16, 32),
}


def _case(counts, seed=0):
    rng = np.random.default_rng(seed)
    absent = [e for e in range(E) if not FIRST <= e < FIRST + HELD]
    flat = np.concatenate(
        [np.full(c, FIRST + e) for e, c in enumerate(counts)]
        + [rng.choice(absent, T * K - sum(counts))])
    idx = jnp.asarray(rng.permutation(flat).reshape(T, K), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, M))
    gates = jax.random.uniform(ks[1], (T, K), minval=0.1)
    w = {"w1": jax.random.normal(ks[2], (HELD, M, H)) * 0.1,
         "w2": jax.random.normal(ks[3], (HELD, H, M)) * 0.1,
         "w3": jax.random.normal(ks[4], (HELD, M, H)) * 0.1}
    return x, idx, gates, w


def _dense(x, idx, gates, w):
    """Every token through every held expert, masked by the routing."""
    hi = jax.lax.Precision.HIGHEST
    a = jnp.einsum("tm,emh->eth", x, w["w3"], precision=hi)
    b = jnp.einsum("tm,emh->eth", x, w["w1"], precision=hi)
    y = jnp.einsum("eth,ehm->etm", jax.nn.silu(a) * b, w["w2"],
                   precision=hi)
    weight = jnp.sum(
        (idx[None] == (FIRST + jnp.arange(HELD))[:, None, None])
        * gates[None], axis=-1)  # [held, t]
    return jnp.einsum("et,etm->tm", weight, y, precision=hi)


def _value_and_grads(fn, x, gates, w):
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    return jax.value_and_grad(
        lambda x, gates, w: jnp.sum(fn(x, gates, w) * probe),
        (0, 1, 2))(x, gates, w)


def _close(got, want, what):
    for (path, g), (_, r) in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves_with_path(want)):
        np.testing.assert_allclose(
            g, r, atol=3e-5 * max(1.0, float(jnp.abs(r).max())),
            err_msg=f"{what} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("floor", [0, 2], ids=["bare", "floor_of_2"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_kernels_match_the_walk_and_a_dense_sum(routing, floor,
                                                monkeypatch):
    counts = ROUTINGS[routing]
    x, idx, gates, w = _case(counts)

    def held(x, gates, w):
        y, got = moe.held_experts_apply(x, idx, gates, w, jax.nn.silu,
                                        FIRST, TILE, floor)
        assert y.shape == x.shape and y.dtype == jnp.float32
        np.testing.assert_array_equal(got, counts)
        return y

    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    assert not moe._use_grouped(x, w, jax.nn.silu, TILE)
    walk = _value_and_grads(held, x, gates, w)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert moe._use_grouped(x, w, jax.nn.silu, TILE)
    kernels = _value_and_grads(held, x, gates, w)
    dense = _value_and_grads(
        lambda x, gates, w: _dense(x, idx, gates, w), x, gates, w)
    _close(kernels, walk, "kernels against the walk:")
    _close(kernels, dense, "kernels against the dense sum:")
    if not sum(counts):
        assert all(float(jnp.abs(g).max()) == 0.0
                   for g in jax.tree_util.tree_leaves(kernels[1]))


@pytest.mark.parametrize("floor", [0, 2], ids=["bare", "floor_of_2"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_layout_is_sorted_tile_aligned_and_counts_its_rows(routing, floor,
                                                           monkeypatch):
    """An expert's stretch starts on a tile boundary and is its rows in
    whole tiles, never under the floor nor under one tile; every held
    assignment sits in its expert's stretch once, in the order of the
    sort; ``held_rows_walked`` reads the live tiles' rows through the
    kernels and the blocks' rows on the walk."""
    counts = np.array(ROUTINGS[routing])
    x, idx, gates, w = _case(counts)
    order, got = moe._held_rows(idx, FIRST, HELD)
    flat, tok, tile_expert, n_live = moe._sorted_layout(
        order, got, K, T, TILE, floor)
    tiles = np.maximum(-(-counts // TILE), max(floor, 1))
    assert int(n_live[0]) == tiles.sum()
    assert flat.shape[0] % TILE == 0 and flat.shape[0] >= T * K
    starts = np.concatenate([[0], np.cumsum(tiles)])
    at = 0
    for e in range(HELD):
        rows = slice(starts[e] * TILE, starts[e + 1] * TILE)
        np.testing.assert_array_equal(
            tile_expert[starts[e]:starts[e + 1]], e)
        np.testing.assert_array_equal(
            flat[rows][:counts[e]], order[at:at + counts[e]])
        np.testing.assert_array_equal(
            tok[rows][:counts[e]], order[at:at + counts[e]] // K)
        assert (np.asarray(flat[rows][counts[e]:]) >= T * K).all()
        assert (np.asarray(tok[rows][counts[e]:]) >= T).all()
        at += counts[e]
    dead = slice(int(n_live[0]) * TILE, None)
    assert (np.asarray(flat[dead]) >= T * K).all()
    assert (np.asarray(tile_expert[int(n_live[0]):]) == HELD - 1).all()

    def walked():
        return int(moe.held_rows_walked(x, w, jax.nn.silu, got, TILE,
                                        floor))

    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    assert walked() == np.maximum(-(-counts // TILE), floor).sum() * TILE
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert walked() == int(n_live[0]) * TILE


def test_bf16_rows_stay_within_rounding_of_the_walk(monkeypatch):
    """bf16 operands, float32 sums: the two paths round in different
    places and agree to a few bf16 steps."""
    x, idx, gates, w = _case(ROUTINGS["an_expert_over_the_floor"])
    x = x.astype(jnp.bfloat16)
    w = jax.tree_util.tree_map(lambda v: v.astype(jnp.bfloat16), w)

    def held(x, gates, w):
        return moe.held_experts_apply(x, idx, gates, w, jax.nn.silu,
                                      FIRST, TILE, 2)[0]

    # the output itself and the gradients: the probe's sum cancels too
    # much of itself to be compared in bf16
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    walk = held(x, gates, w), _value_and_grads(held, x, gates, w)[1]
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    kernels = held(x, gates, w), _value_and_grads(held, x, gates, w)[1]
    for g, r in zip(jax.tree_util.tree_leaves(kernels),
                    jax.tree_util.tree_leaves(walk)):
        assert g.dtype == r.dtype
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        assert float(jnp.linalg.norm(g - r)) <= 2e-2 * float(
            jnp.linalg.norm(r))


def _shapes(m, h, gated):
    w = {"w1": jax.ShapeDtypeStruct((8, m, h), jnp.bfloat16),
         "w2": jax.ShapeDtypeStruct((8, h, m), jnp.bfloat16)}
    if gated:
        w["w3"] = w["w1"]
    return jax.ShapeDtypeStruct((8192, m), jnp.bfloat16), w


@pytest.mark.parametrize("name, m, h, gated, act, taken", [
    ("lfm2", 2048, 1792, True, jax.nn.silu, True),
    ("nemotron", 2688, 1856, False, moe.relu2, False),
    ("two_matrix_at_whole_lanes", 2048, 1792, False, moe.relu2, False),
    ("gated_at_a_width_off_the_lanes", 2688, 1856, True, jax.nn.silu,
     False),
    ("gated_under_another_activation", 2048, 1792, True, jax.nn.gelu,
     False),
])
def test_dispatcher_sends_only_gated_lane_aligned_silu_to_the_kernels(
        name, m, h, gated, act, taken, monkeypatch):
    """And nothing off the chip unless ``PADDLE_TPU_FORCE_PALLAS`` is
    set: the walk is the reference there."""
    x, w = _shapes(m, h, gated)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    assert not moe._use_grouped(x, w, act, 256)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert moe._use_grouped(x, w, act, 256) == taken
    assert grouped_experts.aligned(m, h, 256) == (h % 128 == 0)


def test_layer_leaves_rows_walked_for_gated_experts_only(monkeypatch):
    from paddle_tpu.nn import functional as F

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, M))
    for force in (False, True):
        if force:
            monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
        layer = moe.HeldExpertsMoE(M, E, H, K, (FIRST, HELD),
                                   activation="silu", gated=True,
                                   even_share_slack=1.25)
        layer.block_rows = TILE
        assert layer.experts.act is F.silu
        layer(x)
        c = layer.last_counts
        # an even share is 8 rows: the floor is one tile of 16
        assert int(c["rows_walked"]) >= max(int(c["rows_held"]),
                                            HELD * TILE)
        assert int(c["rows_walked"]) % TILE == 0
    plain = moe.HeldExpertsMoE(M, E, H, K, (FIRST, HELD))
    plain(x)
    assert set(plain.last_counts) == {"rows_routed", "rows_held",
                                      "rows_max"}
    assert set(moe.sum_routing_counts([plain.last_counts])) == {
        "moe_rows_routed", "moe_rows_held", "moe_rows_max"}
    assert "moe_rows_walked" in moe.sum_routing_counts([c, c])
