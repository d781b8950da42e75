"""``Phi4FlashForCausalLM`` against the plain reference
(``chipbench/reference/phi4flash.py``), whose Mamba-1 layer walks the
recurrence step by step where the program runs the chunked kernel (in
the Pallas interpreter here), and whose attention makes the published
four value products where the program makes two calls over
``V = [v1 | v2]`` with the scores' heads zero-padded.

Seeded weights from the benchmark's own generator on both sides;
conftest pins matmul precision ``highest``. In float32 the two differ by
the order of float32 sums only (chunked against stepwise, two maps
against four products, a loss in blocks against one whole), so the
tolerances are a few float32 roundings of sums over 64 tokens: 2e-5
relative on the loss, 2e-4 of a leaf's largest entry on its gradient.
With bf16 parameters and activations in the program (the reference stays
float32 on the same bf16-rounded weights) every product and every kept
activation is rounded to 8 bits of mantissa, 4e-3 a rounding, and a
gradient passes through some tens of them in six layers: 2e-2 relative
on the loss and 6e-2 of a leaf's largest entry on its gradient, ten
times what a sound bf16 run reads here and far under the O(1) gap a
wrong mask, pairing, lambda or hand-over gives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench.reference import phi4flash as ref
from chipbench.weights import phi4flash as weights
from paddle_tpu import distributed as dist, optimizer as opt
from paddle_tpu.core.functional import functional_call
from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM
from paddle_tpu.trainer import TrainStep

# bf16 only: a layer's four lambda vectors share ONE scalar's gradient,
# dL/dlambda, a sum over every token, pair and value of cot . a2 whose
# terms cancel; the bf16 rounding of a1 and a2 moves it by tens of
# percent at this size, all four vectors by the same factor
LAMBDA_TOL = 0.6

# a published depth of 8 (half = 4): layers 0-3 the self-decoder's two
# periods, 4 the memory's maker, 5 the keys' and values', 6-7 one
# cross-decoder period
SIX = (0, 1, 4, 5, 6, 7)


def _widths(held, vocab=128):
    return {
        "vocab_size": vocab, "hidden_size": 32, "intermediate_size": 48,
        "num_hidden_layers": len(held), "published_num_hidden_layers": 8,
        "published_layer_indices": list(held), "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 4, "mb_per_layer": 2,
        "sliding_window": 16, "layer_norm_eps": 1e-5, "mamba_d_state": 8,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 2,
        "scan_chunk": 16, "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4}


def _model(w):
    pt.seed(0)
    return Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        intermediate_size=w["intermediate_size"],
        num_hidden_layers=w["published_num_hidden_layers"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        sliding_window=w["sliding_window"],
        published_layer_indices=tuple(w["published_layer_indices"]),
        mamba_d_state=w["mamba_d_state"], mamba_dt_rank=w["mamba_dt_rank"],
        scan_chunk=w["scan_chunk"]))


def _params(w, seed=7):
    """Larger than 0.02 (at toy widths the layers must matter to the
    loss), biases and lambdas that are not zero, float32 values that
    bf16 holds exactly."""
    out = {}
    for n, v in weights.make_all(w, seed, len(
            w["published_layer_indices"])).items():
        v = v.astype(jnp.float32)
        if n.endswith(".bias") and "dt_proj" not in n:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), len(out))
            v = 0.1 * jax.random.normal(key, v.shape)
        elif v.ndim > 1 and "A_log" not in n and "conv" not in n:
            v = 5.0 * v
        out[n] = v.astype(jnp.bfloat16).astype(jnp.float32)
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("held", [(0,), (1,), (4, 6), (5, 7), SIX], ids=[
    "mamba", "window_attention", "memory_and_gmu", "kv_and_cross", "six"])
def test_loss_and_gradients_match_the_reference(held, dtype):
    """Each kind of mixer with no more company than it needs (a gated
    memory unit behind the layer whose scan output it reads, a
    cross-attention behind the layer whose keys and values it reads),
    then the six-layer cut."""
    w = _widths(held)
    model = _model(w)
    params = _params(w)
    assert set(params) == {n for n, _ in model.named_parameters()}
    assert weights.layer_shapes(w) == {} and \
        weights.n_params(w, len(held)) == sum(
            v.size for v in params.values())
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                             w["vocab_size"])

    prog = jax.jit(jax.value_and_grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=ids).astype(jnp.float32)))
    plain = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, w, len(held))))
    got_l, got = prog({n: v.astype(dtype) for n, v in params.items()})
    want_l, want = plain(params)
    loss_tol, leaf_tol = (2e-5, 2e-4) if dtype == jnp.float32 \
        else (2e-2, 6e-2)
    np.testing.assert_allclose(got_l, want_l, rtol=loss_tol)
    off = {}
    for n in params:
        largest = float(jnp.abs(want[n]).max())
        assert largest > 0, n
        gap = float(jnp.abs(got[n].astype(jnp.float32) - want[n]).max())
        tol = LAMBDA_TOL if ".lambda_" in n and dtype != jnp.float32 \
            else leaf_tol
        if gap > tol * largest + 1e-9:
            off[n] = gap / largest
    assert not off, off


def test_layer_kinds_follow_the_published_index():
    cfg = Phi4FlashConfig(vocab_size=64)
    kinds = [cfg.layer_kind(l) for l in range(32)]
    assert kinds[:16] == ["mamba", "attention"] * 8
    assert kinds[16:18] == ["mamba", "attention"]  # memory; keys, values
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert [l for l in range(32) if cfg.window(l)] == list(range(1, 16, 2))
    assert all(cfg.window(l) == 512 for l in range(1, 16, 2))
    assert cfg.lambda_init(0) == pytest.approx(0.2)
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    assert (cfg.mamba_dt_rank, cfg.d_inner, cfg.head_dim) == (160, 5120, 64)
    assert cfg.published_layer_indices == tuple(range(32))
    # the reference and the weights spell the same kinds
    w = {"published_num_hidden_layers": 32, "mb_per_layer": 2}
    assert [ref.layer_kind(l, w) for l in range(32)] == kinds
    assert [weights.layer_kind(l, w) for l in range(32)] == kinds
    # a reader without the layer it reads is refused
    with pytest.raises(ValueError, match="memory"):
        Phi4FlashConfig(published_layer_indices=(0, 1, 17, 18))
    with pytest.raises(ValueError, match="keys and values"):
        Phi4FlashConfig(published_layer_indices=(16, 18, 19))


def test_tied_leaf_gradient_is_the_sum_of_both_uses():
    """One leaf is embedding and head: the program's gradient on it is
    the look-up's (rows of the ids that occur) plus the head's (dense),
    each taken apart through the reference's own pieces."""
    w = _widths(SIX)
    model, params = _model(w), _params(w)
    name = "model.embed_tokens.weight"
    assert not any("lm_head" in n for n in params)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 40)
    got = jax.jit(jax.grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=ids)))(params)[name]

    def apart(looked_up, head):
        top, per = ref.split_params(params, len(SIX))
        x, memory, kv = looked_up[ids], None, None
        for lp, l in zip(per, SIX):
            x, memory, kv = ref.decoder_layer(x, lp, w, l, memory, kv)
        logits = ref.head_logits(x, {**top, name: head}, w)[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1))

    g_embed, g_head = jax.jit(jax.grad(apart, (0, 1)))(
        params[name], params[name])
    unused = np.setdiff1d(np.arange(w["vocab_size"]), np.asarray(ids))
    assert unused.size and not np.asarray(g_embed)[unused].any()
    assert float(jnp.abs(g_embed).max()) > 0
    assert np.asarray(g_head)[unused].any()
    np.testing.assert_allclose(
        got, g_embed + g_head, rtol=0,
        atol=2e-4 * float(jnp.abs(g_embed + g_head).max()))


def test_eight_vocabulary_slices_make_the_whole_heads_logits():
    """The deployment's cut: each of 8 chips holds an eighth of the tied
    embedding. A chip's model, on ids of its own slice, gives the whole
    model's hidden state and its slice of the whole head's logits; the
    eight slices of one input's logits, side by side, are the whole
    head's."""
    w = _widths(SIX, vocab=128)
    whole, params = _model(w), _params(w)
    name, rows = "model.embed_tokens.weight", 128 // 8
    slice_w = _widths(SIX, vocab=rows)
    chip = _model(slice_w)
    whole_logits = jax.jit(lambda p, ids: functional_call(
        whole, p, input_ids=ids))
    chip_logits = jax.jit(lambda p, ids: functional_call(
        chip, p, input_ids=ids))
    chip_hidden = jax.jit(lambda p, ids: functional_call(
        chip.model, {n[len("model."):]: v for n, v in p.items()},
        input_ids=ids))
    for k in range(8):
        ids = k * rows + jax.random.randint(
            jax.random.PRNGKey(k), (1, 32), 0, rows)
        mine = {**params, name: params[name][k * rows:(k + 1) * rows]}
        np.testing.assert_allclose(
            chip_logits(mine, ids - k * rows),
            whole_logits(params, ids)[..., k * rows:(k + 1) * rows],
            rtol=1e-5, atol=1e-5)
    # one input (of slice 0, so that chip 0's own look-up serves): every
    # chip's rows against the hidden state, concatenated
    ids = jax.random.randint(jax.random.PRNGKey(9), (1, 32), 0, rows)
    hidden = chip_hidden({**params, name: params[name][:rows]}, ids)
    parts = [hidden @ params[name][k * rows:(k + 1) * rows].T
             for k in range(8)]
    np.testing.assert_allclose(
        jnp.concatenate(parts, axis=-1), whole_logits(params, ids),
        rtol=1e-5, atol=1e-5)


def test_a_sequence_that_is_no_multiple_of_the_chunk_raises():
    """The new model never takes ``MambaMixer``'s float32 associative
    fall-back."""
    model = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    ids = jnp.zeros((1, 24), jnp.int32)  # scan_chunk is 16
    with pytest.raises(ValueError, match="scan_chunk"):
        model(ids, labels=ids)
    assert model(jnp.zeros((1, 32), jnp.int32)).shape == (1, 32, 256)


def test_trains_through_train_step_with_one_tied_leaf():
    """bf16 parameters, fp32 masters and moments, clip 1.0, through
    ``TrainStep.run`` as the benchmark drives it: the loss falls, the
    embedding's slot is the only one of its size, and its first moment
    after a step is that step's clipped gradient on the tied leaf."""
    pt.seed(0)
    model = Phi4FlashForCausalLM(Phi4FlashConfig.tiny(
        published_layer_indices=SIX))
    model.to(pt.bfloat16)
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    ts = TrainStep(
        model, opt.AdamW(3e-3, multi_precision=True,
                         grad_clip=opt.ClipGradByGlobalNorm(1.0)), mesh)
    ids = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"input_ids": ids, "labels": ids}
    p0 = {n: jnp.asarray(p.value) for n, p in model.named_parameters()}
    grads = jax.jit(jax.grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=ids).astype(jnp.float32)))(p0)
    losses = [float(ts.run(batch))]
    name = "model.embed_tokens.weight"
    m1 = np.asarray(ts.opt_state["slots"][name]["moment1"], np.float32)
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grads.values())))
    want = 0.1 * np.asarray(grads[name], np.float32) * min(1.0, 1.0 / norm)
    np.testing.assert_allclose(m1, want, rtol=0,
                               atol=2e-2 * np.abs(want).max())
    assert sum("embed" in n or "head" in n for n in ts.opt_state["slots"]) \
        == 1
    losses += [float(ts.run(batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
