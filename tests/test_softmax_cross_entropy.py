"""The one-pass hard-label softmax cross-entropy and the label shift.

``F.cross_entropy`` with hard labels and no smoothing goes through
``F.softmax_cross_entropy_rows``: its forward keeps each row's
log-sum-exp and picks the label's logit inside the row reduction, and
its backward makes the logits' cotangent in one pass, in the logits'
dtype. Each case here is held to a plain float32 ``log_softmax`` and
``take_along_axis`` written in the test. The causal LMs shift their
labels (``F.shift_labels``) instead of slicing the logits: the loss and
every leaf's gradient must be the sliced loss's, and the gradient's
program must hold no gather from or scatter into an array of the
logits' shape, all of it under the ``head_loss`` scope that the
benchmark's ``head_device_ms.train`` reads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from paddle_tpu.core.functional import functional_call
from paddle_tpu.incubate.nn import functional as IF

V = 300  # no multiple of 128, and no other width of the tiny models


def _plain(logits, labels, ignore_index=-100, reduction="mean"):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels != ignore_index
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    if reduction == "none":
        return nll
    if reduction == "sum":
        return nll.sum()
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def _case(dtype, shape=(3, 7), seed=0, ignored=((0, 2), (2, 6))):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal(shape + (V,)) * 4, dtype)
    labels = jnp.asarray(rng.integers(0, V, shape), jnp.int32)
    for at in ignored:
        labels = labels.at[at].set(-100)
    return logits, labels


def _loss_and_grad(fn, logits, labels, reduction):
    weights = jnp.linspace(0.5, 1.5, labels.size).reshape(labels.shape)

    def scalar(x):
        out = fn(x, labels, reduction=reduction)
        return jnp.sum(out * weights) if reduction == "none" else out

    return fn(logits, labels, reduction=reduction), jax.grad(scalar)(logits)


@pytest.mark.parametrize("reduction", ["none", "sum", "mean"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_loss_matches_log_softmax_and_gather(dtype, reduction):
    logits, labels = _case(dtype)
    got, got_g = _loss_and_grad(F.cross_entropy, logits, labels, reduction)
    want, want_g = _loss_and_grad(_plain, logits, labels, reduction)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # the cotangent is rounded to the logits' dtype, as the parent's
    # convert rounded it
    assert got_g.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(got_g.astype(jnp.float32),
                               want_g.astype(jnp.float32),
                               rtol=tol, atol=tol * float(
                                   jnp.abs(want_g.astype(jnp.float32)).max()))
    # an ignored row gives no loss and no gradient
    for at in ((0, 2), (2, 6)):
        assert float(jnp.abs(got_g[at].astype(jnp.float32)).max()) == 0.0
        if reduction == "none":
            assert float(got[at]) == 0.0


@pytest.mark.parametrize("reduction", ["none", "sum", "mean"])
def test_a_batch_with_every_label_ignored_gives_zero(reduction):
    logits, labels = _case(jnp.bfloat16, shape=(2, 3), ignored=())
    labels = jnp.full_like(labels, -100)
    got, got_g = _loss_and_grad(F.cross_entropy, logits, labels, reduction)
    assert float(jnp.abs(got).max()) == 0.0
    assert float(jnp.abs(got_g.astype(jnp.float32)).max()) == 0.0


def test_other_ignore_index_and_class_axis():
    logits, labels = _case(jnp.float32, ignored=())
    labels = labels.at[1, 3].set(7)
    got = F.cross_entropy(logits, labels, ignore_index=7)
    np.testing.assert_allclose(got, _plain(logits, labels, 7), rtol=1e-6)
    moved = jnp.moveaxis(logits, -1, 1)
    got_g = jax.grad(lambda x: F.cross_entropy(x, labels, axis=1))(moved)
    want_g = jax.grad(lambda x: _plain(jnp.moveaxis(x, 1, -1), labels))(
        moved)
    np.testing.assert_allclose(got_g, want_g, rtol=1e-6, atol=1e-7)


def _custom_vjp_calls(fn, *args):
    return [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if "custom_vjp" in e.primitive.name]


def test_soft_labels_and_smoothing_keep_the_log_softmax_path():
    logits, labels = _case(jnp.float32, ignored=())
    soft = jax.nn.softmax(jnp.asarray(
        np.random.default_rng(5).standard_normal(logits.shape), jnp.float32))
    assert _custom_vjp_calls(lambda x: F.cross_entropy(x, labels), logits)
    assert not _custom_vjp_calls(
        lambda x: F.cross_entropy(x, soft, soft_label=True), logits)
    assert not _custom_vjp_calls(
        lambda x: F.cross_entropy(x, labels, label_smoothing=0.1), logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    np.testing.assert_allclose(
        F.cross_entropy(logits, soft, soft_label=True),
        jnp.mean(-jnp.sum(soft * logp, axis=-1)), rtol=1e-6)
    onehot = jax.nn.one_hot(labels, V)
    smooth = onehot * 0.9 + 0.1 / V
    np.testing.assert_allclose(
        F.cross_entropy(logits, labels, label_smoothing=0.1),
        jnp.mean(-jnp.sum(smooth * logp, axis=-1)), rtol=1e-6)


def test_shift_labels_ends_each_row_with_ignore_index():
    labels = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
    np.testing.assert_array_equal(
        F.shift_labels(labels),
        [[1, 2, 3, 4, -100], [6, 7, 8, 9, -100]])
    np.testing.assert_array_equal(F.shift_labels(labels, 0)[:, -1], [0, 0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_linear_loss_equals_the_whole_logit_loss(dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 32, 24)), dtype)
    w = jnp.asarray(rng.standard_normal((V, 24)) * 0.3, dtype)
    labels = F.shift_labels(jnp.asarray(rng.integers(0, V, (2, 32)),
                                        jnp.int32))

    def chunked(x, w):
        return IF.fused_linear_cross_entropy(
            x, w, labels, transpose_weight=True, seq_chunk=8)

    def whole(x, w):
        return _plain(x @ w.T, labels)

    got, got_g = jax.value_and_grad(chunked, argnums=(0, 1))(x, w)
    want, want_g = jax.value_and_grad(whole, argnums=(0, 1))(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, h in zip(got_g, want_g):
        g, h = g.astype(jnp.float32), h.astype(jnp.float32)
        np.testing.assert_allclose(g, h, rtol=tol,
                                   atol=tol * float(jnp.abs(h).max()))


# ---- the four causal LMs of the benchmark's cells, at tiny widths


def _lfm2():
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
    return Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
        vocab_size=V, num_dense_layers=1, use_flash_attention=False))


def _nemotron():
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
    return NemotronHForCausalLM(NemotronHConfig.tiny(
        vocab_size=V, use_flash_attention=False))


def _llama(chunk=0):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=V, use_flash_attention=False,
        fused_head_loss_chunk=chunk))


def _llama_chunked():
    return _llama(chunk=8)


def _phi4flash():
    from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM
    return Phi4FlashForCausalLM(Phi4FlashConfig.tiny(
        vocab_size=V, published_layer_indices=(0, 1, 4, 5, 6, 7)))


MODELS = {"lfm2": _lfm2, "nemotron_h": _nemotron, "llama": _llama,
          "llama_chunked": _llama_chunked, "phi4flash": _phi4flash}


def _setup(name):
    model = MODELS[name]()
    params = {n: p.value for n, p in model.named_parameters()}
    ids = jax.random.randint(jax.random.PRNGKey(11), (2, 32), 0, V)
    labels = ids.at[1, 5].set(-100)
    return model, params, ids, labels


@pytest.mark.parametrize("name", sorted(MODELS))
def test_shifted_labels_give_the_sliced_loss_and_gradients(name):
    model, params, ids, labels = _setup(name)

    def shifted(p):
        return functional_call(model, p, input_ids=ids, labels=labels)

    def sliced(p):
        logits = functional_call(model, p, input_ids=ids)
        return _plain(logits[:, :-1, :], labels[:, 1:])

    got, got_g = jax.jit(jax.value_and_grad(shifted))(params)
    want, want_g = jax.jit(jax.value_and_grad(sliced))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for n in params:
        scale = float(jnp.abs(want_g[n]).max())
        assert scale > 0, n
        np.testing.assert_allclose(got_g[n], want_g[n], rtol=0,
                                   atol=2e-5 * scale, err_msg=n)


def _eqns(jaxpr, stack=""):
    """Every equation of a jaxpr and of the jaxprs inside it, with the
    whole name stack it is traced under."""
    for e in jaxpr.eqns:
        here = "/".join(s for s in (stack, str(e.source_info.name_stack))
                        if s)
        yield e, here
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, here)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_loss_gradient_neither_gathers_nor_scatters_over_the_vocab(name):
    model, params, ids, labels = _setup(name)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=labels)))(params).jaxpr
    over_vocab, backward = [], 0
    for e, stack in _eqns(jaxpr):
        if e.primitive.name.startswith("scatter") or \
                e.primitive.name == "gather":
            assert e.invars[0].aval.shape[-1:] != (V,), (e, stack)
        # what reads an array and writes one over the vocab; an iota
        # or a broadcast constant is hoisted out of the chunked loss's
        # loop without a scope, and XLA sinks it back into the loop
        reads = any(len(getattr(v.aval, "shape", ())) >= 2
                    for v in e.invars)
        shapes = [v.aval.shape for v in e.outvars if hasattr(v.aval, "shape")]
        if reads and any(s[-1:] == (V,) and len(s) >= 2 for s in shapes):
            over_vocab.append((e.primitive.name, stack))
            backward += "transpose(" in stack and e.primitive.name == "exp"
    # the logits, their softmax and its cotangent: all under head_loss
    assert over_vocab
    outside = [x for x in over_vocab if "head_loss" not in x[1]]
    assert not outside, outside
    # the backward's softmax is made under the scope too
    assert backward >= 1, over_vocab
