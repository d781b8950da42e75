"""Pallas flash-attention kernel numerics vs the XLA reference (interpret
mode on CPU; the same code compiles via Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import _reference_attention
from paddle_tpu.kernels.pallas_attention import mha


@pytest.mark.parametrize("causal", [False, True])
def test_mha_forward_matches_reference(causal):
    rng = np.random.default_rng(0)
    b, s, h, d = 1, 256, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = mha(q, k, v, causal=causal, q_block=128, k_block=128)
    ref = _reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mha_grad_matches_reference():
    rng = np.random.default_rng(1)
    b, s, h, d = 1, 256, 1, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)

    def loss_pallas(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, q_block=128, k_block=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


def test_mha_gqa():
    rng = np.random.default_rng(2)
    b, s, d = 1, 128, 128
    q = jnp.asarray(rng.standard_normal((b, s, 4, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)
    out = mha(q, k, v, causal=True, q_block=128, k_block=128)
    ref = _reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mha_gqa_grad():
    """GQA backward: dk/dv group-reduction happens inside the kernel."""
    rng = np.random.default_rng(3)
    b, s, d = 1, 256, 128
    q = jnp.asarray(rng.standard_normal((b, s, 4, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)

    def loss_pallas(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, q_block=128, k_block=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


def _segment_reference(q, k, v, seg_q, seg_kv, causal):
    """Dense reference for packed/varlen attention."""
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    logits = logits.astype(jnp.float32)
    mask = seg_q[:, None, :, None] == seg_kv[:, None, None, :]
    if causal:
        sk = k.shape[1]
        mask = jnp.logical_and(mask, jnp.tril(jnp.ones((sq, sk), bool)))
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("causal", [False, True])
def test_mha_varlen_segments(causal):
    """Packed sequences: attention stays within segment boundaries."""
    rng = np.random.default_rng(4)
    b, s, h, d = 1, 512, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    # three packed sequences of lengths 200, 200, 112
    seg = jnp.asarray(
        np.concatenate([np.zeros(200), np.ones(200), 2 * np.ones(112)]),
        jnp.int32,
    )[None, :]
    out = mha(q, k, v, causal=causal, q_block=128, k_block=128,
              segment_ids=seg)
    ref = _segment_reference(q, k, v, seg, seg, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mha_varlen_grad():
    rng = np.random.default_rng(5)
    b, s, h, d = 1, 256, 1, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    seg = jnp.asarray(
        np.concatenate([np.zeros(100), np.ones(156)]), jnp.int32
    )[None, :]

    def loss_pallas(q, k, v):
        return jnp.sum(
            mha(q, k, v, causal=True, q_block=128, k_block=128,
                segment_ids=seg) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_segment_reference(q, k, v, seg, seg, True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


def test_mha_nonsquare_blocks():
    """q_block != k_block exercises the causal pruning index arithmetic."""
    rng = np.random.default_rng(6)
    b, s, h, d = 1, 512, 1, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = mha(q, k, v, causal=True, q_block=256, k_block=128)
    ref = _reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )

    def loss(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, q_block=128, k_block=256) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


@pytest.mark.parametrize("d", [64, 96])
@pytest.mark.parametrize("causal", [False, True])
def test_mha_unaligned_head_dim(d, causal):
    """head_dim 64/96 (GPT/ViT): kernel zero-pads to lane width — must
    match the dense reference exactly, not fall back to it."""
    rng = np.random.default_rng(7)
    b, s, h = 1, 256, 2
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = mha(q, k, v, causal=causal, q_block=128, k_block=128)
    assert out.shape == (b, s, h, d)
    ref = _reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mha_unaligned_head_dim_grad():
    rng = np.random.default_rng(8)
    b, s, h, d = 1, 256, 4, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)  # +GQA
    v = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)

    def loss_pallas(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, q_block=128, k_block=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        assert a.shape == b_.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


@pytest.mark.parametrize("window", [64, 128, 200])
def test_mha_sliding_window(window):
    """Mistral-style local attention (parity: flash_attn window_size):
    kernel output must match the dense windowed reference."""
    rng = np.random.default_rng(20)
    b, s, h, d = 1, 512, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    out = mha(q, k, v, causal=True, q_block=128, k_block=128, window=window)
    ref = _reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_mha_sliding_window_grad():
    rng = np.random.default_rng(21)
    b, s, h, d = 1, 256, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    w = 96

    def loss_pallas(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, q_block=128, k_block=128,
                           window=w) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=True,
                                            window=w) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


def test_mha_window_requires_causal():
    q = jnp.ones((1, 128, 1, 128))
    with pytest.raises(ValueError):
        mha(q, q, q, causal=False, window=64)


def test_flash_attention_window_fallback_paths():
    """window_size must be honored (or loudly rejected) on every wrapper
    path — dense fallback, segment fallback, dropout path."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    rng = np.random.default_rng(30)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    # dense fallback (unaligned seq → no pallas)
    out = flash_attention(q, q, q, causal=True, window_size=16)
    ref = _reference_attention(q, q, q, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # segment fallback honors the window too
    seg = jnp.zeros((1, 64), jnp.int32)
    out = flash_attention(q, q, q, causal=True, segment_ids=seg,
                          window_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # non-causal window is rejected on every path
    with pytest.raises(ValueError):
        flash_attention(q, q, q, causal=False, window_size=16)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, causal=False, window_size=16,
                        dropout_p=0.5)


def _dense_attention(q, k, v, window=0, seg=None):
    """Causal dense reference that also returns logsumexp [b, h, s]."""
    b, s, hq, d = q.shape
    rep = hq // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    diff = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    mask = diff >= 0
    if window:
        mask = jnp.logical_and(mask, diff < window)
    mask = mask[None, None]
    if seg is not None:
        mask = jnp.logical_and(
            mask, seg[:, None, :, None] == seg[:, None, None, :])
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    return out, lse


# s, block, kv heads, window, segment lengths, lse cotangent; each case
# runs at 1 and at 16 q heads a kv head. 512-blocks are worked in four
# 128-row strips; s = 1024 adds the tiles below the diagonal, which a
# window of 600 crosses one block row down.
SUBTILED = {
    "mha": (512, 512, 2, 0, None, False),
    "two_blocks": (1024, 512, 1, 0, None, False),
    "window": (512, 512, 2, 200, None, False),
    "segments": (512, 512, 2, 0, (200, 200, 112), False),
    "dlse": (512, 512, 2, 0, None, True),
    "two_blocks_window": (1024, 512, 1, 600, None, True),
}


@pytest.mark.parametrize("rep", [1, 16])
@pytest.mark.parametrize("case", sorted(SUBTILED))
def test_subtiled_causal_matches_reference(case, rep):
    """Forward and gradients where the diagonal tile is worked in strips
    that skip what lies above the diagonal, alone and in a GQA group of
    16, whose dk/dv the backward sums over the group."""
    from paddle_tpu.kernels import pallas_attention as pa

    s, blk, hk, window, seg_lens, dlse = SUBTILED[case]
    assert pa._strip_rows(blk, blk) == 128
    assert pa.causal_live_share(s, s, blk, blk, 128, True, window) < \
        pa.causal_live_share(s, s, blk, blk, 0, True, window)
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((1, s, hk * rep, 128)),
                    jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, hk, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, hk, 128)), jnp.float32)
    seg = None
    if seg_lens:
        seg = jnp.asarray(np.repeat(np.arange(len(seg_lens)), seg_lens),
                          jnp.int32)[None, :]

    def run_pallas(q, k, v):
        return pa.mha_with_lse(q, k, v, causal=True, q_block=blk,
                               k_block=blk, segment_ids=seg, window=window)

    def run_ref(q, k, v):
        return _dense_attention(q, k, v, window, seg)

    def loss(run):
        def f(q, k, v):
            o, lse = run(q, k, v)
            total = jnp.sum(o ** 2)
            # a non-zero lse cotangent, as ring attention's merge sends
            return total + jnp.sum(jnp.sin(lse)) if dlse else total
        return f

    o, lse = run_pallas(q, k, v)
    o_ref, lse_ref = run_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-3, atol=2e-3)
    gp = jax.grad(loss(run_pallas), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(run_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def _bwd_pallas_calls(rep, s, d=128):
    """The pallas_call equations of the flash backward's jaxpr at the
    cells' blocks, traced on abstract bf16 inputs (g 1, causal)."""
    from paddle_tpu.kernels import pallas_attention as pa

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    q, kv = spec(1, rep, s, d), spec(1, s, d)
    jaxpr = jax.make_jaxpr(lambda *a: pa._mha_bwd_impl(
        *a, None, None, d ** -0.5, True, 1024, 1024))(
            q, kv, kv, q, q, spec(1, rep, s, dtype=jnp.float32))
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]


def _vmem_limit(call):
    return call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes


def test_backward_is_one_call_at_rep16_s8192():
    """The Nemotron cell's attention (rep 16, s 8192, d 128): dq, dk and
    dv come out of ONE kernel, whose grid streams k blocks innermost and
    keeps 16 MiB of whole-length dk/dv beside the tiles."""
    (call,) = _bwd_pallas_calls(16, 8192)
    assert call.params["grid_mapping"].grid == (1, 16, 8, 8)
    assert [a.shape for a in call.params["out_avals"]] == [
        (1, 16 * 8, 1024, 128), (1, 8, 1024, 128), (1, 8, 1024, 128)]
    assert _vmem_limit(call) == (16 << 20) + 2 * 8192 * 128 * (4 + 2 * 2)


def test_backward_vmem_stops_growing_with_the_group():
    """While the group's whole dq fits the budget the grid streams q
    blocks innermost and asks for that dq (rep 1 and 2 at s 8192); past
    it, what the kernel asks of VMEM follows the kv length alone: the
    same at 4, 16 and 64 q heads a kv head, the whole-length part twice
    as large at twice the length."""
    def grid_and_limit(rep, s):
        (call,) = _bwd_pallas_calls(rep, s)
        return call.params["grid_mapping"].grid, _vmem_limit(call) - (16 << 20)

    whole_dkv = 2 * 8192 * 128 * 8
    assert grid_and_limit(1, 8192) == ((1, 8, 1, 8), 8192 * 128 * 8)
    assert grid_and_limit(2, 8192) == ((1, 8, 2, 8), 2 * 8192 * 128 * 8)
    for rep in (4, 16, 64):
        assert grid_and_limit(rep, 8192) == ((1, rep, 8, 8), whole_dkv)
    assert grid_and_limit(16, 16384)[1] == 2 * whole_dkv


@pytest.fixture
def whole_dq_budget(monkeypatch):
    """set(n) sets the budget under which the backward keeps the group's
    whole dq. The kernels' calls are jitted and the budget is baked into
    a cached trace, so JAX's caches are cleared with every change."""
    from paddle_tpu.kernels import pallas_attention as pa

    def set_(n):
        monkeypatch.setattr(pa, "_WHOLE_DQ_VMEM_BUDGET", n)
        jax.clear_caches()

    yield set_
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("window", [0, 600])
def test_both_grid_orders_give_bitwise_equal_gradients(window,
                                                       whole_dq_budget):
    """Streaming q blocks innermost (the whole dq kept) and k blocks
    innermost (the whole dk/dv kept) sum every gradient in the same
    order: the same bits, in strips and with an lse cotangent."""
    from paddle_tpu.kernels import pallas_attention as pa

    rng = np.random.default_rng(43)
    q = jnp.asarray(rng.standard_normal((1, 1024, 8, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1024, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1024, 2, 128)), jnp.float32)

    def grads():
        def f(q, k, v):
            o, lse = pa.mha_with_lse(q, k, v, causal=True, q_block=512,
                                     k_block=512, window=window)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    whole_dq_budget(1 << 40)
    q_inner = grads()
    whole_dq_budget(0)
    k_inner = grads()
    for a, b in zip(q_inner, k_inner):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mha_grad_window_and_lse_cotangent_at_rep16():
    """A group of 16 q heads over 2 kv heads with a window, whole tiles
    (blocks of 256 are not cut in strips) three k blocks deep and a
    non-zero lse cotangent: forward, lse and the three gradients match
    the dense reference."""
    from paddle_tpu.kernels import pallas_attention as pa

    rng = np.random.default_rng(41)
    s, hk, window = 768, 2, 300
    q = jnp.asarray(rng.standard_normal((1, s, 16 * hk, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, hk, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, hk, 128)), jnp.float32)

    def loss(run):
        def f(q, k, v):
            o, lse = run(q, k, v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.cos(lse))
        return f

    def run_pallas(q, k, v):
        return pa.mha_with_lse(q, k, v, causal=True, q_block=256,
                               k_block=256, window=window)

    def run_ref(q, k, v):
        return _dense_attention(q, k, v, window)

    for a, b in zip(run_pallas(q, k, v), run_ref(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    gp = jax.grad(loss(run_pallas), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(run_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_causal_live_share_pins():
    """The static count the causal pruning is judged by."""
    from paddle_tpu.kernels import pallas_attention as pa

    share = pa.causal_live_share
    # whole 1024-tiles, the kernel before PR 30: 3 of 4
    assert share(2048, 2048, 1024, 1024, 0) == 0.75
    # what the defaults choose at the benchmark cell's s = 2048
    rows = pa._strip_rows(pa.DEFAULT_Q_BLOCK, pa.DEFAULT_K_BLOCK)
    assert rows == 128
    assert share(2048, 2048, pa.DEFAULT_Q_BLOCK, pa.DEFAULT_K_BLOCK,
                 rows) == 0.53125 <= 0.625
    assert share(2048, 2048, 1024, 1024, 512) == 0.625
    assert share(2048, 2048, 1024, 1024, rows, causal=False) == 1.0
    assert pa._strip_rows(1024, 1024, causal=False) == 0
    # blocks of 128-256 and non-square tiles are worked whole
    assert pa._strip_rows(256, 256) == 0 and pa._strip_rows(512, 1024) == 0
    assert share(512, 512, 128, 256, 0) == 0.75
    # the window prunes from the other side: a band of 256 over s = 1024
    # in 512-blocks keeps 3 whole tiles, in 128-row strips 21 squares of
    # 64 (a strip sees at most 3: q_pos - k_pos < 256 reaches 2 back)
    assert share(1024, 1024, 512, 512, 0, window=256) == 0.75
    assert share(1024, 1024, 512, 512, 128, window=256) == 21 / 64
    # the triangle itself, (s + 1) / 2s, is the floor
    assert share(2048, 2048, 1024, 1024, rows) > 2049 / 4096


def test_tile_strips_work_list():
    """The diagonal tile's strips see a growing run of columns and mask
    only the 128 the diagonal crosses; a tile below it runs whole."""
    from paddle_tpu.kernels import pallas_attention as pa

    assert pa._tile_strips(0, 512, 128, 0) == tuple(
        (r, r + 128, 0, r + 128, ((r, r + 128, 0),))
        for r in range(0, 512, 128))
    assert pa._tile_strips(1, 512, 128, 0) == ((0, 512, 0, 512, ()),)
    assert pa._tile_strips(-1, 512, 128, 0) == ()
    # a window of 512, one block row down: the band's far edge
    assert pa._tile_strips(1, 512, 128, 512) == tuple(
        (r, r + 128, r, 512, ((0, 128, 512),)) for r in range(0, 512, 128))


@pytest.mark.parametrize("segments", [False, True])
def test_flash_attention_under_mesh_runs_per_shard(segments, monkeypatch):
    """Under a multi-device mesh the kernel is wrapped in a shard_map
    (the chip's SPMD partitioner refuses a bare Mosaic call): batch over
    (dp, fsdp), heads over tp, GQA kv heads included — same numbers as
    the dense reference, forward and grad."""
    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed.sharding import mesh_context
    from paddle_tpu.kernels.flash_attention import (
        _segment_reference_attention,
        flash_attention,
    )

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    mesh = dist.build_mesh(dp=2, fsdp=2, tp=2)
    rng = np.random.default_rng(7)
    b, s, d = 4, 128, 32
    q = jnp.asarray(rng.standard_normal((b, s, 4, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, 2, d)), jnp.float32)
    seg = (jnp.asarray(np.arange(s)[None, :] // 48 + np.arange(b)[:, None],
                       jnp.int32) if segments else None)

    def ref(q, k, v):
        if segments:
            return _segment_reference_attention(q, k, v, seg, causal=True)
        return _reference_attention(q, k, v, causal=True)

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids=seg)

    with mesh_context(mesh):
        assert "shard_map" in str(jax.make_jaxpr(fn)(q, k, v))
        out = jax.jit(fn)(q, k, v)
        g = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), (0, 1, 2)))(
            q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)
