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


def test_mha_grad_two_pass_path_matches_fused():
    """n_kb > _FUSED_BWD_MAX_KB falls back to the two-pass backward;
    both paths must produce identical gradients."""
    from paddle_tpu.kernels import pallas_attention as pa

    rng = np.random.default_rng(11)
    # seq 768 / k_block 128 -> n_kb = 6 > pa._FUSED_BWD_MAX_KB
    # (two-pass); k_block 256 -> n_kb = 3 (fused). Same math either way.
    assert 768 // 128 > pa._FUSED_BWD_MAX_KB >= 768 // 256
    q = jnp.asarray(rng.standard_normal((1, 2, 768, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 768, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 768, 64)), jnp.float32)

    def loss(blk):
        def f(q, k, v):
            return jnp.sum(
                mha(q, k, v, causal=True, q_block=128, k_block=blk) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_two = loss(128)   # n_kb=6: two-pass
    g_fused = loss(256)  # n_kb=3: fused
    for a, b in zip(g_two, g_fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


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
