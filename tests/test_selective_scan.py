"""Chunked Pallas selective scan vs associative-scan reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.selective_scan import chunked_selective_scan
from paddle_tpu.models.mamba import (
    MambaConfig,
    MambaForCausalLM,
    selective_scan,
)


def _inputs(b=2, s=64, d=32, n=8, seed=0):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((b, s, d)).astype(np.float32)
    delta = np.abs(rng.standard_normal((b, s, d))).astype(np.float32) * 0.1
    A = -np.abs(rng.standard_normal((d, n))).astype(np.float32)
    B = rng.standard_normal((b, s, n)).astype(np.float32)
    C = rng.standard_normal((b, s, n)).astype(np.float32)
    D = rng.standard_normal((d,)).astype(np.float32)
    return map(jnp.asarray, (u, delta, A, B, C, D))


@pytest.mark.parametrize("chunk", [16, 64, 4, 6, 8, 32, 128])
def test_chunked_matches_associative(chunk):
    # slabs of 8 steps, and of 4 and 2 where the chunk is 4 or 6
    u, delta, A, B, C, D = _inputs(s={4: 64, 6: 48, 128: 256}.get(chunk, 64))
    ref = np.asarray(selective_scan(u, delta, A, B, C, D))
    out = np.asarray(chunked_selective_scan(u, delta, A, B, C, D,
                                            chunk=chunk))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_chunked_d_blocking():
    u, delta, A, B, C, D = _inputs(d=64)
    ref = np.asarray(selective_scan(u, delta, A, B, C, D))
    out = np.asarray(chunked_selective_scan(u, delta, A, B, C, D,
                                            chunk=32, d_block=32))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("at_step", [0, 7, 15, 23])
def test_state_carries_across_chunks(at_step):
    # long-memory input: one impulse, tiny delta afterwards → later
    # outputs depend on state carried through many chunk boundaries.
    # With chunks of 16 the impulse is in the first step, in the last
    # step of a slab of 8 (read in the first of the next slab), in the
    # last of a chunk (read in the next chunk's first) and in the last
    # of a slab of the second chunk
    b, s, d, n = 1, 64, 8, 4
    u = np.zeros((b, s, d), np.float32)
    u[:, at_step] = 1.0
    delta = np.full((b, s, d), 0.01, np.float32)
    A = -np.full((d, n), 0.1, np.float32)
    B = np.ones((b, s, n), np.float32)
    C = np.ones((b, s, n), np.float32)
    D = np.zeros((d,), np.float32)
    args = map(jnp.asarray, (u, delta, A, B, C, D))
    out = np.asarray(chunked_selective_scan(*args, chunk=16))
    ref = np.asarray(selective_scan(*map(jnp.asarray,
                                         (u, delta, A, B, C, D))))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)
    assert not out[0, :at_step].any()         # nothing before the impulse
    assert abs(out[0, at_step + 1].sum()) > 1e-4  # read one step later
    assert abs(out[0, -1].sum()) > 1e-4  # state survived to the end


def test_mamba_model_chunked_flag():
    import paddle_tpu as pt

    pt.seed(0)
    cfg = MambaConfig.tiny(use_chunked_scan=True, scan_chunk=8)
    model = MambaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    logits = model(jnp.asarray(ids))
    cfg2 = MambaConfig.tiny()
    pt.seed(0)
    model2 = MambaForCausalLM(cfg2)
    ref = model2(jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_chunked_grad_flows():
    u, delta, A, B, C, D = _inputs(b=1, s=16, d=8, n=4)

    def loss(u, delta, A, B, C, D):
        return jnp.sum(chunked_selective_scan(u, delta, A, B, C, D,
                                              chunk=8) ** 2)

    g = jax.grad(loss, argnums=(0, 2))(u, delta, A, B, C, D)
    assert all(float(jnp.linalg.norm(x)) > 0 for x in g)


# chunk, n, d, d_block (None: the kernel's own choice), batch, seq
_GRAD_CASES = {
    "chunk16": (16, 8, 32, None, 2, 64),
    "chunk8_n16": (8, 16, 32, None, 2, 32),
    "chunk32_blocks": (32, 8, 64, 32, 2, 64),     # d_block narrower than d
    "chunk128_n16": (128, 16, 16, 16, 1, 256),    # the cell's chunk and n
    "chunk4_slab4": (4, 8, 16, None, 2, 16),
    "chunk6_slab2_blocks": (6, 16, 32, 16, 2, 24),
}


def _grads(fn, args, cotangent):
    out, vjp = jax.vjp(fn, *args)
    return out, vjp(cotangent.astype(out.dtype))


@pytest.mark.parametrize("case", list(_GRAD_CASES))
def test_chunked_bwd_grads_match_associative(case):
    """The output and all six gradients from the recompute-based Pallas
    backward must match autodiff through the associative reference, to
    float32 rounding."""
    chunk, n, d, d_block, b, s = _GRAD_CASES[case]
    args = tuple(_inputs(b=b, s=s, d=d, n=n, seed=3))
    g = jnp.cos(jnp.arange(b * s * d, dtype=jnp.float32)).reshape(b, s, d)
    out, gc = _grads(lambda *a: chunked_selective_scan(
        *a, chunk=chunk, d_block=d_block), args, g)
    ref, gr = _grads(selective_scan, args, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    for name, a, r in zip("u delta A B C D".split(), gc, gr):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=1e-4, atol=2e-5 * scale,
            err_msg=f"grad mismatch for {name}")


def _as_bf16(args):
    """u, B, C as the model hands them (bf16); delta, A, D float32."""
    u, delta, A, B, C, D = args
    bf = jnp.bfloat16
    return u.astype(bf), delta, A, B.astype(bf), C.astype(bf), D


@pytest.mark.parametrize("d_block", [None, 32])
def test_bf16_operands_widened_exactly_and_rounded_once(d_block):
    """bf16 ``u``, ``B``, ``C`` with float32 ``delta``: the kernel
    widens them itself (exact), so ``y + u D`` is the float32 run's on
    the same values to the bit (float32 out: the caller rounds it once),
    ``du``, ``dB``, ``dC`` are the float32 run's cast once to bf16, and
    the float32 gradients are the float32 run's to the bit."""
    low = _as_bf16(tuple(_inputs(b=2, s=32, d=64, n=16, seed=5)))
    wide = tuple(x.astype(jnp.float32) for x in low)
    g = jnp.sin(jnp.arange(2 * 32 * 64, dtype=jnp.float32)).reshape(
        2, 32, 64).astype(jnp.bfloat16)

    def fn(*a):
        return chunked_selective_scan(*a, chunk=16, d_block=d_block)

    y_low, g_low = _grads(fn, low, g)
    y_wide, g_wide = _grads(fn, wide, g)
    assert y_low.dtype == y_wide.dtype == jnp.float32
    assert [x.dtype for x in g_low] == [x.dtype for x in low]
    np.testing.assert_array_equal(np.asarray(y_low), np.asarray(y_wide))
    for name, a, r in zip("u delta A B C D".split(), g_low, g_wide):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32),
            np.asarray(r.astype(a.dtype), np.float32), err_msg=name)
    np.testing.assert_allclose(np.asarray(y_low),
                               np.asarray(selective_scan(*wide)),
                               rtol=1e-4, atol=1e-4)


def test_chunked_bwd_no_bsdn_materialization():
    """The backward jaxpr must contain no [b,s,d,n] (or [b,s,n,d])
    tensor — the whole point of the recompute-based VJP. (The round-2
    backward called jax.vjp(associative_selective_scan), whose jaxpr is
    full of them.)"""
    b, s, d, n = 2, 64, 32, 8
    u, delta, A, B, C, D = _inputs(b=b, s=s, d=d, n=n)

    def loss(*args):
        return jnp.sum(chunked_selective_scan(*args, chunk=16) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(
        u, delta, A, B, C, D)
    text = str(jaxpr)
    for shape in (f"{b},{s},{d},{n}", f"{b},{s},{n},{d}"):
        assert f"f32[{shape}]" not in text, (
            f"[b,s,d,n] tensor materialized in backward: f32[{shape}]")
    # sanity: the associative form DOES contain it (detector works)
    ref_jaxpr = jax.make_jaxpr(
        jax.grad(lambda *a: jnp.sum(selective_scan(*a) ** 2),
                 argnums=tuple(range(6))))(u, delta, A, B, C, D)
    assert f"f32[{b},{s},{d},{n}]" in str(ref_jaxpr)
