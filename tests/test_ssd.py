"""The chunked SSD (kernels/ssd.py) against the step-by-step recurrence
of the benchmark's plain reference (chipbench/reference/nemotron_h.py).

Both are float32 here (conftest pins matmul precision ``highest``), so
they differ only by the order of the sums: the chunked form multiplies
decays that the recurrence applies one after the other. Tolerances are
a few float32 roundings of values of order 1 summed over the sequence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from paddle_tpu.kernels import ssd

# jitted: op by op, every einsum would compile on its own
ssd_chunked = jax.jit(ssd.ssd_chunked, static_argnames="chunk")


@jax.jit
def ssd_recurrent(x, dt, A, B, C, D):
    """The plain reference's recurrence, one step at a time, float32
    (it takes B and C already repeated per head, and adds no D)."""
    f32 = jnp.float32
    rep = x.shape[2] // B.shape[2]
    x = x.astype(f32)
    return ref._recurrence(
        x, dt, A, jnp.repeat(B.astype(f32), rep, axis=2),
        jnp.repeat(C.astype(f32), rep, axis=2)) + x * D[:, None]


def _inputs(seed, b, s, h, p, g, n, d_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    # dt as softplus gives it, A = -exp(A_log) with A_log = log U(1, 16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 3.0)
    A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    D = d_scale * jax.random.normal(ks[5], (h,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("s,chunk", [(16, 16), (64, 16), (40, 16)],
                         ids=["one_chunk", "four_chunks", "padded"])
def test_chunked_forward_matches_recurrence(s, chunk):
    args = _inputs(0, 2, s, 4, 8, 2, 16)
    want = ssd_recurrent(*args)
    got = ssd_chunked(*args, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # 1e-5 of the largest output: float32 sums of up to 64 terms
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_chunked_gradients_match_recurrence():
    """Several chunks, a non-zero D: every input's gradient."""
    args = _inputs(1, 1, 48, 4, 8, 2, 16)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 48, 4, 8))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    want = jax.jit(jax.grad(loss(ssd_recurrent), range(6)))(*args)
    got = jax.jit(jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=16)),
                           range(6)))(*args)
    for name, g, r in zip("x dt A B C D".split(), got, want):
        # 2e-5 of the gradient's largest entry: the same float32 sums,
        # once more through the backward pass
        np.testing.assert_allclose(
            g, r, rtol=0, atol=2e-5 * float(jnp.abs(r).max()),
            err_msg=name)


def test_d_is_a_skip_connection():
    x, dt, A, B, C, D = _inputs(2, 1, 32, 4, 8, 2, 16)
    base = ssd_chunked(x, dt, A, B, C, jnp.zeros_like(D), chunk=16)
    with_d = ssd_chunked(x, dt, A, B, C, D, chunk=16)
    np.testing.assert_allclose(with_d - base, x * D[:, None], atol=1e-5)


def test_bf16_inputs_give_float32_output_near_the_recurrence():
    """As training calls it: x, B, C in bf16, dt and A float32. The
    chunked form rounds the decayed scores to bf16 before the product,
    so it sits a bf16 rounding (2**-8) of the output's size away."""
    x, dt, A, B, C, D = _inputs(3, 1, 64, 4, 8, 2, 16)
    x, B, C = (v.astype(jnp.bfloat16) for v in (x, B, C))
    want = ssd_recurrent(x, dt, A, B, C, D)
    got = ssd_chunked(x, dt, A, B, C, D, chunk=16)
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err < 2 ** -7, err
