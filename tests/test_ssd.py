"""The chunked SSD (kernels/ssd.py: a Pallas kernel pair, run here in
interpret mode) against the step-by-step recurrence of the benchmark's
plain reference (chipbench/reference/nemotron_h.py).

Both are float32 here unless a case says bf16 (conftest pins matmul
precision ``highest``), so they differ only by the order of the sums:
the chunked form multiplies decays that the recurrence applies one after
the other. Tolerances are a few float32 roundings of values of order 1
summed over the sequence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from paddle_tpu.kernels import ssd

# jitted: op by op, every operation around the kernels would compile on
# its own
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


def _inputs(seed, b, s, h, p, g, n, d_scale=1.0, A=None, bf16=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    # dt as softplus gives it, A = -exp(A_log) with A_log = log U(1, 16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 3.0)
    if A is None:
        A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    D = d_scale * jax.random.normal(ks[5], (h,))
    if bf16:    # as training calls it: dt, A and D stay float32
        x, B, C = (v.astype(jnp.bfloat16) for v in (x, B, C))
    return x, dt, jnp.asarray(A, jnp.float32), B, C, D


# name: (inputs' keywords, chunk, tolerance of the forward pass and of the
# gradients as a share of the largest entry)
CASES = {
    "one_chunk": (dict(b=2, s=16, h=4, p=8, g=2, n=16), 16, 1e-5, 2e-5),
    "four_chunks": (dict(b=2, s=64, h=4, p=8, g=2, n=16), 16, 1e-5, 2e-5),
    # steps of dt = 0 fill the last chunk; their gradients are cut
    "padded": (dict(b=2, s=40, h=4, p=8, g=2, n=16), 16, 1e-5, 2e-5),
    # a state that outlives five chunks beside one that is gone within a
    # chunk: exp(A dt) a step is 0.9995 for two heads and 0.45 for two
    "carried": (dict(b=1, s=96, h=4, p=8, g=2, n=16,
                     A=(-0.01, -16.0, -0.01, -16.0)), 16, 1e-5, 2e-5),
    # the tiling of nemotron3-nano-train-8k: chunk 128, 8 heads of 64 a
    # group, state 128; two groups, three chunks
    "cell_tiling": (dict(b=1, s=384, h=16, p=64, g=2, n=128), 128,
                    1e-5, 2e-5),
    # ... in training's types: mix, the decayed x and what enters a
    # product from a float32 state or cotangent are rounded to bf16, so
    # a result sits a few bf16 roundings (2**-8) of its size away
    "cell_tiling_bf16": (dict(b=1, s=384, h=16, p=64, g=2, n=128, bf16=True),
                         128, 2 ** -7, 2 ** -6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_forward_matches_recurrence(case):
    kw, chunk, tol, _ = CASES[case]
    args = _inputs(0, **kw)
    want = ssd_recurrent(*args)
    got = ssd_chunked(*args, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # float32: 1e-5 of the largest output, sums of up to 64 terms
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize("case", sorted(set(CASES) - {"one_chunk"}))
def test_chunked_gradients_match_recurrence(case):
    """A non-zero D: every input's gradient."""
    kw, chunk, _, tol = CASES[case]
    args = _inputs(1, **kw)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    want = jax.jit(jax.grad(loss(ssd_recurrent), range(6)))(*args)
    got = jax.jit(jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=chunk)),
                           range(6)))(*args)
    for name, g, r in zip("x dt A B C D".split(), got, want):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        # float32: 2e-5 of the gradient's largest entry, the same sums
        # once more through the backward pass
        np.testing.assert_allclose(
            g.astype(jnp.float32), r.astype(jnp.float32), rtol=0,
            atol=tol * float(jnp.abs(r.astype(jnp.float32)).max()),
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
    x, dt, A, B, C, D = _inputs(3, 1, 64, 4, 8, 2, 16, bf16=True)
    want = ssd_recurrent(x, dt, A, B, C, D)
    got = ssd_chunked(x, dt, A, B, C, D, chunk=16)
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err < 2 ** -7, err
