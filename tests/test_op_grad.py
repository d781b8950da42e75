"""OpTest-style numeric gradient checker (parity model:
test/legacy_test/op_test.py — the reference's core op-correctness
mechanism checks analytic gradients against central finite differences).

TPU-world form: for every op with parameters we verify
⟨∇f, dir⟩ ≈ (φ(h) − φ(−h)) / 2h for random directions ``dir``, with the
check run in fp64 on CPU (`jax.experimental.enable_x64`) so the finite
difference itself is trustworthy. The same directional check (fp32,
looser tolerance) covers the Pallas kernels' custom VJPs — those are
hand-written backward passes, exactly what a finite-difference check
exists to catch. Plus a bf16/fp32 dtype sweep on the forward surface.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from jax import enable_x64 as _enable_x64

# core-engine fast lane (see README "Tests")
pytestmark = pytest.mark.fast


def _rand(shape, seed, scale=1.0):
    return np.random.default_rng(seed).standard_normal(shape) * scale


def directional_grad_check(fn, args, argnums, h=1e-5, rtol=1e-4, atol=1e-6,
                           seed=0):
    """Check ⟨jax.grad(fn), dir⟩ against a central difference of the
    scalar map t ↦ fn(x + t·dir), per differentiable argument."""
    rng = np.random.default_rng(seed + 1000)
    args = [jnp.asarray(a) for a in args]
    grads = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=argnums)(*args)
    if not isinstance(grads, tuple):
        grads = (grads,)
    for argnum, g in zip(argnums, grads):
        x = args[argnum]
        direction = rng.standard_normal(x.shape).astype(np.float64)
        direction /= np.linalg.norm(direction) + 1e-30
        d = jnp.asarray(direction, x.dtype)

        def phi(t):
            shifted = list(args)
            shifted[argnum] = x + t * d
            return float(jnp.sum(fn(*shifted)))

        numeric = (phi(h) - phi(-h)) / (2 * h)
        analytic = float(jnp.sum(g * d))
        np.testing.assert_allclose(
            analytic, numeric, rtol=rtol, atol=atol,
            err_msg=f"analytic vs central-difference grad for arg {argnum}")


# ---------------------------------------------------------------------------
# op inventory: (name, fn, args builder, argnums to check)
# Inputs chosen away from non-differentiable kinks (|x| > 0.05 for
# relu-family) so the finite difference is valid.
# ---------------------------------------------------------------------------
def _kink_free(shape, seed, margin=0.05):
    x = _rand(shape, seed)
    return np.where(np.abs(x) < margin, x + 4 * margin, x)


_label3 = np.array([0, 2, 1])


OPS = [
    ("linear", lambda x, w, b: F.linear(x, w, b),
     lambda: [_rand((4, 8), 0), _rand((8, 6), 1), _rand((6,), 2)],
     (0, 1, 2)),
    ("matmul", jnp.matmul,
     lambda: [_rand((4, 8), 3), _rand((8, 5), 4)], (0, 1)),
    ("embedding", lambda w: F.embedding(jnp.asarray([[0, 2], [1, 1]]), w),
     lambda: [_rand((5, 8), 5)], (0,)),
    ("relu", F.relu, lambda: [_kink_free((4, 8), 6)], (0,)),
    ("relu6", F.relu6,
     lambda: [np.clip(_kink_free((4, 8), 7), -5, 5.9)], (0,)),
    ("gelu", F.gelu, lambda: [_rand((4, 8), 8)], (0,)),
    ("gelu_tanh", functools.partial(F.gelu, approximate=True),
     lambda: [_rand((4, 8), 9)], (0,)),
    ("silu", F.silu, lambda: [_rand((4, 8), 10)], (0,)),
    ("sigmoid", F.sigmoid, lambda: [_rand((4, 8), 11)], (0,)),
    ("tanh", F.tanh, lambda: [_rand((4, 8), 12)], (0,)),
    ("leaky_relu", F.leaky_relu, lambda: [_kink_free((4, 8), 13)], (0,)),
    ("elu", F.elu, lambda: [_kink_free((4, 8), 14)], (0,)),
    ("softplus", F.softplus, lambda: [_rand((4, 8), 15)], (0,)),
    ("mish", F.mish, lambda: [_rand((4, 8), 16)], (0,)),
    ("softmax", F.softmax, lambda: [_rand((4, 8), 17)], (0,)),
    ("log_softmax", F.log_softmax, lambda: [_rand((4, 8), 18)], (0,)),
    ("swiglu", F.swiglu, lambda: [_rand((4, 16), 19)], (0,)),
    ("layer_norm",
     lambda x, w, b: F.layer_norm(x, (8,), w, b),
     lambda: [_rand((4, 8), 20), 1 + 0.1 * _rand((8,), 21),
              _rand((8,), 22)],
     (0, 1, 2)),
    ("rms_norm", lambda x, w: F.rms_norm(x, w),
     lambda: [_rand((4, 8), 23), 1 + 0.1 * _rand((8,), 24)], (0, 1)),
    ("group_norm",
     lambda x, w, b: F.group_norm(x, 2, w, b),
     lambda: [_rand((2, 4, 3, 3), 25), 1 + 0.1 * _rand((4,), 26),
              _rand((4,), 27)],
     (0, 1, 2)),
    ("cross_entropy",
     lambda x: F.cross_entropy(x, jnp.asarray(_label3)),
     lambda: [_rand((3, 5), 28)], (0,)),
    ("cross_entropy_smooth",
     lambda x: F.cross_entropy(x, jnp.asarray(_label3),
                               label_smoothing=0.1),
     lambda: [_rand((3, 5), 29)], (0,)),
    ("nll_loss",
     lambda x: F.nll_loss(F.log_softmax(x), jnp.asarray(_label3)),
     lambda: [_rand((3, 5), 30)], (0,)),
    ("mse_loss",
     lambda x, y: F.mse_loss(x, y),
     lambda: [_rand((4, 8), 31), _rand((4, 8), 32)], (0, 1)),
    ("bce_with_logits",
     lambda x: F.binary_cross_entropy_with_logits(
         x, jnp.asarray((_rand((4, 8), 33) > 0).astype(np.float64))),
     lambda: [_rand((4, 8), 34)], (0,)),
    ("conv2d",
     lambda x, w, b: F.conv2d(x, w, b, stride=1, padding=1),
     lambda: [_rand((2, 3, 6, 6), 35), _rand((4, 3, 3, 3), 36) * 0.3,
              _rand((4,), 37)],
     (0, 1, 2)),
    ("conv1d",
     lambda x, w: F.conv1d(x, w, padding=1),
     lambda: [_rand((2, 3, 8), 38), _rand((4, 3, 3), 39) * 0.3], (0, 1)),
    ("conv2d_transpose",
     lambda x, w: F.conv2d_transpose(x, w, stride=2),
     lambda: [_rand((1, 3, 4, 4), 40), _rand((3, 2, 2, 2), 41) * 0.3],
     (0, 1)),
    ("avg_pool2d",
     lambda x: F.avg_pool2d(x, 2), lambda: [_rand((2, 3, 6, 6), 42)], (0,)),
    ("max_pool2d",
     lambda x: F.max_pool2d(x, 2),
     # well-separated values → argmax stable under ±h perturbation
     lambda: [np.arange(72).reshape(2, 1, 6, 6)
              + 0.1 * _rand((2, 1, 6, 6), 43)], (0,)),
    ("sdpa",
     lambda q, k, v: F.scaled_dot_product_attention(q, k, v, is_causal=True),
     lambda: [_rand((1, 8, 2, 4), 44), _rand((1, 8, 2, 4), 45),
              _rand((1, 8, 2, 4), 46)],
     (0, 1, 2)),
    ("normalize", F.normalize, lambda: [_rand((4, 8), 47)], (0,)),
    ("cosine_similarity",
     F.cosine_similarity,
     lambda: [_rand((4, 8), 48), _rand((4, 8), 49)], (0, 1)),
    ("glu", F.glu, lambda: [_rand((4, 16), 50)], (0,)),
    # --- round-3 additions: CTC, resampling, signal ---
    ("ctc_loss",
     lambda lg: F.ctc_loss(
         lg, jnp.asarray([[1, 2, 1], [2, 2, 1]]),
         jnp.asarray([8, 7]), jnp.asarray([3, 2]), reduction="sum"),
     lambda: [_rand((8, 2, 4), 80)], (0,)),
    ("interpolate_bilinear",
     lambda x: F.interpolate(x, size=(7, 5), mode="bilinear"),
     lambda: [_rand((2, 3, 4, 6), 81)], (0,)),
    ("interpolate_bicubic",
     lambda x: F.interpolate(x, size=(9, 5), mode="bicubic"),
     lambda: [_rand((2, 2, 5, 7), 82)], (0,)),
    ("grid_sample",
     lambda x, g: F.grid_sample(x, g, padding_mode="border"),
     # grad w.r.t. grid is piecewise (kinks at cell crossings): place
     # sampling points mid-cell (pix = k + 0.5 → frac 0.5) so the
     # central difference stays inside one cell
     lambda: [_rand((1, 2, 6, 6), 83),
              ((np.arange(1, 5)[None, :, None, None] + 0.5
                + 0.1 * _rand((1, 4, 4, 2), 84))
               / 2.5 - 1.0).astype(np.float64)],
     (0, 1)),
    ("stft_power",
     lambda x: jnp.sum(jnp.abs(__import__(
         "paddle_tpu.signal", fromlist=["stft"]).stft(x, 16, 8)) ** 2),
     lambda: [_rand((2, 64), 85)], (0,)),
    ("adaptive_avg_pool_nondiv",
     lambda x: F.adaptive_avg_pool2d(x, (3, 4)),
     lambda: [_rand((2, 2, 7, 9), 86)], (0,)),
]


@pytest.mark.parametrize("name,fn,build,argnums", OPS,
                         ids=[o[0] for o in OPS])
def test_numeric_grad_fp64(name, fn, build, argnums):
    with _enable_x64(True):
        args = [jnp.asarray(a, jnp.float64)
                if np.asarray(a).dtype.kind == "f" else jnp.asarray(a)
                for a in build()]
        directional_grad_check(fn, args, argnums)


# ---------------------------------------------------------------------------
# Pallas custom VJPs (fp32 — the kernels are fp32-accumulating by design).
# A random-direction probe drowns in f32 summation noise (the directional
# derivative of a random direction cancels to ~1e-6/element), so these use
# per-coordinate central differences at the largest-|grad| coordinates,
# where the signal is orders of magnitude above the noise floor.
# ---------------------------------------------------------------------------
def _f32(x):
    return jnp.asarray(x, jnp.float32)


def coordinate_grad_check(fn, args, argnums, h=0.05, rtol=3e-2, n_coords=6):
    args = [jnp.asarray(a) for a in args]
    grads = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=argnums)(*args)
    if not isinstance(grads, tuple):
        grads = (grads,)
    for argnum, g in zip(argnums, grads):
        x = args[argnum]
        gn = np.asarray(g).ravel()
        coords = np.argsort(-np.abs(gn))[:n_coords]
        for c in coords:
            e = np.zeros(x.size, np.float32)
            e[c] = h
            e = jnp.asarray(e.reshape(x.shape))
            shifted_p, shifted_m = list(args), list(args)
            shifted_p[argnum] = x + e
            shifted_m[argnum] = x - e
            numeric = (float(jnp.sum(fn(*shifted_p)))
                       - float(jnp.sum(fn(*shifted_m)))) / (2 * h)
            np.testing.assert_allclose(
                gn[c], numeric, rtol=rtol, atol=1e-3,
                err_msg=f"arg {argnum} coord {c}")


def test_numeric_grad_flash_mha():
    from paddle_tpu.kernels.pallas_attention import mha

    q = _f32(_rand((1, 128, 2, 64), 60) * 0.5)
    k = _f32(_rand((1, 128, 1, 64), 61) * 0.5)  # GQA path
    v = _f32(_rand((1, 128, 1, 64), 62) * 0.5)
    coordinate_grad_check(
        lambda q, k, v: mha(q, k, v, causal=True, q_block=128, k_block=128),
        [q, k, v], (0, 1, 2))


def test_numeric_grad_flash_mha_with_lse():
    from paddle_tpu.kernels.pallas_attention import mha_with_lse

    q = _f32(_rand((1, 128, 1, 128), 63) * 0.5)
    k = _f32(_rand((1, 128, 1, 128), 64) * 0.5)
    v = _f32(_rand((1, 128, 1, 128), 65) * 0.5)

    def fn(q, k, v):
        o, lse = mha_with_lse(q, k, v, causal=False)
        return jnp.sum(o) + jnp.sum(lse)  # exercises the dlse path too

    coordinate_grad_check(fn, [q, k, v], (0, 1, 2))


def test_numeric_grad_selective_scan():
    from paddle_tpu.kernels.selective_scan import chunked_selective_scan

    rng = np.random.default_rng(66)
    b, s, d, n = 1, 32, 16, 4
    u = _f32(rng.standard_normal((b, s, d)))
    delta = _f32(np.abs(rng.standard_normal((b, s, d))) * 0.1)
    A = _f32(-np.abs(rng.standard_normal((d, n))))
    B = _f32(rng.standard_normal((b, s, n)))
    C = _f32(rng.standard_normal((b, s, n)))
    D = _f32(rng.standard_normal((d,)))
    coordinate_grad_check(
        lambda *a: chunked_selective_scan(*a, chunk=16),
        [u, delta, A, B, C, D], (0, 1, 2, 3, 4, 5))


def test_numeric_grad_rope():
    from paddle_tpu.kernels.rope import apply_rope, rope_frequencies

    q = _f32(_rand((1, 32, 2, 64), 67))
    k = _f32(_rand((1, 32, 2, 64), 68))
    cos, sin = rope_frequencies(64, 32)

    def fn(q, k):
        oq, ok = apply_rope(q, k, cos, sin)
        return jnp.sum(oq) + jnp.sum(ok)

    coordinate_grad_check(fn, [q, k], (0, 1))


def test_numeric_grad_ring_attention():
    from paddle_tpu.kernels.ring_attention import ring_attention
    from paddle_tpu.distributed.sharding import mesh_context

    import paddle_tpu.distributed as dist

    mesh = dist.build_mesh(sep=2)
    q = _f32(_rand((1, 256, 2, 64), 69) * 0.5)
    k = _f32(_rand((1, 256, 2, 64), 70) * 0.5)
    v = _f32(_rand((1, 256, 2, 64), 71) * 0.5)
    fn = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh,
                                                axis="sep", causal=True))
    with mesh_context(mesh):
        coordinate_grad_check(fn, [q, k, v], (0, 1, 2))


# ---------------------------------------------------------------------------
# dtype sweep: ops must accept bf16 inputs and stay close to fp32
# ---------------------------------------------------------------------------
SWEEP_OPS = [
    ("linear", lambda x: F.linear(x, jnp.asarray(_rand((8, 6), 1), x.dtype))),
    ("gelu", F.gelu),
    ("silu", F.silu),
    ("softmax", F.softmax),
    ("layer_norm", lambda x: F.layer_norm(x, (8,))),
    ("rms_norm", lambda x: F.rms_norm(x)),
]


@pytest.mark.parametrize("name,fn", SWEEP_OPS, ids=[o[0] for o in SWEEP_OPS])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_dtype_sweep(name, fn, dtype):
    x32 = jnp.asarray(_rand((4, 8), 80), jnp.float32)
    x = x32.astype(dtype)
    out = fn(x)
    ref = fn(x32)
    assert out.shape == ref.shape
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref),
        rtol=0.1 if dtype == "bfloat16" else 1e-6, atol=0.1)
