"""linalg / fft / distribution / jit / quantization surfaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distribution, fft, jit, linalg, nn, quantization as q
from jax import enable_x64 as _enable_x64


def test_linalg_basics():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((4, 4)),
                    jnp.float32)
    spd = a @ a.T + 4 * jnp.eye(4)
    np.testing.assert_allclose(
        np.asarray(linalg.inv(spd) @ spd), np.eye(4), atol=1e-4
    )
    L = linalg.cholesky(spd)
    np.testing.assert_allclose(np.asarray(L @ L.T), np.asarray(spd),
                               rtol=1e-4, atol=1e-4)
    u, s, vt = linalg.svd(a)
    np.testing.assert_allclose(
        np.asarray((u * s) @ vt), np.asarray(a), rtol=1e-4, atol=1e-4
    )
    x = linalg.solve(spd, jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(spd @ x), 1.0, rtol=1e-4)


def test_fft_roundtrip():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fft.ifft(fft.fft(x)).real), np.asarray(x), atol=1e-5
    )


def test_distributions():
    pt.seed(3)
    n = distribution.Normal(0.0, 1.0)
    s = n.sample((1000,))
    assert abs(float(s.mean())) < 0.15
    np.testing.assert_allclose(
        float(n.log_prob(jnp.asarray(0.0))), -0.9189385, rtol=1e-5
    )
    kl = distribution.kl_divergence(
        distribution.Normal(0.0, 1.0), distribution.Normal(0.0, 1.0)
    )
    np.testing.assert_allclose(float(kl), 0.0, atol=1e-6)
    c = distribution.Categorical(logits=jnp.asarray([0.0, 0.0]))
    assert float(c.entropy()) == pytest.approx(np.log(2), rel=1e-5)
    b = distribution.Bernoulli(0.5)
    assert float(b.entropy()) == pytest.approx(np.log(2), rel=1e-4)


def test_jit_to_static_and_save_load(tmp_path):
    pt.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    traced = jit.to_static(net)
    x = jnp.ones((3, 4))
    ref = net(x)
    np.testing.assert_allclose(np.asarray(traced(x)), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    path = str(tmp_path / "model")
    jit.save(traced, path, input_spec=[x])
    loaded = jit.load(path)
    np.testing.assert_allclose(
        np.asarray(loaded(x)), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


def test_weight_only_int8():
    pt.seed(1)
    lin = nn.Linear(16, 8)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 16)),
                    jnp.float32)
    ref = np.asarray(lin(x))
    wql = q.WeightOnlyLinear(lin)
    out = np.asarray(wql(x))
    # int8 per-channel quantization error stays small
    denom = np.maximum(np.abs(ref), 1.0)
    assert np.max(np.abs(out - ref) / denom) < 0.05
    assert wql._buffers["qweight"].dtype == jnp.int8


def test_quantize_model_sweep():
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    q.quantize_model_weight_only(net)
    from paddle_tpu.quantization import WeightOnlyLinear

    kinds = [type(l).__name__ for l in net._sub_layers.values()]
    assert kinds.count("WeightOnlyLinear") == 2
    y = net(jnp.ones((1, 8)))
    assert y.shape == (1, 4)


def test_fake_quant_ste_grad():
    import jax

    fq = q.FakeQuant(bits=8)
    fq.eval()
    x = jnp.linspace(-1, 1, 8)
    g = jax.grad(lambda x: jnp.sum(fq(x) ** 2))(x)
    # straight-through: gradient ≈ 2x
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * x), atol=0.1)


def test_paddle_flatten_semantics():
    x = jnp.zeros((2, 3, 4, 5))
    assert pt.flatten(x).shape == (120,)
    assert pt.flatten(x, 1).shape == (2, 60)          # the canonical call
    assert pt.flatten(x, 1, 2).shape == (2, 12, 5)
    assert pt.flatten(x, -2, -1).shape == (2, 3, 20)
    with pytest.raises(ValueError):
        pt.flatten(x, 3, 1)


def test_paddle_topk_semantics():
    x = jnp.asarray([[3.0, 1.0, 4.0, 1.5], [2.0, 7.0, 1.0, 8.0]])
    v, i = pt.topk(x, 2)
    np.testing.assert_allclose(np.asarray(v), [[4.0, 3.0], [8.0, 7.0]])
    np.testing.assert_array_equal(np.asarray(i), [[2, 0], [3, 1]])
    v, i = pt.topk(x, 2, largest=False)
    np.testing.assert_allclose(np.asarray(v), [[1.0, 1.5], [1.0, 2.0]])
    v, i = pt.topk(x, 1, axis=0)
    np.testing.assert_allclose(np.asarray(v), [[3.0, 7.0, 4.0, 8.0]])


def test_paddle_norm_semantics():
    x = jnp.asarray(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    # axis=None on ndim>2 flattens (jnp.linalg.norm would raise)
    np.testing.assert_allclose(
        float(pt.norm(x)), np.linalg.norm(np.asarray(x).ravel()), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(pt.norm(x, p=1, axis=-1)),
        np.abs(np.asarray(x)).sum(-1), rtol=1e-6)
    np.testing.assert_allclose(
        float(pt.norm(x, p=float("inf"))), 23.0)
    np.testing.assert_allclose(
        np.asarray(linalg.norm(x, axis=(1, 2))),
        np.linalg.norm(np.asarray(x), axis=(1, 2)), rtol=1e-6)


def test_gather_scatter_family():
    x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(4, 3))
    np.testing.assert_allclose(
        np.asarray(pt.gather(x, jnp.asarray([2, 0]))),
        np.asarray(x)[[2, 0]])
    idx = jnp.asarray([[0, 1], [3, 2]])
    np.testing.assert_allclose(
        np.asarray(pt.gather_nd(x, idx)), [1.0, 11.0])
    upd = jnp.asarray([[9.0, 9.0, 9.0], [7.0, 7.0, 7.0]])
    out = pt.scatter(x, jnp.asarray([1, 3]), upd)
    np.testing.assert_allclose(np.asarray(out)[1], 9.0)
    np.testing.assert_allclose(np.asarray(out)[3], 7.0)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(x)[0])
    out = pt.scatter_nd_add(jnp.zeros((4, 3)), idx,
                            jnp.asarray([1.0, 2.0]))
    assert float(out[0, 1]) == 1.0 and float(out[3, 2]) == 2.0


def test_huber_vs_smooth_l1_delta():
    a = jnp.asarray(np.linspace(-4, 4, 33, dtype=np.float32))
    b = jnp.zeros((33,))
    d = np.abs(np.asarray(a))
    delta = 2.0
    sl = nn.SmoothL1Loss(delta=delta)(a, b)
    ref_sl = np.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    np.testing.assert_allclose(float(sl), ref_sl.mean(), rtol=1e-5)
    hb = nn.HuberLoss(delta=delta)(a, b)
    ref_hb = np.where(d < delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    np.testing.assert_allclose(float(hb), ref_hb.mean(), rtol=1e-5)
    # they must now genuinely differ for delta != 1
    assert abs(float(sl) - float(hb)) > 1e-3


def test_distribution_support_guards():
    for dist_, bad, good in [
        (distribution.Gamma(2.0, 1.0), -1.0, 1.0),
        (distribution.Beta(2.0, 2.0), 1.5, 0.5),
        (distribution.LogNormal(0.0, 1.0), -0.5, 1.0),
        (distribution.Poisson(3.0), -1.0, 2.0),
        (distribution.Exponential(1.0), -2.0, 1.0),
        (distribution.Uniform(0.0, 1.0), 2.0, 0.5),
    ]:
        assert float(dist_.log_prob(jnp.asarray(bad))) == float("-inf")
        assert np.isfinite(float(dist_.log_prob(jnp.asarray(good))))


def test_round3_tensor_surface():
    x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert pt.trace(x).item() == 0 + 5 + 10
    np.testing.assert_allclose(np.asarray(pt.diagonal(x)), [0, 5, 10])
    np.testing.assert_allclose(
        float(pt.logsumexp(x)), float(jnp.log(jnp.sum(jnp.exp(x)))),
        rtol=1e-6)
    assert pt.unbind(x, 0)[1].shape == (4,)
    assert [c.shape for c in pt.chunk(x, 2, axis=1)] == [(3, 2), (3, 2)]
    np.testing.assert_allclose(
        np.asarray(pt.masked_fill(x, x > 5, -1.0))[2], [-1, -1, -1, -1])
    np.testing.assert_allclose(float(pt.median(x)), 5.5)
    v, i = pt.mode(jnp.asarray([[1, 2, 2, 3], [7, 7, 1, 1]]))
    np.testing.assert_array_equal(np.asarray(v), [2, 1])
    assert np.asarray(jnp.asarray([[1, 2, 2, 3]]))[0, int(i[0])] == 2
    u, counts = pt.unique(jnp.asarray([3, 1, 3, 2, 1]),
                          return_counts=True)
    np.testing.assert_array_equal(np.asarray(u), [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(counts), [2, 1, 2])
    np.testing.assert_array_equal(
        np.asarray(pt.searchsorted(jnp.asarray([1.0, 3.0, 5.0]),
                                   jnp.asarray([2.0, 5.0]))), [1, 2])
    np.testing.assert_array_equal(
        np.asarray(pt.searchsorted(jnp.asarray([1.0, 3.0, 5.0]),
                                   jnp.asarray([5.0]), right=True)), [3])
    np.testing.assert_allclose(
        np.asarray(pt.lerp(jnp.zeros(3), jnp.ones(3), 0.25)), 0.25)
    # logcumsumexp matches the log of cumsum of exp
    a = jnp.asarray([0.1, 2.0, -1.0])
    np.testing.assert_allclose(
        np.asarray(pt.logcumsumexp(a)),
        np.log(np.cumsum(np.exp(np.asarray(a)))), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(pt.addmm(jnp.ones((2, 2)), jnp.eye(2), jnp.eye(2),
                            beta=2.0, alpha=3.0)),
        2.0 * np.ones((2, 2)) + 3.0 * np.eye(2))
    assert pt.histogram(jnp.asarray([0.0, 0.5, 1.0]), bins=2).sum() == 3
    nz = pt.nonzero(jnp.asarray([[1, 0], [0, 2]]))
    np.testing.assert_array_equal(np.asarray(nz), [[0, 0], [1, 1]])
    rows, cols = pt.nonzero(jnp.asarray([[1, 0], [0, 2]]), as_tuple=True)
    np.testing.assert_array_equal(np.asarray(rows), [0, 1])


def test_group_sharded_and_recompute_api():
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn

    model = nn.Linear(4, 4)
    m, o, strategy, scaler = dist.group_sharded_parallel(model, object(),
                                                         level="os_g")
    assert strategy.sharding and strategy.sharding_configs.stage == 2
    assert scaler is None  # fixed arity: scaler slot present regardless
    with pytest.raises(ValueError):
        dist.group_sharded_parallel(model, object(), level="bogus")

    calls = []

    def f(a):
        calls.append(1)
        return jnp.sin(a) * a

    x = jnp.asarray(np.random.default_rng(0).standard_normal(8),
                    jnp.float32)
    y, vjp = jax.vjp(lambda a: dist.recompute(f, a), x)
    ref, ref_vjp = jax.vjp(f, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vjp(jnp.ones(8))[0]),
                               np.asarray(ref_vjp(jnp.ones(8))[0]),
                               rtol=1e-6)


@pytest.fixture(autouse=True)
def _linalg_x64(request):
    """fp64 comparisons against numpy/torch need x64 jax (CPU tests)."""
    if "TestLinalgExtended" in request.node.nodeid:
        import jax

        with _enable_x64(True):
            yield
    else:
        yield


class TestLinalgExtended:
    """Round-3 widening: the remaining paddle.linalg surface, checked
    against torch.linalg / numpy."""

    def setup_method(self, _):
        import numpy as np

        rng = np.random.default_rng(42)
        a = rng.normal(size=(5, 5)).astype(np.float64)
        self.spd = (a @ a.T + 5 * np.eye(5)).astype(np.float64)
        self.a = a
        self.rect = rng.normal(size=(8, 5)).astype(np.float64)

    def test_cholesky_solve(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        b = np.ones((5, 2))
        chol = np.linalg.cholesky(self.spd)
        x = np.asarray(L.cholesky_solve(jnp.asarray(b), jnp.asarray(chol)))
        np.testing.assert_allclose(self.spd @ x, b, atol=1e-8)

    def test_eigvals_eigvalsh(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        ours = np.sort(np.asarray(L.eigvalsh(jnp.asarray(self.spd))))
        ref = np.sort(np.linalg.eigvalsh(self.spd))
        np.testing.assert_allclose(ours, ref, rtol=1e-6)
        ev = np.asarray(L.eigvals(jnp.asarray(self.spd)))
        np.testing.assert_allclose(
            np.sort(ev.real), ref, rtol=1e-6, atol=1e-8
        )

    def test_lu_roundtrip(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        lu_mat, piv = L.lu(jnp.asarray(self.a))
        P, Lm, U = L.lu_unpack(lu_mat, piv)
        np.testing.assert_allclose(
            np.asarray(P @ Lm @ U), self.a, atol=1e-8
        )

    def test_cov_corrcoef(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        np.testing.assert_allclose(
            np.asarray(L.cov(jnp.asarray(self.rect.T))),
            np.cov(self.rect.T), rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(L.corrcoef(jnp.asarray(self.rect.T))),
            np.corrcoef(self.rect.T), rtol=1e-6,
        )

    def test_multi_dot_matrix_exp_svdvals(self):
        import numpy as np
        import jax.numpy as jnp
        import torch
        from paddle_tpu import linalg as L

        mats = [self.rect, self.spd, self.a]
        np.testing.assert_allclose(
            np.asarray(L.multi_dot([jnp.asarray(m) for m in mats])),
            np.linalg.multi_dot(mats), rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(L.matrix_exp(jnp.asarray(self.a * 0.1))),
            torch.linalg.matrix_exp(torch.tensor(self.a * 0.1)).numpy(),
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(L.svdvals(jnp.asarray(self.rect))),
            np.linalg.svd(self.rect, compute_uv=False), rtol=1e-6,
        )

    def test_vector_matrix_norms(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        np.testing.assert_allclose(
            float(L.vector_norm(jnp.asarray(self.rect), p=3.0)),
            np.sum(np.abs(self.rect) ** 3) ** (1 / 3), rtol=1e-6,
        )
        np.testing.assert_allclose(
            float(L.matrix_norm(jnp.asarray(self.rect), p="fro")),
            np.linalg.norm(self.rect, "fro"), rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(L.matrix_transpose(jnp.asarray(self.rect))),
            self.rect.T,
        )

    def test_householder_product(self):
        import numpy as np
        import jax.numpy as jnp
        import torch
        from paddle_tpu import linalg as L

        At = torch.tensor(self.rect)
        h, tau = torch.geqrf(At)
        ours = np.asarray(
            L.householder_product(jnp.asarray(h.numpy()),
                                  jnp.asarray(tau.numpy()))
        )
        ref = torch.linalg.householder_product(h, tau).numpy()
        np.testing.assert_allclose(ours, ref, rtol=1e-6, atol=1e-8)

    def test_lowrank(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import linalg as L

        # rank-3 matrix: svd_lowrank with q=3 reconstructs it
        u = self.rect[:, :3]
        m = (u @ u.T).astype(np.float64)  # 8x8 rank<=3
        U, s, V = L.svd_lowrank(jnp.asarray(m), q=3, niter=4)
        rec = np.asarray(U) * np.asarray(s) @ np.asarray(V).T
        np.testing.assert_allclose(rec, m, atol=1e-6)
        U2, s2, V2 = L.pca_lowrank(jnp.asarray(m), q=2)
        assert U2.shape == (8, 2) and s2.shape == (2,)


class TestInitializers:
    def _mk(self, init, shape, dtype=jnp.float32):
        import jax

        return np.asarray(init(jax.random.PRNGKey(0), shape, dtype))

    def test_orthogonal(self):
        from paddle_tpu.nn import initializer as I

        for shape in [(8, 8), (4, 12), (12, 4), (6, 2, 3)]:
            w = self._mk(I.Orthogonal(), shape).reshape(shape[0], -1)
            rows, cols = w.shape
            if rows <= cols:
                np.testing.assert_allclose(w @ w.T, np.eye(rows),
                                           atol=1e-5)
            else:
                np.testing.assert_allclose(w.T @ w, np.eye(cols),
                                           atol=1e-5)

    def test_dirac_identity_conv(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.nn import initializer as I

        w = jnp.asarray(self._mk(I.Dirac(), (3, 3, 3, 3)))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(1, 3, 6, 6)).astype(np.float32))
        y = F.conv2d(x, w, padding=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   atol=1e-5)

    def test_assign_and_gain(self):
        from paddle_tpu.nn import initializer as I

        v = np.arange(6, dtype=np.float32).reshape(2, 3)
        w = self._mk(I.Assign(v), (2, 3))
        np.testing.assert_array_equal(w, v)
        with pytest.raises(ValueError):
            self._mk(I.Assign(v), (3, 2))
        assert I.calculate_gain("relu") == pytest.approx(np.sqrt(2))
        assert I.calculate_gain("tanh") == pytest.approx(5 / 3)

    def test_bilinear_upsample_kernel(self):
        from paddle_tpu.nn import initializer as I

        w = self._mk(I.Bilinear(), (2, 2, 4, 4))
        # reference: EVERY (out, in) filter carries the separable ramp
        assert w[0, 0].max() > 0
        np.testing.assert_allclose(w[0, 1], w[0, 0], atol=1e-6)
        np.testing.assert_allclose(w[1, 0], w[0, 0], atol=1e-6)
        np.testing.assert_allclose(w[0, 0], w[0, 0].T, atol=1e-6)


class TestTensorOpsRound3:
    def test_tensordot(self):
        import torch

        a = np.random.default_rng(0).normal(size=(3, 4, 5))
        b = np.random.default_rng(1).normal(size=(4, 5, 6))
        ours = np.asarray(pt.tensor.tensordot(jnp.asarray(a),
                                              jnp.asarray(b), axes=2))
        ref = torch.tensordot(torch.tensor(a), torch.tensor(b),
                              dims=2).numpy()
        # ours runs f32 (jnp default) vs torch's f64; the contraction
        # order XLA picks varies by version, so allow f32-edge slack
        np.testing.assert_allclose(ours, ref, rtol=3e-5, atol=1e-6)

    def test_renorm(self):
        import torch

        x = np.random.default_rng(2).normal(size=(4, 5)).astype(
            np.float32) * 3
        ours = np.asarray(pt.tensor.renorm(jnp.asarray(x), 2.0, 0, 1.0))
        ref = torch.renorm(torch.tensor(x), 2, 0, 1.0).numpy()
        np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)
        norms = np.linalg.norm(ours, axis=1)
        assert (norms <= 1.0 + 1e-5).all()

    def test_scatter_nd(self):
        idx = jnp.asarray([[1], [2], [1]])
        upd = jnp.asarray([9.0, 10.0, 11.0])
        out = np.asarray(pt.tensor.scatter_nd(idx, upd, [4]))
        np.testing.assert_allclose(out, [0.0, 20.0, 10.0, 0.0])
        x = jnp.ones((4,))
        out2 = np.asarray(pt.tensor.scatter_nd_add(x, idx, upd))
        np.testing.assert_allclose(out2, [1.0, 21.0, 11.0, 1.0])


class TestRandomCreation:
    def test_shapes_and_ranges(self):
        pt.seed(7)
        r = pt.rand((3, 4))
        assert r.shape == (3, 4) and (np.asarray(r) >= 0).all() \
            and (np.asarray(r) < 1).all()
        n = pt.randn((5,))
        assert n.shape == (5,)
        i = pt.randint(2, 9, (100,))
        ai = np.asarray(i)
        assert ai.min() >= 2 and ai.max() < 9
        p = np.asarray(pt.randperm(10))
        assert sorted(p.tolist()) == list(range(10))
        u = np.asarray(pt.uniform((50,), min=3.0, max=4.0))
        assert u.min() >= 3.0 and u.max() < 4.0

    def test_seed_reproducible(self):
        pt.seed(123)
        a = np.asarray(pt.randn((4,)))
        pt.seed(123)
        b = np.asarray(pt.randn((4,)))
        np.testing.assert_array_equal(a, b)
        c = np.asarray(pt.randn((4,)))
        assert not np.array_equal(b, c)   # stream advances

    def test_multinomial(self):
        pt.seed(0)
        probs = jnp.asarray([0.0, 0.7, 0.3, 0.0])
        s = np.asarray(pt.multinomial(probs, 200, replacement=True))
        assert set(np.unique(s)) <= {1, 2}
        assert (s == 1).mean() > 0.5
        nr = np.asarray(pt.multinomial(jnp.ones(6), 6))
        assert sorted(nr.tolist()) == list(range(6))

    def test_multinomial_overdraw_raises(self):
        with pytest.raises(ValueError, match="nonzero"):
            pt.multinomial(jnp.asarray([0.0, 0.5, 0.5, 0.0]), 3)

    def test_dtype_strings(self):
        assert pt.rand((2,), "float32").dtype == jnp.float32
        assert pt.randint(0, 5, (3,), "int32").dtype == jnp.int32


class TestInputSpec:
    def test_jit_save_load_with_input_spec(self, tmp_path):
        from paddle_tpu import jit, static

        pt.seed(0)
        lin = pt.nn.Linear(4, 2)
        spec = static.InputSpec([3, 4], "float32", name="x")
        path = str(tmp_path / "model")
        jit.save(lin, path, input_spec=[spec])
        loaded = jit.load(path)
        x = jnp.ones((3, 4))
        np.testing.assert_allclose(
            np.asarray(loaded(x)), np.asarray(lin(x)), rtol=1e-5)

    def test_dynamic_dim_resolution(self):
        from paddle_tpu import static

        spec = static.InputSpec([None, 8], "int64")
        s = spec.to_struct(batch_size=4)
        assert s.shape == (4, 8)
        with pytest.raises(ValueError, match="dynamic dim"):
            static.InputSpec([4, None], "int64").to_struct()

    def test_dynamic_batch_export(self, tmp_path):
        """None dims export batch-POLYMORPHIC StableHLO: one saved
        module serves every batch size."""
        from paddle_tpu import jit, static

        pt.seed(0)
        lin = pt.nn.Linear(4, 2)
        path = str(tmp_path / "dyn")
        jit.save(lin, path,
                 input_spec=[static.InputSpec([None, 4], "float32")])
        loaded = jit.load(path)
        for b in (1, 3, 6):
            x = jnp.ones((b, 4))
            np.testing.assert_allclose(
                np.asarray(loaded(x)), np.asarray(lin(x)), rtol=1e-5)

    def test_to_static_validates_spec(self):
        from paddle_tpu import jit, static

        pt.seed(0)
        lin = pt.nn.Linear(4, 2)
        ts = jit.to_static(lin,
                           input_spec=[static.InputSpec([None, 4])])
        ts(jnp.ones((3, 4)))       # matches
        with pytest.raises(ValueError, match="does not match"):
            ts(jnp.ones((3, 5)))

    def test_from_tensor(self):
        from paddle_tpu import static

        t = jnp.zeros((2, 3), jnp.float32)
        spec = static.InputSpec.from_tensor(t, name="t")
        assert spec.shape == (2, 3) and spec.name == "t"

    def test_multi_dynamic_input_export(self, tmp_path):
        """two dynamic-batch inputs share one symbolic scope."""
        from paddle_tpu import jit, static

        pt.seed(0)

        class TwoIn(pt.nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = pt.nn.Linear(4, 2)

            def forward(self, a, b):
                return self.lin(a) + self.lin(b)

        m = TwoIn()
        path = str(tmp_path / "two")
        jit.save(m, path, input_spec=[
            static.InputSpec([None, 4], "float32"),
            static.InputSpec([None, 4], "float32"),
        ])
        loaded = jit.load(path)
        for bsz in (2, 5):
            a = jnp.ones((bsz, 4))
            np.testing.assert_allclose(
                np.asarray(loaded(a, a * 2)),
                np.asarray(m(a, a * 2)), rtol=1e-5)
