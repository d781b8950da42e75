"""autograd functional transforms, dlpack interop, device namespace,
iinfo/finfo (upstream models: test/legacy_test/test_jacobian.py,
test_hessian.py, test_vjp_jvp.py, test_dlpack.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

import paddle_tpu as pt
from paddle_tpu import autograd
from jax import enable_x64 as _enable_x64


class TestFunctionalAutograd:
    def test_jacobian_matches_analytic(self):
        A = jnp.asarray(np.random.default_rng(0).normal(
            size=(3, 4)).astype(np.float64))
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(4,)).astype(np.float64))
        with _enable_x64(True):
            J = autograd.jacobian(lambda v: A @ v, x)
        np.testing.assert_allclose(np.asarray(J), np.asarray(A),
                                   rtol=1e-10)

    def test_jacobian_tuple_inputs(self):
        x = jnp.asarray([1.0, 2.0])
        y = jnp.asarray([3.0, 4.0])
        J = autograd.jacobian(lambda a, b: a * b, (x, y))
        np.testing.assert_allclose(np.asarray(J[0]), np.diag([3.0, 4.0]))
        np.testing.assert_allclose(np.asarray(J[1]), np.diag([1.0, 2.0]))

    def test_hessian_quadratic(self):
        A = np.array([[2.0, 1.0], [1.0, 4.0]], np.float32)
        H = autograd.hessian(
            lambda v: 0.5 * v @ jnp.asarray(A) @ v, jnp.ones(2))
        np.testing.assert_allclose(np.asarray(H), A, rtol=1e-5)

    def test_vjp_jvp(self):
        x = jnp.asarray([1.0, 2.0, 3.0])
        out, g = autograd.vjp(lambda v: jnp.sum(v ** 2), x)
        np.testing.assert_allclose(float(out), 14.0)
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x))
        out2, t = autograd.jvp(lambda v: v ** 2, x,
                               jnp.asarray([1.0, 0.0, 0.0]))
        np.testing.assert_allclose(np.asarray(t), [2.0, 0.0, 0.0])


class TestDlpack:
    def test_torch_roundtrip(self):
        t = torch.arange(12, dtype=torch.float32).reshape(3, 4)
        ours = pt.utils.dlpack.from_dlpack(t)
        np.testing.assert_allclose(np.asarray(ours), t.numpy())
        back = torch.from_dlpack(ours)  # jax array __dlpack__ direct
        np.testing.assert_allclose(back.numpy(), t.numpy())

    def test_capsule_export(self):
        x = jnp.arange(6.0)
        cap = pt.utils.dlpack.to_dlpack(x)
        assert cap is not None
        back = pt.utils.dlpack.from_dlpack(x)
        np.testing.assert_allclose(np.asarray(back), np.arange(6.0))


class TestDeviceInfo:
    def test_device_queries(self):
        d = pt.device.get_device()
        assert ":" in d
        assert pt.device.device_count() >= 1
        pt.device.synchronize()
        s = pt.device.current_stream()
        s.synchronize()
        assert not pt.device.is_compiled_with_cuda()

    def test_iinfo_finfo(self):
        assert pt.iinfo("int32").max == 2**31 - 1
        assert pt.finfo(pt.float32).eps == np.finfo(np.float32).eps
        assert float(pt.finfo(pt.bfloat16).max) > 3e38


class TestReviewFixes:
    def test_upsample_nhwc(self):
        x = jnp.ones((1, 2, 2, 3))
        out = pt.nn.Upsample(size=(4, 4), mode="nearest",
                             data_format="NHWC")(x)
        assert out.shape == (1, 4, 4, 3)

    def test_iinfo_dtype_objects(self):
        assert pt.iinfo(pt.int32).max == 2**31 - 1
        assert pt.iinfo(jnp.int8).min == -128

    def test_custom_device_query_is_name_specific(self):
        assert pt.device.is_compiled_with_custom_device("cpu")
        assert not pt.device.is_compiled_with_custom_device("npu")

    def test_set_device_unknown_raises(self):
        with pytest.raises(ValueError):
            pt.device.set_device("npu:0")
