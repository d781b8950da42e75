"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle (the reference, hackerapple/Paddle), re-designed
for JAX/XLA/Pallas/pjit instead of CUDA/Phi/NCCL.

Architecture (see SURVEY.md §7): the reference's kernel registry, IRs,
tensor compiler and collective runtime are *subsumed by XLA*; this package
provides the module/optimizer/tensor API, the hybrid-parallel sharding
engine (DP / ZeRO-1/2/3 / TP / PP / SP / CP / EP expressed as GSPMD
shardings over a jax Mesh), Pallas kernels for the genuinely hot paths,
and the host-side runtime (trainer, data, checkpoint, launch, profiler).
"""

from . import amp  # noqa: F401
from . import audio  # noqa: F401
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distribution  # noqa: F401
from . import errors  # noqa: F401
from . import fft  # noqa: F401
from . import generation  # noqa: F401
from . import flags  # noqa: F401

# PT_FLAGS_default_matmul_precision: process-wide jax matmul precision
# override, applied once at import (first-use time, like the registry's
# xla_* passthrough); empty = jax's own default (bf16 on the MXU)
_mmp = flags.flag("default_matmul_precision")
if _mmp:
    import jax as _jax_cfg

    try:
        _jax_cfg.config.update("jax_default_matmul_precision",
                               str(_mmp))
    except Exception as _e:
        raise ValueError(
            f"PT_FLAGS_default_matmul_precision={_mmp!r} is not a "
            "valid jax matmul precision (use bfloat16|tensorfloat32|"
            "float32|highest, or empty for the default)") from _e
    del _jax_cfg
del _mmp
# The persistent compile cache keys a program WITHOUT its op_name
# metadata by default, so a build whose only change is a
# ``jax.named_scope`` (or a shifted source line) is served another
# build's executable, and a profile of it shows that build's names: the
# device phases of observability/spans.py would read "no scope". A
# profile must not show another build's names. Price: the first run
# after a change that moves traced source lines compiles again.
import jax as _jax_cfg

_jax_cfg.config.update("jax_compilation_cache_include_metadata_in_key", True)
del _jax_cfg
from . import incubate  # noqa: F401
from . import jit  # noqa: F401
from . import linalg  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import signal  # noqa: F401
from . import static  # noqa: F401
from . import utils  # noqa: F401
from .hapi.summary import flops, summary  # noqa: F401
from . import sparse  # noqa: F401
from . import vision  # noqa: F401
from .core import dtype as _dtype_mod
from .core.dtype import (  # noqa: F401
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.functional import functional_call  # noqa: F401
from .core.module import Layer  # noqa: F401
from .core.parameter import Parameter  # noqa: F401
from .core.random import get_rng_state_tracker, seed  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import to_tensor  # noqa: F401
from .core import tensor_methods as _tensor_methods

# paddle.Tensor METHOD surface onto jax.Array (x.numpy(), x.cast(...),
# x.unsqueeze(...)) — strictly additive, see core/tensor_methods.py
_tensor_methods.install()
from .version import full_version as __version__  # noqa: F401


def save(obj, path):
    from .framework import io

    return io.save(obj, path)


def load(path):
    from .framework import io

    return io.load(path)


def no_grad(fn=None):
    """Parity shim: gradients in this framework are explicit (jax.grad), so
    no_grad is an identity context/decorator kept for API compatibility."""
    import contextlib

    if fn is None:
        return contextlib.nullcontext()
    return fn


def iinfo(dtype):
    import jax.numpy as _jnp
    import numpy as _np

    return _np.iinfo(_jnp.dtype(dtype))


def finfo(dtype):
    import jax.numpy as _jnp
    import numpy as _np

    d = _jnp.dtype(dtype)
    if d == _jnp.bfloat16:
        import ml_dtypes

        return ml_dtypes.finfo(ml_dtypes.bfloat16)
    return _np.finfo(d)


# ---- round-5 migration-surface sweep (top-level paddle names) ----

from . import observability  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import models  # noqa: F401,E402
from .core.parameter import ParamAttr  # noqa: F401,E402
from .device import get_device, set_device  # noqa: F401,E402

import builtins  # noqa: E402
import jax as _jax  # noqa: E402

#: the tensor type IS jax.Array (see tensor.py's module docstring)
Tensor = _jax.Array
bool = bool_  # noqa: A001  (paddle.bool is a public dtype name)


class CPUPlace:
    """Parity: paddle.CPUPlace. Device placement on TPU is owned by
    PJRT/shardings; Places exist so migrating call sites keep working
    (to_tensor(place=...), Config.set_device)."""

    def __repr__(self):
        return "Place(cpu)"

    def __eq__(self, other):
        return type(other) is type(self)

    def __hash__(self):
        return hash(type(self))


class CUDAPlace:
    """Parity: paddle.CUDAPlace(id) — maps to the id-th accelerator."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(accelerator:{self.device_id})"

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.device_id == self.device_id)

    def __hash__(self):
        return hash((type(self), self.device_id))


XPUPlace = CUDAPlace


def grad(outputs, inputs=None, grad_outputs=None, **kw):
    """Parity adapter for paddle.grad. There is no dygraph tape here —
    differentiation is a functional transform — so ``outputs`` must be
    the CALLABLE producing the outputs, and ``inputs`` its example
    arguments: ``paddle_tpu.grad(f, (x, y))`` returns (df/dx, df/dy) at
    (x, y), one gradient per input like paddle.grad. Passing arrays
    raises with the migration hint."""
    if callable(outputs) and inputs is not None:
        args = tuple(inputs) if isinstance(inputs, (list, tuple)) \
            else (inputs,)
        return _jax.grad(outputs,
                         argnums=tuple(range(len(args))))(*args)
    raise TypeError(
        "paddle_tpu.grad has no dygraph tape: pass the function AND its "
        "inputs, e.g. grad(lambda x: loss(x), (x,)) — see "
        "autograd.functional for vjp/jvp/jacobian/hessian")


_grad_enabled = True


class set_grad_enabled:
    """Parity: paddle.set_grad_enabled — context manager tracking the
    flag; gradient computation itself is explicit (jax transforms), so
    the flag only drives is_grad_enabled()."""

    def __init__(self, mode: builtins.bool):
        global _grad_enabled
        self._prev = _grad_enabled
        _grad_enabled = builtins.bool(mode)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _grad_enabled
        _grad_enabled = self._prev
        return False


def is_grad_enabled():
    return _grad_enabled


class DataParallel(Layer):
    """Parity: paddle.DataParallel(model). On TPU, data parallelism is a
    sharding of the batch axis over the mesh's dp axis inside the one
    compiled program — gradient all-reduce is inserted by GSPMD, so the
    wrapper has no reducer to run. It exists so migrating training
    scripts keep their structure; pass the wrapped model to TrainStep
    with a dp mesh axis for the actual parallelism."""

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def state_dict(self, include_sublayers=True,
                   structured_name_prefix=""):
        # delegate like upstream paddle.DataParallel: checkpoint keys
        # match the UNWRAPPED model, so training with the wrapper and
        # loading into a bare model (the standard infer path) just works
        return self._layers.state_dict(include_sublayers,
                                       structured_name_prefix)

    def set_state_dict(self, state_dict, use_structured_name=True):
        return self._layers.set_state_dict(state_dict,
                                           use_structured_name)

    load_dict = set_state_dict

    def __getattr__(self, name):
        try:
            return super().__getattr__(name)
        except AttributeError:
            return getattr(self._layers, name)

from .hapi import Model  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import hub  # noqa: F401,E402
