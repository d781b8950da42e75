"""How a Pallas kernel of this package runs: compiled, interpreted, or
not at all. The one place that asks JAX for the backend and reads
``PADDLE_TPU_FORCE_PALLAS``; the kernels and their dispatchers ask here
(``tests/test_kernel_backend.py`` holds them to it).
"""

from __future__ import annotations

import os

import jax


def interpret() -> bool:
    """``pallas_call``'s ``interpret=``: off the TPU a kernel runs in
    the Pallas interpreter (how the CPU tests run them)."""
    return jax.default_backend() != "tpu"


def use_kernel(aligned: bool) -> bool:
    """Whether a dispatcher takes the kernel rather than its XLA
    reference, given that the shapes tile (``aligned``): on the TPU, or
    anywhere with ``PADDLE_TPU_FORCE_PALLAS`` set (the kernel then runs
    interpreted: the tests' and the dry run's way onto the real path)."""
    return aligned and (bool(os.environ.get("PADDLE_TPU_FORCE_PALLAS"))
                        or not interpret())
