"""One place knows how a kernel runs (``kernels/_backend.py``): no other
module of the kernels asks JAX for the backend or reads
``PADDLE_TPU_FORCE_PALLAS``, and the four dispatchers that choose
between a kernel and its XLA reference follow that one rule."""

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.distributed import moe
from paddle_tpu.inference import paged
from paddle_tpu.kernels import flash_attention, ring_attention

PACKAGE = pathlib.Path(__file__).parent.parent / "paddle_tpu"


def test_no_module_sniffs_the_backend():
    """``nn/layout.py`` and ``inference/serving.py`` ask for the backend
    too, to choose a layout and a dtype, not a kernel: out of scope."""
    files = sorted((PACKAGE / "kernels").glob("*.py")) + [
        PACKAGE / "inference" / "paged.py"]
    assert len(files) > 10
    sniffers = [
        f"{f.relative_to(PACKAGE)}:{n}"
        for f in files if f.name != "_backend.py"
        for n, line in enumerate(f.read_text().splitlines(), 1)
        if re.search(r"default_backend\(|PADDLE_TPU_FORCE_PALLAS", line)]
    assert not sniffers, sniffers


def _qkv(s, d):
    return jax.ShapeDtypeStruct((2, s, 4, d), jnp.bfloat16)


def _pool(page_size):
    pages = jax.ShapeDtypeStruct((2, 8, page_size, 128), jnp.bfloat16)
    return paged.PagedLayerCache(pages, pages)


def _experts(m, h, gated):
    """(x, the held experts' matrices, their activation, block rows)."""
    w = {"w1": jax.ShapeDtypeStruct((8, m, h), jnp.bfloat16),
         "w2": jax.ShapeDtypeStruct((8, h, m), jnp.bfloat16)}
    if gated:
        w["w3"] = w["w1"]
    return (jax.ShapeDtypeStruct((8192, m), jnp.bfloat16), w,
            jax.nn.silu if gated else moe.relu2, 256)


# dispatcher -> (its gate, arguments that tile, arguments that do not)
GATES = {
    # the LFM2 cell's gated experts; the Nemotron cell's two matrices of
    # a width that is no whole number of lanes
    "experts": (moe._use_grouped, _experts(2048, 1792, True),
                _experts(2688, 1856, False)),
    "flash": (flash_attention._use_pallas, (_qkv(256, 64),),
              (_qkv(200, 64),)),
    "ring": (ring_attention._use_flash, (256, 256, 128), (256, 256, 64)),
    "paged": (paged._use_pallas_decode, (_pool(16),), (_pool(8),)),
}


@pytest.mark.parametrize("name", sorted(GATES))
def test_force_pallas_routes_dispatch(name, monkeypatch):
    """On the CPU the reference runs; with the variable set the kernel
    (interpreted) does, where the shape tiles and only there."""
    gate, aligned, misaligned = GATES[name]
    assert jax.default_backend() == "cpu"
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    assert not gate(*aligned) and not gate(*misaligned)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert gate(*aligned) and not gate(*misaligned)
