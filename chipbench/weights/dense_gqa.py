"""Weights made on the device from ``--seed``, in the type they are run in.

One jitted call makes a whole decoder layer (every layer has the same
shapes, so one program serves all of them) and one makes the embedding,
the final norm and the head. The keys are folded from the seed, the
layer and the leaf, so the plain reference can make layer ``i`` again on
its own, long after the program's copy is freed, and get the same bits:
it calls the same jitted functions. The names are the program's
parameter names (``models/llama.py``), so the values are set by name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02  # LlamaConfig.initializer_range, and Mistral's own
IMPL = "rbg"  # the chip's own generator: threefry costs seconds a layer

LAYER_LEAVES = (
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
    "input_layernorm.weight", "post_attention_layernorm.weight")
TOP_LEAVES = ("model.embed_tokens.weight", "model.norm.weight",
              "lm_head.weight")


def layer_shapes(widths: dict) -> dict:
    h, i = widths["hidden_size"], widths["intermediate_size"]
    d = widths["head_dim"]
    q, kv = widths["num_attention_heads"] * d, \
        widths["num_key_value_heads"] * d
    return dict(zip(LAYER_LEAVES, (
        (h, q), (h, kv), (h, kv), (q, h), (h, i), (h, i), (i, h),
        (h,), (h,))))


def top_shapes(widths: dict) -> dict:
    h, v = widths["hidden_size"], widths["vocab_size"]
    return dict(zip(TOP_LEAVES, ((v, h), (h,), (h, v))))


def base_key(seed: int):
    """``--seed`` may be a little over 2**31: fold the high part in."""
    key = jax.random.key(seed % (1 << 31), impl=IMPL)
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, shape):
    if len(shape) == 1:  # a norm's scale starts at one, as the program's
        return jnp.ones(shape, jnp.bfloat16)
    return (STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, shapes: tuple):
    return tuple(_leaf(jax.random.fold_in(key, n), s)
                 for n, s in enumerate(shapes))


def make_layer(widths: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s nine leaves, bf16, keyed by their short names."""
    shapes = layer_shapes(widths)
    key = jax.random.fold_in(base_key(seed), 1 + layer)
    return dict(zip(shapes, _make(key, tuple(shapes.values()))))


def make_top(widths: dict, seed: int) -> dict:
    shapes = top_shapes(widths)
    key = jax.random.fold_in(base_key(seed), 0)
    return dict(zip(shapes, _make(key, tuple(shapes.values()))))


def make_all(widths: dict, seed: int, layers: int) -> dict:
    """Every leaf under the program's full parameter name."""
    out = make_top(widths, seed)
    for i in range(layers):
        for name, value in make_layer(widths, seed, i).items():
            out[f"model.layers.{i}.{name}"] = value
    return out


def n_params(widths: dict, layers: int) -> int:
    per_layer = sum(math.prod(s) for s in layer_shapes(widths).values())
    return layers * per_layer + sum(
        math.prod(s) for s in top_shapes(widths).values())
