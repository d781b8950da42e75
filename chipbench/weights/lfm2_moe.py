"""LFM2-MoE weights made on the device from ``--seed``, in bf16.

A layer's leaves depend on its operator (``layer_types``) and on
whether its feed-forward is dense or sparse (``num_dense_layers``), and
the train kind's ``leaf_sizes`` asks ``layer_shapes(w)`` without a layer
index. So EVERY leaf is named in full, under the program's parameter
name (``models/lfm2.py``), by ``top_shapes`` / ``make_top``;
``layer_shapes`` and ``make_layer`` are empty, as in
``weights/nemotron_h.py``. One jitted call makes all the leaves; the
keys are folded from the seed and the leaf's place in the list, so the
plain reference makes the same bits again after the program's copy is
freed. The head is the embedding: one leaf.

Initialisation (the configuration's ``assumed``): matrices
normal(0, 0.02); norm scales 1; the short convolution's taps uniform in
+-1/sqrt(conv_L_cache), no bias (what the published code's Conv1d starts
from). The router's expert bias is a buffer of zeros in the program and
absent here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02
IMPL = "rbg"  # the chip's own generator: threefry costs seconds


def layer_specs(w: dict, i: int) -> dict:
    """leaf -> (shape, how it is made) for layer ``i``."""
    h = w["hidden_size"]
    out = {"operator_norm.weight": ((h,), "ones"),
           "ffn_norm.weight": ((h,), "ones")}
    if w["layer_types"][i] == "full_attention":
        d = w["head_dim"]
        q, kv = w["num_attention_heads"] * d, w["num_key_value_heads"] * d
        out.update({
            "self_attn.q_proj.weight": ((h, q), "normal"),
            "self_attn.k_proj.weight": ((h, kv), "normal"),
            "self_attn.v_proj.weight": ((h, kv), "normal"),
            "self_attn.q_layernorm.weight": ((d,), "ones"),
            "self_attn.k_layernorm.weight": ((d,), "ones"),
            "self_attn.out_proj.weight": ((q, h), "normal")})
    else:
        out.update({
            "conv.in_proj.weight": ((h, 3 * h), "normal"),
            "conv.conv_weight": ((h, w["conv_L_cache"]), "taps"),
            "conv.out_proj.weight": ((h, h), "normal")})
    if i < w["num_dense_layers"]:
        f = w["intermediate_size"]
        out.update({
            "feed_forward.w1.weight": ((h, f), "normal"),
            "feed_forward.w3.weight": ((h, f), "normal"),
            "feed_forward.w2.weight": ((f, h), "normal")})
    else:
        held, f = w["num_experts"], w["moe_intermediate_size"]
        out.update({
            "feed_forward.gate_weight": ((h, w["router_num_experts"]),
                                         "normal"),
            "feed_forward.experts.w1": ((held, h, f), "normal"),
            "feed_forward.experts.w2": ((held, f, h), "normal"),
            "feed_forward.experts.w3": ((held, h, f), "normal")})
    return out


def _specs(w: dict) -> dict:
    if len(w["layer_types"]) != w["num_hidden_layers"]:
        raise ValueError("num_hidden_layers is not layer_types' length")
    h = w["hidden_size"]
    out = {"model.embed_tokens.weight": ((w["vocab_size"], h), "normal")}
    for i in range(w["num_hidden_layers"]):
        out.update({f"model.layers.{i}.{n}": s
                    for n, s in layer_specs(w, i).items()})
    out["model.embedding_norm.weight"] = ((h,), "ones")
    return out


def top_shapes(w: dict) -> dict:
    return {n: s for n, (s, _) in _specs(w).items()}


def layer_shapes(w: dict) -> dict:
    return {}


def base_key(seed: int):
    """``--seed`` may be a little over 2**31: fold the high part in."""
    key = jax.random.key(seed % (1 << 31), impl=IMPL)
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, shape, how):
    f32 = jnp.float32
    if how == "ones":
        v = jnp.ones(shape, f32)
    elif how == "normal":
        v = STD * jax.random.normal(key, shape, f32)
    else:  # taps
        bound = shape[1] ** -0.5
        v = jax.random.uniform(key, shape, f32, -bound, bound)
    return v.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, specs: tuple):
    return tuple(_leaf(jax.random.fold_in(key, n), shape, how)
                 for n, (shape, how) in enumerate(specs))


def make_top(w: dict, seed: int) -> dict:
    """Every leaf of the model, bf16, under the program's full name."""
    specs = _specs(w)
    return dict(zip(specs, _make(base_key(seed), tuple(specs.values()))))


def make_layer(w: dict, seed: int, layer: int) -> dict:
    return {}


def make_all(w: dict, seed: int, layers: int) -> dict:
    if layers != w["num_hidden_layers"]:
        raise ValueError("layers is not num_hidden_layers")
    return make_top(w, seed)


def n_params(w: dict, layers: int) -> int:
    return sum(math.prod(s) for s in top_shapes(w).values())
