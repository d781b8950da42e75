"""Phi-4-mini-flash weights made on the device from ``--seed``, in bf16.

A layer's leaves depend on its kind, which follows its PUBLISHED index
(``published_layer_indices`` of the widths), and the train kind's
``leaf_sizes`` asks ``layer_shapes(w)`` without a layer index. So EVERY
leaf is named in full, under the program's parameter name
(``models/phi4flash.py``; a layer's place in the held list, not its
published index, is in the name), by ``top_shapes`` / ``make_top``;
``layer_shapes`` and ``make_layer`` are empty. The embedding is the head
too: one leaf. One jitted call makes all the leaves; the keys are folded
from the seed and the leaf's place in the list, so the plain reference
makes the same bits again after the program's copy is freed.

Initialisation (the configuration's ``assumed``): matrices
normal(0, 0.02); LayerNorm and sub-norm scales 1, every bias 0 but
``dt_proj``'s; the depthwise conv's taps uniform in +-1/sqrt(kernel);
``A_log = log(1..n)`` along every row; ``dt_proj.bias`` the inverse
softplus of a log-uniform ``dt`` in [time_step_min, time_step_max]
floored at time_step_floor; ``D = 1``; the four lambda vectors
normal(0, 0.1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.phi4flash import layer_kind

STD = 0.02
LAMBDA_STD = 0.1
IMPL = "rbg"  # the chip's own generator: threefry costs seconds


def layer_specs(w: dict, kind: str) -> dict:
    """leaf -> (shape, how it is made) for one layer of ``kind``."""
    h, f = w["hidden_size"], w["intermediate_size"]
    d_in = w["mamba_expand"] * h
    out = {"input_layernorm.weight": ((h,), "ones"),
           "input_layernorm.bias": ((h,), "zeros")}
    if kind == "mamba":
        n, r = w["mamba_d_state"], w["mamba_dt_rank"]
        out.update({
            "mixer.in_proj.weight": ((h, 2 * d_in), "normal"),
            "mixer.conv_weight": ((d_in, w["mamba_d_conv"]), "conv"),
            "mixer.conv_bias": ((d_in,), "zeros"),
            "mixer.x_proj.weight": ((d_in, r + 2 * n), "normal"),
            "mixer.dt_proj.weight": ((r, d_in), "normal"),
            "mixer.dt_proj.bias": ((d_in,), "dt_bias"),
            "mixer.A_log": ((d_in, n), "A_log"),
            "mixer.D": ((d_in,), "ones"),
            "mixer.out_proj.weight": ((d_in, h), "normal")})
    elif kind == "gmu":
        out.update({
            "mixer.in_proj.weight": ((h, d_in), "normal"),
            "mixer.out_proj.weight": ((d_in, h), "normal")})
    else:
        d = w["head_dim"]
        q, kv = w["num_attention_heads"] * d, w["num_key_value_heads"] * d
        name, width = ("Wq", q) if kind == "cross" else ("Wqkv", q + 2 * kv)
        out.update({
            f"mixer.{name}.weight": ((h, width), "normal"),
            f"mixer.{name}.bias": ((width,), "zeros"),
            "mixer.out_proj.weight": ((q, h), "normal"),
            "mixer.out_proj.bias": ((h,), "zeros"),
            "mixer.subln.weight": ((2 * d,), "ones")})
        out.update({f"mixer.lambda_{x}": ((d,), "lambda")
                    for x in ("q1", "k1", "q2", "k2")})
    out.update({"post_attention_layernorm.weight": ((h,), "ones"),
                "post_attention_layernorm.bias": ((h,), "zeros"),
                "mlp.fc1.weight": ((h, 2 * f), "normal"),
                "mlp.fc2.weight": ((f, h), "normal")})
    return out


def held(w: dict) -> list:
    idx = list(w["published_layer_indices"])
    if len(idx) != w["num_hidden_layers"]:
        raise ValueError("num_hidden_layers is not the number of "
                         "published_layer_indices")
    return idx


def _specs(w: dict) -> dict:
    h = w["hidden_size"]
    out = {"model.embed_tokens.weight": ((w["vocab_size"], h), "normal")}
    for i, l in enumerate(held(w)):
        out.update({f"model.layers.{i}.{n}": s for n, s in
                    layer_specs(w, layer_kind(l, w)).items()})
    out["model.final_layernorm.weight"] = ((h,), "ones")
    out["model.final_layernorm.bias"] = ((h,), "zeros")
    return out


def top_shapes(w: dict) -> dict:
    return {n: s for n, (s, _) in _specs(w).items()}


def layer_shapes(w: dict) -> dict:
    return {}


def base_key(seed: int):
    """``--seed`` may be a little over 2**31: fold the high part in."""
    key = jax.random.key(seed % (1 << 31), impl=IMPL)
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, shape, how, dt_range):
    f32 = jnp.float32
    if how == "ones":
        v = jnp.ones(shape, f32)
    elif how == "zeros":
        v = jnp.zeros(shape, f32)
    elif how == "normal":
        v = STD * jax.random.normal(key, shape, f32)
    elif how == "lambda":
        v = LAMBDA_STD * jax.random.normal(key, shape, f32)
    elif how == "conv":
        bound = shape[1] ** -0.5
        v = jax.random.uniform(key, shape, f32, -bound, bound)
    elif how == "A_log":
        v = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)),
                             shape)
    else:  # dt_bias: softplus(v) = dt
        lo, hi, floor = dt_range
        dt = jnp.exp(jax.random.uniform(key, shape, f32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        v = dt + jnp.log(-jnp.expm1(-dt))
    return v.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, specs: tuple, dt_range: tuple):
    return tuple(_leaf(jax.random.fold_in(key, n), shape, how, dt_range)
                 for n, (shape, how) in enumerate(specs))


def make_top(w: dict, seed: int) -> dict:
    """Every leaf of the model, bf16, under the program's full name."""
    specs = _specs(w)
    dt_range = (w["time_step_min"], w["time_step_max"],
                w["time_step_floor"])
    return dict(zip(specs, _make(base_key(seed), tuple(specs.values()),
                                 dt_range)))


def make_layer(w: dict, seed: int, layer: int) -> dict:
    return {}


def make_all(w: dict, seed: int, layers: int) -> dict:
    if layers != len(held(w)):
        raise ValueError("layers is not the number of held layers")
    return make_top(w, seed)


def n_params(w: dict, layers: int) -> int:
    return sum(math.prod(s) for s in top_shapes(w).values())
