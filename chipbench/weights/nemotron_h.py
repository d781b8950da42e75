"""Nemotron-H weights made on the device from ``--seed``, in bf16.

The stack is a hybrid: a block's leaves depend on its kind, and the
train kind's ``leaf_sizes`` asks ``layer_shapes(w)`` without a layer
index. So EVERY leaf is named in full, under the program's parameter
name (``models/nemotron_h.py``), by ``top_shapes`` / ``make_top``, which
read the pattern from the widths; ``layer_shapes`` and ``make_layer``
are empty. One jitted call makes all the leaves; the keys are folded
from the seed and the leaf's place in the list, so the plain reference
makes the same bits again after the program's copy is freed.

Initialisation (the configuration's ``assumed``): matrices
normal(0, 0.02); norm scales 1; the depthwise conv's taps uniform in
+-1/sqrt(kernel) with a zero bias (what the published code's Conv1d
starts from); ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus
of a log-uniform ``dt`` in [time_step_min, time_step_max] floored at
time_step_floor; ``D = 1``. The router's correction bias is a buffer of
zeros in the program and absent here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02
IMPL = "rbg"  # the chip's own generator: threefry costs seconds


def block_shapes(w: dict, kind: str) -> dict:
    """leaf -> (shape, how it is made) for one block of ``kind``."""
    h = w["hidden_size"]
    out = {"norm.weight": ((h,), "ones")}
    if kind == "M":
        nh, p = w["mamba_num_heads"], w["mamba_head_dim"]
        gn = w["n_groups"] * w["ssm_state_size"]
        d_in, conv = nh * p, nh * p + 2 * gn
        out.update({
            "mixer.in_proj.weight": ((h, d_in + conv + nh), "normal"),
            "mixer.conv_weight": ((conv, w["conv_kernel"]), "conv"),
            "mixer.conv_bias": ((conv,), "zeros"),
            "mixer.dt_bias": ((nh,), "dt_bias"),
            "mixer.A_log": ((nh,), "A_log"),
            "mixer.D": ((nh,), "ones"),
            "mixer.norm_weight": ((d_in,), "ones"),
            "mixer.out_proj.weight": ((d_in, h), "normal")})
    elif kind == "E":
        held, f = w["n_routed_experts"], w["moe_intermediate_size"]
        sh = w["moe_shared_expert_intermediate_size"]
        out.update({
            "mixer.gate_weight": ((h, w["router_num_experts"]), "normal"),
            "mixer.experts.w1": ((held, h, f), "normal"),
            "mixer.experts.w2": ((held, f, h), "normal"),
            "mixer.shared_experts.w1": ((1, h, sh), "normal"),
            "mixer.shared_experts.w2": ((1, sh, h), "normal")})
    else:
        d = w["head_dim"]
        q, kv = w["num_attention_heads"] * d, w["num_key_value_heads"] * d
        out.update({
            "mixer.q_proj.weight": ((h, q), "normal"),
            "mixer.k_proj.weight": ((h, kv), "normal"),
            "mixer.v_proj.weight": ((h, kv), "normal"),
            "mixer.o_proj.weight": ((q, h), "normal")})
    return out


def pattern(w: dict) -> str:
    pat = w["hybrid_override_pattern"]
    if len(pat) != w["num_hidden_layers"]:
        raise ValueError("num_hidden_layers is not the pattern's length")
    return pat


def _specs(w: dict) -> dict:
    h, v = w["hidden_size"], w["vocab_size"]
    out = {"backbone.embeddings.weight": ((v, h), "normal")}
    for i, kind in enumerate(pattern(w)):
        out.update({f"backbone.layers.{i}.{n}": s
                    for n, s in block_shapes(w, kind).items()})
    out["backbone.norm_f.weight"] = ((h,), "ones")
    out["lm_head.weight"] = ((h, v), "normal")
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
    elif how == "conv":
        bound = shape[1] ** -0.5
        v = jax.random.uniform(key, shape, f32, -bound, bound)
    elif how == "A_log":
        v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
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
    if layers != len(pattern(w)):
        raise ValueError("layers is not the pattern's length")
    return make_top(w, seed)


def n_params(w: dict, layers: int) -> int:
    return sum(math.prod(s) for s in top_shapes(w).values())
