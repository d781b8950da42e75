"""Selective state-space layers: Mamba-1 (S6) and Mamba-2.

Two different layers live here, and each has its kernel:

``MambaMixer`` / ``MambaForCausalLM`` are **Mamba-1** (S6): ``x_proj``
and ``dt_proj`` make a step size per channel, ``A`` is ``[d_inner, n]``
(n = 16), and the state ``[n, d_inner]`` is walked by
``kernels/selective_scan.py``: a parallel associative scan
(``jax.lax.associative_scan`` over h_t = a_t * h_{t-1} + b_t, whose
composition (a, b) o (a', b') = (a a', a' b + b') is associative), or
the Pallas chunked scan for long sequences. This is the layer the
"selective-scan + linear-recurrence Phi op" line of BASELINE.json
names.

``Mamba2Mixer`` is **Mamba-2**: one fused ``in_proj`` gives the gate
``z``, the convolved ``x, B, C`` and a step size per HEAD; ``A`` is one
scalar a head, the state ``[head_dim, n]`` a head (n = 128), ``B`` and
``C`` are shared by the heads of a group, and a gated group RMSNorm
follows. Its recurrence runs in the chunked, matmul form of
``kernels/ssd.py``. ``models/nemotron_h.py`` stacks it with expert and
attention blocks.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.parallel_layers import VocabParallelEmbedding
from ..distributed.sharding import shard_activation
from ..kernels.selective_scan import (
    RESIDUAL_NAMES as _S6_RESIDUAL_NAMES,
    associative_selective_scan as selective_scan,
    chunked_selective_scan,
)
from ..kernels.ssd import RESIDUAL_NAMES as _SSD_RESIDUAL_NAMES, ssd_chunked
from ..nn import functional as F
from ..nn.layer.common import LayerList, Linear
from ..nn.layer.norm import RMSNorm


@dataclasses.dataclass
class MambaConfig:
    vocab_size: int = 50277
    hidden_size: int = 768
    state_size: int = 16
    num_hidden_layers: int = 24
    expand: int = 2
    dt_rank: int = 48  # ceil(hidden/16)
    conv_kernel: int = 4
    rms_norm_eps: float = 1e-5
    # Pallas chunked scan (kernels/selective_scan.py): avoids the
    # [b,s,d,n] HBM blow-up of the associative scan; requires seq len
    # divisible by scan_chunk
    use_chunked_scan: bool = False
    scan_chunk: int = 128
    # initialisation: matrices normal(0, initializer_range); softplus of
    # dt_proj's bias is log-uniform in [time_step_min, time_step_max],
    # floored
    initializer_range: float = 0.02
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @property
    def d_inner(self):
        return self.expand * self.hidden_size

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("state_size", 8)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("dt_rank", 4)
        return cls(**kw)


def _dt_bias_init(time_step_min, time_step_max, time_step_floor):
    """An initializer: softplus(bias) is log-uniform in [min, max],
    floored (both Mamba layers start their step sizes so)."""
    def init(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (jnp.log(time_step_max) - jnp.log(
            time_step_min)) + jnp.log(time_step_min))
        dt = jnp.maximum(dt, time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class MambaMixer(Layer):
    """The Mamba-1 (S6) mixer, on h [b, s, hidden] (the block's norm and
    residual are the caller's):

        x, z = split(h W_in)                          (no bias)
        x = silu(causal_depthwise_conv1d(x) + b_conv)
        dt, B, C = split(x W_x)                       (dt_rank, n, n)
        delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t^T
        y_t = s_t C_t + D x_t
        out = (y * silu(z)) W_out

    ``forward(h, return_scan_output=True)`` also hands out ``y`` before
    the gate (what a later layer's gated memory unit reads). The conv's
    sums, ``delta`` and ``A`` are float32. The device phases are the
    scopes ``s6_in``, ``s6_scan`` and ``s6_out``.

    The recurrence is ``kernels/selective_scan.chunked_selective_scan``
    where ``use_chunked_scan`` is set and the sequence is a multiple of
    ``scan_chunk`` (the distance between the states its backward starts
    from). It is handed ``x``, ``B`` and ``C`` in the mixer's dtype and
    widens them itself, walks the steps one by one in float32, adds the
    ``D`` skip and returns float32, which is rounded once here.
    **Otherwise it falls back, silently, to the float32
    associative scan**, which writes two ``[b, s, d_inner, n]`` float32
    tensors (``2 x b x s x d x n x 4 B``: 2 x 2.7 GB at 1 x 8192 x 5120
    x 16) and exists for ``MambaForCausalLM``'s CPU tests only; a model
    meant for the chip checks the sequence itself and raises
    (``models/phi4flash.py``)."""

    def __init__(self, config: MambaConfig):
        super().__init__()
        cfg = config
        d_in = cfg.d_inner
        init = I.Normal(0.0, cfg.initializer_range)
        self.in_proj = Linear(cfg.hidden_size, 2 * d_in, weight_attr=init,
                              bias_attr=False)
        # depthwise causal conv over the sequence
        bound = cfg.conv_kernel ** -0.5
        self.conv_weight = self.create_parameter(
            (d_in, cfg.conv_kernel),
            default_initializer=I.Uniform(-bound, bound))
        self.conv_bias = self.create_parameter((d_in,), is_bias=True)
        self.x_proj = Linear(d_in, cfg.dt_rank + 2 * cfg.state_size,
                             weight_attr=init, bias_attr=False)
        self.dt_proj = Linear(
            cfg.dt_rank, d_in, weight_attr=init,
            bias_attr=_dt_bias_init(cfg.time_step_min, cfg.time_step_max,
                                    cfg.time_step_floor))
        self.A_log = self.create_parameter(
            (d_in, cfg.state_size),
            default_initializer=lambda key, shape, dtype: jnp.log(
                jnp.broadcast_to(
                    jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape
                )
            ),
        )
        self.D = self.create_parameter(
            (d_in,), default_initializer=I.Constant(1.0)
        )
        self.out_proj = Linear(d_in, cfg.hidden_size, weight_attr=init,
                               bias_attr=False)
        self.config = config

    def forward(self, x, return_scan_output=False):
        cfg = self.config
        with jax.named_scope("s6_in"):
            xz = self.in_proj(x)
        gated, y = _s6_core(
            xz, self.conv_weight.value, self.conv_bias.value,
            self.x_proj.weight.value, self.dt_proj.weight.value,
            self.dt_proj.bias.value, self.A_log.value, self.D.value,
            (cfg.dt_rank, cfg.state_size,
             cfg.scan_chunk if cfg.use_chunked_scan else 0))
        with jax.named_scope("s6_out"):
            out = self.out_proj(gated)
        return (out, y) if return_scan_output else out


# what ``_s6_core``'s forward keeps for its backward pass beside its
# arguments: the scan's output and the states between chunks, so that
# the forward kernel runs once
_S6_KEPT = ("s6_y", *_S6_RESIDUAL_NAMES)


@functools.partial(
    jax.checkpoint, static_argnums=(8,),
    policy=jax.checkpoint_policies.save_only_these_names(*_S6_KEPT))
def _s6_core(xz, taps, conv_bias, x_proj_w, dt_w, dt_b, A_log, D, sizes):
    """Between ``in_proj`` and ``out_proj``: conv, the two small
    projections, the recurrence, the gate. Returns the gated product and
    the scan's own output ``y`` (before the gate), both in ``xz``'s
    dtype. The backward pass makes the stretch again from ``xz`` (the
    conv, ``x_proj`` and ``dt_proj``: 0.1% of a layer's products, and
    element-wise passes), all but the scan's kernel, whose ``y`` and
    states it keeps (``_S6_KEPT``): kept whole, the float32 conv sums,
    ``delta`` and their activations are 0.7 GB a layer at 8192 x 5120."""
    dt_rank, n, chunk = sizes
    b, s, _ = xz.shape
    f32, dtype = jnp.float32, xz.dtype
    xs, z = jnp.split(xz, 2, axis=-1)  # [b, s, d_in] each
    with jax.named_scope("s6_in"):
        # causal depthwise conv; tap k-1 weighs the current step
        k = taps.shape[1]
        padded = jnp.pad(xs.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
        xs = sum(padded[:, i:i + s] * taps[:, i].astype(f32)
                 for i in range(k)) + conv_bias.astype(f32)
        xs = F.silu(xs).astype(dtype)
        dt, B, C = jnp.split(xs @ x_proj_w, [dt_rank, dt_rank + n], axis=-1)
        delta = jax.nn.softplus(
            jnp.matmul(dt, dt_w, preferred_element_type=f32)
            + dt_b.astype(f32))
        A = -jnp.exp(A_log.astype(f32))
    with jax.named_scope("s6_scan"):
        if chunk and s % chunk == 0:
            y = chunked_selective_scan(xs, delta, A, B, C, D, chunk=chunk)
        else:
            y = selective_scan(xs.astype(f32), delta, A, B.astype(f32),
                               C.astype(f32), D.astype(f32))
        y = checkpoint_name(y.astype(dtype), "s6_y")
    with jax.named_scope("s6_out"):
        return y * F.silu(z), y


class MambaBlock(Layer):
    def __init__(self, config: MambaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mixer = MambaMixer(config)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class MambaForCausalLM(Layer):
    def __init__(self, config: MambaConfig):
        super().__init__()
        self.config = config
        self.embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size
        )
        self.layers = LayerList(
            [MambaBlock(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm_f = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, labels=None):
        x = self.embeddings(input_ids)
        x = shard_activation(x, ("dp", "fsdp"), "sep", None)
        for layer in self.layers:
            x = layer(x)
        x = self.norm_f(x)
        logits = x @ self.embeddings.weight.value.T  # tied
        if labels is None:
            return logits
        return F.cross_entropy(logits, F.shift_labels(labels))


class Mamba2Mixer(Layer):
    """The Mamba-2 mixer, on h [b, s, hidden] (the block's norm and
    residual are the caller's):

        [z | xBC | dt] = h W_in                      (no bias)
        xBC = silu(causal_depthwise_conv1d(xBC) + b_conv)
        x, B, C = split(xBC);  dt = softplus(dt + dt_bias)
        y = SSD(x, dt, A = -exp(A_log), B, C, D)     (kernels/ssd.py)
        out = GroupRMSNorm(y * silu(z)) W_out        (gate first)

    ``dt``, ``A`` and the recurrence are float32; the device phases are
    the scopes ``ssm_in``, ``ssm_scan`` and ``ssm_out``."""

    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 n_groups, conv_kernel=4, chunk_size=128, norm_eps=1e-5,
                 init_std=0.02, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.chunk_size, self.norm_eps = chunk_size, norm_eps
        d_in = num_heads * head_dim
        conv_dim = d_in + 2 * n_groups * state_size
        init = I.Normal(0.0, init_std)
        self.in_proj = Linear(hidden_size, d_in + conv_dim + num_heads,
                              weight_attr=init, bias_attr=False)
        self.conv_weight = self.create_parameter(
            (conv_dim, conv_kernel),
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter((conv_dim,), is_bias=True)

        self.dt_bias = self.create_parameter(
            (num_heads,), default_initializer=_dt_bias_init(
                time_step_min, time_step_max, time_step_floor))
        self.A_log = self.create_parameter(
            (num_heads,),
            default_initializer=lambda key, shape, dtype: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
            ).astype(dtype))
        self.D = self.create_parameter(
            (num_heads,), default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            (d_in,), default_initializer=I.Constant(1.0))
        self.out_proj = Linear(d_in, hidden_size, weight_attr=init,
                               bias_attr=False)

    def forward(self, h):
        nh, g = self.num_heads, self.n_groups
        with jax.named_scope("ssm_in"):
            zxbcdt = self.in_proj(h)
        y = _mamba2_core(
            zxbcdt, self.conv_weight.value, self.conv_bias.value,
            self.dt_bias.value, self.A_log.value, self.D.value,
            self.norm_weight.value,
            (nh, self.head_dim, g, self.state_size, self.chunk_size,
             self.norm_eps))
        with jax.named_scope("ssm_out"):
            return self.out_proj(y)


# what ``_mamba2_core``'s forward keeps for its backward pass: the conv's
# float32 pre-activation, the SSD's ``y``, decays and states, each group's
# mean and rsqrt. The kernels' bf16 ``x``, ``B``, ``C`` and the gated
# product are made again from these, element-wise: with them kept too the
# benchmark's step no longer fits one v5e by the compiler's count, and
# XLA makes weight products again
_MAMBA2_KEPT = ("mamba2_conv_pre", "mamba2_y", "mamba2_group_mean",
                "mamba2_group_rsqrt", *_SSD_RESIDUAL_NAMES)


@functools.partial(
    jax.checkpoint, static_argnums=(7,),
    policy=jax.checkpoint_policies.save_only_these_names(*_MAMBA2_KEPT))
def _mamba2_core(zxbcdt, taps, conv_bias, dt_bias, A_log, D, norm_weight,
                 sizes):
    """Between the two projections: conv, the SSD, the gated norm. The
    conv's sums, the SSD's kernel and cumulative sums and the norm's
    group sums run once: the forward keeps what the backward reads of
    them (``_MAMBA2_KEPT``), and what the backward makes again is
    element-wise and fuses into its readers (casts, ``silu``,
    ``softplus``, ``exp``, the gate's product, the pad's slices). A
    caller short of memory at other shapes recomputes whole blocks
    (``distributed/sharding.py:recompute``)."""
    nh, p, g, n, chunk, eps = sizes
    b, s, _ = zxbcdt.shape
    d_in, f32 = nh * p, jnp.float32
    z, xBC, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    with jax.named_scope("ssm_in"):
        # causal depthwise conv; tap k-1 weighs the current step
        k = taps.shape[1]
        padded = jnp.pad(xBC.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
        xBC = sum(padded[:, i:i + s] * taps[:, i].astype(f32)
                  for i in range(k))
        xBC = checkpoint_name(xBC + conv_bias.astype(f32), "mamba2_conv_pre")
        xBC = F.silu(xBC).astype(zxbcdt.dtype)
        x, B, C = jnp.split(xBC, [d_in, d_in + g * n], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        A = -jnp.exp(A_log.astype(f32))
    with jax.named_scope("ssm_scan"):
        y = ssd_chunked(x.reshape(b, s, nh, p), dt, A,
                        B.reshape(b, s, g, n), C.reshape(b, s, g, n), D,
                        chunk)
    with jax.named_scope("ssm_out"):
        # the gated group RMSNorm, gate first
        y = checkpoint_name(y.reshape(b, s, d_in), "mamba2_y")
        y = y * F.silu(z.astype(f32))
        yg = y.reshape(b, s, g, d_in // g)
        mean = checkpoint_name(jnp.mean(yg * yg, -1, keepdims=True) + eps,
                               "mamba2_group_mean")
        yg = yg * checkpoint_name(jax.lax.rsqrt(mean), "mamba2_group_rsqrt")
        return (yg.reshape(b, s, d_in)
                * norm_weight.astype(f32)).astype(zxbcdt.dtype)
