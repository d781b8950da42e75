"""Phi-4-mini-flash: a decoder-hybrid-decoder of Mamba-1 scans,
differential attention and gated memory units (``model_type: phi4flash``).

Every layer is a mixer and a gated MLP behind LayerNorms (weight and
bias) and residuals:

    x <- x + mixer_l(LN(x));  x <- x + MLP(LN(x))
    MLP: g, u = split(x W1);  (u * silu(g)) W2        (no bias)

and the mixer follows the layer's **published index** ``l`` of
``num_hidden_layers`` (32; ``half`` = 16), which ``layer_kind`` spells:

- ``l`` even, ``l <= half``: **Mamba-1** (``models/mamba.py:
  MambaMixer``, the chunked selective scan of
  ``kernels/selective_scan.py``). Layer ``half`` also hands its scan
  output ``y``, before the gate, on as the **memory** ``m``.
- ``l`` odd, ``l <= half + 1``: **differential attention**, causal, with
  a window of ``sliding_window`` for ``l < half`` and full at
  ``half + 1``, which also hands its keys and values on. Consecutive
  heads pair: ``q1, q2 = q[2i], q[2i+1]``, ``k`` and ``v`` likewise,
  ``V = [v1 | v2]``;
  ``a_j = softmax(q_j k_j^T / sqrt(d)) V``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``;
  ``o = RMSNorm_2d(a1 - lambda a2) * (1 - lambda_init(l))``.
  Each map is ONE flash call at head size ``2 d``: scores need ``d`` and
  values ``2 d``, so ``q_j`` and ``k_j`` go in zero-padded to ``2 d``
  (exact: the padding adds 0 to every score) with the scale
  ``1 / sqrt(d)`` stated. The published code makes two calls a map
  (``v1`` and ``v2`` apart) and concatenates.
- ``l`` even, ``l > half + 1``: **gated memory unit**:
  ``(m * silu(h W1)) W2``.
- ``l`` odd, ``l > half + 1``: **cross-attention**: ``q = h Wq + b``
  only; keys and values are layer ``half + 1``'s; the same differential
  form with the layer's own lambdas and sub-norm.

No positional term anywhere, no embedding scale, a final LayerNorm and a
head tied to the embedding. ``published_layer_indices`` lists the layers
this model holds (a pipeline stage's, or a cut's); the whole model is
``range(num_hidden_layers)``.

Device phases (``observability/spans.py``): ``s6_in`` / ``s6_scan`` /
``s6_out``, ``attn_in`` / ``attn_out`` (no scope encloses a flash call
site), ``gmu``, ``mlp``, ``embed``, ``head_loss``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.parallel_layers import VocabParallelEmbedding
from ..distributed.sharding import shard_activation
from ..incubate.nn.functional import fused_linear_cross_entropy
from ..kernels import flash_attention as fa
from ..nn import functional as F
from ..nn.layer.common import LayerList, Linear
from ..nn.layer.norm import LayerNorm, RMSNorm
from .mamba import MambaConfig, MambaMixer


HEAD_LOSS_ROWS = 1024  # positions whose logits are live at once


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32  # the PUBLISHED depth: it decides kinds
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    # the layers held here, by published index; None: all of them
    published_layer_indices: Optional[Tuple[int, ...]] = None
    # Mamba-1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # ceil(hidden_size / 16)
    scan_chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    initializer_range: float = 0.02
    lambda_std: float = 0.1

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("layer kinds are written for mb_per_layer 2")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)
        held = tuple(range(self.num_hidden_layers)) \
            if self.published_layer_indices is None \
            else tuple(self.published_layer_indices)
        if list(held) != sorted(set(held)) or not held or not (
                0 <= held[0] and held[-1] < self.num_hidden_layers):
            raise ValueError(f"published_layer_indices {held}: ascending "
                             f"indices below {self.num_hidden_layers}")
        self.published_layer_indices = held
        half = self.num_hidden_layers // 2
        for l in held:
            kind = self.layer_kind(l)
            if kind == "gmu" and half not in held:
                raise ValueError(f"layer {l} reads the memory of layer "
                                 f"{half}, which is not held")
            if kind == "cross" and half + 1 not in held:
                raise ValueError(f"layer {l} reads the keys and values of "
                                 f"layer {half + 1}, which is not held")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def layer_kind(self, l: int) -> str:
        """``mamba``, ``attention``, ``gmu`` or ``cross`` for published
        layer ``l``."""
        cross_decoder = l >= self.num_hidden_layers // 2 + 2
        if l % self.mb_per_layer == 0:
            return "gmu" if cross_decoder else "mamba"
        return "cross" if cross_decoder else "attention"

    def window(self, l: int) -> int:
        """The attention window of published layer ``l``; 0 is full."""
        if self.layer_kind(l) == "attention" \
                and l < self.num_hidden_layers // 2:
            return self.sliding_window
        return 0

    @staticmethod
    def lambda_init(l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)

    @classmethod
    def tiny(cls, **kw):
        """Test config: four published layers of each half, so every
        kind, the window and both hand-overs are there."""
        for k, v in dict(
                vocab_size=256, hidden_size=64, intermediate_size=96,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, sliding_window=16,
                mamba_d_state=8, scan_chunk=16).items():
            kw.setdefault(k, v)
        return cls(**kw)


class Phi4FlashMLP(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.fc1 = Linear(config.hidden_size, 2 * config.intermediate_size,
                          weight_attr=init, bias_attr=False)
        self.fc2 = Linear(config.intermediate_size, config.hidden_size,
                          weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.fc2(F.swiglu(self.fc1(x)))


class Phi4FlashDiffAttention(Layer):
    """Differential attention of published layer ``l``; with ``cross``
    it projects queries only and reads the keys and values handed in."""

    def __init__(self, config: Phi4FlashConfig, l: int, cross: bool):
        super().__init__()
        self.config = cfg = config
        self.cross, self.window = cross, cfg.window(l)
        self.lambda_init = cfg.lambda_init(l)
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        init = I.Normal(0.0, cfg.initializer_range)
        if cross:
            self.Wq = Linear(h, nq * d, weight_attr=init)
        else:
            self.Wqkv = Linear(h, (nq + 2 * nkv) * d, weight_attr=init)
        self.out_proj = Linear(nq * d, h, weight_attr=init)
        lam = I.Normal(0.0, cfg.lambda_std)
        self.lambda_q1 = self.create_parameter((d,), default_initializer=lam)
        self.lambda_k1 = self.create_parameter((d,), default_initializer=lam)
        self.lambda_q2 = self.create_parameter((d,), default_initializer=lam)
        self.lambda_k2 = self.create_parameter((d,), default_initializer=lam)
        self.subln = RMSNorm(2 * d, cfg.layer_norm_eps)

    def forward(self, x, kv=None):
        cfg = self.config
        b, s, _ = x.shape
        d, nq, nkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads

        def pairs(t, heads):
            """Heads 2i and 2i+1 apart, each zero-padded to 2 d."""
            t = jnp.pad(t.reshape(b, s, heads // 2, 2, d),
                        ((0, 0),) * 4 + ((0, d),))
            return t[:, :, :, 0], t[:, :, :, 1]

        # no scope encloses the flash kernels' call site (spans.py)
        with jax.named_scope("attn_in"):
            if self.cross:
                q = self.Wq(x)
            else:
                q, k, v = jnp.split(self.Wqkv(x),
                                    [nq * d, (nq + nkv) * d], axis=-1)
                kv = (*pairs(k, nkv), v.reshape(b, s, nkv // 2, 2 * d))
            q1, q2 = pairs(q, nq)
            k1, k2, V = kv
        a1, a2 = (fa.flash_attention(
            qj, kj, V, causal=True, training=self.training,
            scale=d ** -0.5, window_size=self.window)
            for qj, kj in ((q1, k1), (q2, k2)))
        with jax.named_scope("attn_out"):
            f32 = jnp.float32
            lam = jnp.exp(jnp.sum(self.lambda_q1.value.astype(f32)
                                  * self.lambda_k1.value.astype(f32))) \
                - jnp.exp(jnp.sum(self.lambda_q2.value.astype(f32)
                                  * self.lambda_k2.value.astype(f32))) \
                + self.lambda_init
            o = self.subln((a1.astype(f32) - lam * a2.astype(f32))
                           ).astype(x.dtype) * (1.0 - self.lambda_init)
            return self.out_proj(o.reshape(b, s, nq * d)), kv


class Phi4FlashGMU(Layer):
    """Gated memory unit: (m * silu(h W1)) W2, ``m`` another layer's scan
    output."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.in_proj = Linear(config.hidden_size, config.d_inner,
                              weight_attr=init, bias_attr=False)
        self.out_proj = Linear(config.d_inner, config.hidden_size,
                               weight_attr=init, bias_attr=False)

    def forward(self, x, memory):
        return self.out_proj(memory * F.silu(self.in_proj(x)))


class Phi4FlashDecoderLayer(Layer):
    """Published layer ``l``: ``forward(x, memory, kv)`` returns the
    three again, ``memory`` and ``kv`` replaced where this layer is the
    one that makes them."""

    def __init__(self, config: Phi4FlashConfig, l: int):
        super().__init__()
        cfg = config
        self.kind = cfg.layer_kind(l)
        half = cfg.num_hidden_layers // 2
        self.makes_memory = self.kind == "mamba" and l == half
        self.makes_kv = self.kind == "attention" and l == half + 1
        self.scan_chunk = cfg.scan_chunk
        self.input_layernorm = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        if self.kind == "mamba":
            self.mixer = MambaMixer(MambaConfig(
                hidden_size=cfg.hidden_size, state_size=cfg.mamba_d_state,
                expand=cfg.mamba_expand, dt_rank=cfg.mamba_dt_rank,
                conv_kernel=cfg.mamba_d_conv, use_chunked_scan=True,
                scan_chunk=cfg.scan_chunk,
                initializer_range=cfg.initializer_range,
                time_step_min=cfg.time_step_min,
                time_step_max=cfg.time_step_max,
                time_step_floor=cfg.time_step_floor))
        elif self.kind == "gmu":
            self.mixer = Phi4FlashGMU(cfg)
        else:
            self.mixer = Phi4FlashDiffAttention(
                cfg, l, cross=self.kind == "cross")
        self.post_attention_layernorm = LayerNorm(cfg.hidden_size,
                                                  cfg.layer_norm_eps)
        self.mlp = Phi4FlashMLP(cfg)

    def forward(self, x, memory, kv):
        if self.kind == "mamba":
            if x.shape[1] % self.scan_chunk:
                # the mixer would fall back to the associative scan
                raise ValueError(
                    f"sequence {x.shape[1]} is no multiple of scan_chunk "
                    f"{self.scan_chunk}")
            with jax.named_scope("s6_in"):
                h = self.input_layernorm(x)
            h, y = self.mixer(h, return_scan_output=True)
            if self.makes_memory:
                memory = y
            with jax.named_scope("s6_out"):
                x = x + h
        elif self.kind == "gmu":
            with jax.named_scope("gmu"):
                x = x + self.mixer(self.input_layernorm(x), memory)
        else:
            with jax.named_scope("attn_in"):
                h = self.input_layernorm(x)
            h, new_kv = self.mixer(h, kv)
            if self.makes_kv:
                kv = new_kv
            with jax.named_scope("attn_out"):
                x = x + h
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x, memory, kv


class Phi4FlashModel(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            Phi4FlashDecoderLayer(config, l)
            for l in config.published_layer_indices])
        self.final_layernorm = LayerNorm(config.hidden_size,
                                         config.layer_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            h = shard_activation(h, ("dp", "fsdp"), "sep", None)
        memory = kv = None
        for layer in self.layers:
            h, memory, kv = layer(h, memory, kv)
        with jax.named_scope("head_loss"):
            return self.final_layernorm(h)


class Phi4FlashForCausalLM(Layer):
    """The embedding is the head too: one leaf, whose gradient is the
    sum of both uses."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        embedding = self.model.embed_tokens.weight.value
        with jax.named_scope("head_loss"):
            if labels is None:
                return hidden @ embedding.T
            # in blocks of the sequence, each block's logits made again
            # in the backward pass: whole, the logits and their float32
            # log-softmax are 1.6 GB at 8192 x 25,008
            return fused_linear_cross_entropy(
                hidden, embedding, F.shift_labels(labels),
                transpose_weight=True, ignore_index=-100,
                seq_chunk=HEAD_LOSS_ROWS)
