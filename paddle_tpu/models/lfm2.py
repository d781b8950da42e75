"""LFM2-MoE: gated short convolutions, q/k-normed grouped-query attention
and sparse SwiGLU experts (``model_type: lfm2_moe``).

Every layer is an operator and a feed-forward behind RMSNorms and
residuals, with ``rms(x) = x * rsqrt(mean(x^2) + norm_eps) * g``:

    h <- h + operator_i(rms(h));  h <- h + ffn_i(rms(h))

``layer_types[i]`` names the operator:

- ``conv``, a **gated short convolution**: ``B, C, x = split3(in_proj(u))``
  (one product, in that order); ``z_t = sum_j w[:, j] (B * x)_{t-L+1+j}``
  (depthwise, causal, zeros before the sequence, the last tap on the
  current step, ``conv_L_cache`` taps, no bias); ``out_proj(C * z)``. No
  activation anywhere in it: not a Mamba mixer.
- ``full_attention``: bias-free q, k, v; **RMSNorm over each q and k
  head** (one weight of ``head_dim`` each) BEFORE rotate-half RoPE over
  the whole head; causal softmax at ``1 / sqrt(head_dim)``, grouped
  queries, through the flash kernel pair; ``out_proj``.

The feed-forward of layer ``i < num_dense_layers`` is a dense SwiGLU MLP
``w2(silu(w1 x) * (w3 x))``; of the others, ``distributed/moe.py:
HeldExpertsMoE`` in its gated form: a float32 sigmoid router over all
``num_experts``, the top ``num_experts_per_tok`` of ``score + expert
bias`` (a float32 buffer, zero as initialised: it moves the choice, not
the weight), the chosen scores renormalised (``/ (sum + 1e-6)``) and
scaled, no shared expert, and of the routed experts those this chip
holds (``held_experts``; all of them by default). An expert's leaves
follow ``ExpertFFN``: ``experts.w3`` is the published ``w1`` (under the
silu), ``experts.w1`` the published ``w3``, ``experts.w2`` its ``w2``.

A final RMSNorm (``embedding_norm``) and a head tied to the embedding.

Device phases (``observability/spans.py``): ``sconv_in`` / ``sconv_mix``
/ ``sconv_out``, ``attn_in`` / ``attn_out`` (no scope encloses a flash
call site), ``mlp``, ``moe_router`` / ``moe_experts``, ``embed``,
``head_loss``. The sparse layers' routing counts of a step come back
through ``step_counters()``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.moe import HeldExpertsMoE, sum_routing_counts
from ..distributed.parallel_layers import VocabParallelEmbedding
from ..distributed.sharding import shard_activation
from ..kernels import flash_attention as fa
from ..kernels.rope import apply_rope, rope_frequencies
from ..nn import functional as F
from ..nn.layer.common import LayerList, Linear
from ..nn.layer.norm import RMSNorm


ROUTER_NORM_EPS = 1e-6  # added to the sum of the chosen scores
# every held expert walks at least the blocks that this times its even
# share of the routed rows fills (HeldExpertsMoE.even_share_slack): the
# step's time then does not follow the draw of the routers
EXPERT_EVEN_SHARE_SLACK = 1.25

_PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    # experts: the router's width, and the (first, count) this chip holds
    num_experts: int = 32
    held_experts: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    initializer_range: float = 0.02
    use_flash_attention: bool = True

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}; a layer's "
                             "operator is conv or full_attention")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers {self.num_dense_layers} "
                             f"of {len(self.layer_types)} layers")
        if self.held_experts is None:
            self.held_experts = (0, self.num_experts)

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        """Test config: both operators under both feed-forwards, four of
        eight experts held."""
        for k, v in dict(
                vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32,
                layer_types=("conv", "full_attention", "conv",
                             "full_attention"),
                num_dense_layers=2, num_attention_heads=4,
                num_key_value_heads=2, num_experts=8, held_experts=(0, 4),
                num_experts_per_tok=2).items():
            kw.setdefault(k, v)
        return cls(**kw)


class Lfm2ShortConv(Layer):
    """``out_proj(C * causal_conv(B * x))``; forward(u [b, s, hidden])."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, taps = config.hidden_size, config.conv_L_cache
        init = I.Normal(0.0, config.initializer_range)
        self.in_proj = Linear(h, 3 * h, weight_attr=init, bias_attr=False)
        bound = taps ** -0.5
        self.conv_weight = self.create_parameter(
            (h, taps), default_initializer=I.Uniform(-bound, bound))
        self.out_proj = Linear(h, h, weight_attr=init, bias_attr=False)

    def forward(self, u):
        s, f32 = u.shape[1], jnp.float32
        with jax.named_scope("sconv_in"):
            bcx = self.in_proj(u)
        with jax.named_scope("sconv_mix"):
            B, C, x = jnp.split(bcx.astype(f32), 3, axis=-1)
            taps = self.conv_weight.value.astype(f32)
            k = taps.shape[1]
            # causal depthwise conv; tap k-1 weighs the current step
            padded = jnp.pad(B * x, ((0, 0), (k - 1, 0), (0, 0)))
            z = sum(padded[:, i:i + s] * taps[:, i] for i in range(k))
            y = (C * z).astype(u.dtype)
        with jax.named_scope("sconv_out"):
            return self.out_proj(y)


class Lfm2Attention(Layer):
    """Causal grouped-query attention, no bias; each q and k head is
    RMS-normed before RoPE."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = cfg = config
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = Linear(h, nq * d, weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, nkv * d, weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, nkv * d, weight_attr=init, bias_attr=False)
        self.q_layernorm = RMSNorm(d, cfg.norm_eps)
        self.k_layernorm = RMSNorm(d, cfg.norm_eps)
        self.out_proj = Linear(nq * d, h, weight_attr=init, bias_attr=False)

    def norm_then_rotate(self, q, k):
        """q, k [b, s, heads, d]: each head normed, THEN rotated."""
        _, s, _, d = q.shape
        return apply_rope(self.q_layernorm(q), self.k_layernorm(k),
                          *rope_frequencies(d, s, self.config.rope_theta))

    def forward(self, x):
        cfg = self.config
        b, s, _ = x.shape
        d, nq, nkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads
        # no scope encloses the flash kernels' call site (spans.py)
        with jax.named_scope("attn_in"):
            q, k = self.norm_then_rotate(
                self.q_proj(x).reshape(b, s, nq, d),
                self.k_proj(x).reshape(b, s, nkv, d))
            v = self.v_proj(x).reshape(b, s, nkv, d)
        if cfg.use_flash_attention:
            out = fa.flash_attention(q, k, v, causal=True,
                                     training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training)
        with jax.named_scope("attn_out"):
            return self.out_proj(out.reshape(b, s, nq * d))


class Lfm2MLP(Layer):
    """``w2(silu(w1 x) * (w3 x))``, no bias."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        init = I.Normal(0.0, config.initializer_range)
        self.w1 = Linear(h, f, weight_attr=init, bias_attr=False)
        self.w3 = Linear(h, f, weight_attr=init, bias_attr=False)
        self.w2 = Linear(f, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.w2(F.swiglu(self.w1(x), self.w3(x)))


class Lfm2DecoderLayer(Layer):
    """Layer ``i``: its operator by ``layer_types[i]``, its feed-forward
    dense below ``num_dense_layers`` and sparse from there."""

    def __init__(self, config: Lfm2MoeConfig, i: int):
        super().__init__()
        cfg = config
        self.is_attention = cfg.layer_types[i] == "full_attention"
        self.is_sparse = i >= cfg.num_dense_layers
        self.operator_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if self.is_attention:
            self.self_attn = Lfm2Attention(cfg)
        else:
            self.conv = Lfm2ShortConv(cfg)
        self.ffn_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if self.is_sparse:
            self.feed_forward = HeldExpertsMoE(
                cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                activation="silu", routed_scale=cfg.routed_scaling_factor,
                init_std=cfg.initializer_range, gated=True,
                norm_eps=ROUTER_NORM_EPS,
                even_share_slack=EXPERT_EVEN_SHARE_SLACK)
        else:
            self.feed_forward = Lfm2MLP(cfg)

    def forward(self, x):
        first, last = ("attn_in", "attn_out") if self.is_attention \
            else ("sconv_in", "sconv_out")
        with jax.named_scope(first):
            h = self.operator_norm(x)
        h = self.self_attn(h) if self.is_attention else self.conv(h)
        with jax.named_scope(last):
            x = x + h
        if not self.is_sparse:
            with jax.named_scope("mlp"):
                return x + self.feed_forward(self.ffn_norm(x))
        with jax.named_scope("moe_router"):
            h = self.ffn_norm(x)
        h = self.feed_forward(h)
        with jax.named_scope("moe_experts"):
            return x + h


class Lfm2MoeModel(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            Lfm2DecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.embedding_norm = RMSNorm(config.hidden_size, config.norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            h = shard_activation(h, ("dp", "fsdp"), "sep", None)
        for layer in self.layers:
            h = layer(h)
        with jax.named_scope("head_loss"):
            return self.embedding_norm(h)


class Lfm2MoeForCausalLM(Layer):
    """The embedding is the head too: one leaf, whose gradient is the
    sum of both uses."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        with jax.named_scope("head_loss"):
            logits = hidden @ self.model.embed_tokens.weight.value.T
            if labels is None:
                return logits
            return F.cross_entropy(logits, F.shift_labels(labels),
                                   ignore_index=-100)

    def step_counters(self):
        """The routing counts of the forward pass just traced, summed
        over the sparse layers (``moe_rows_max``: the fullest held
        expert of the worst layer), as ``NemotronHForCausalLM`` has
        them, and ``moe_rows_walked``, the rows the held experts
        multiplied, padding and floor included; ``TrainStep`` returns
        them beside the gradient norm."""
        return sum_routing_counts([
            layer.feed_forward.last_counts for layer in self.model.layers
            if layer.is_sparse])
