"""Nemotron-H: a hybrid stack of Mamba-2, sparse-expert and attention blocks.

``hybrid_override_pattern`` spells the stack, one character a block:
``M`` a Mamba-2 mixer (``models/mamba.py: Mamba2Mixer``, the chunked
SSD of ``kernels/ssd.py``), ``E`` a sparse-expert layer
(``distributed/moe.py: HeldExpertsMoE``: sigmoid router over all the
experts, top-k, a shared expert, and of the routed experts those this
chip holds), ``*`` grouped-query attention through the flash kernels,
with **no positional term** (the recurrent blocks carry the order).
Every block is ONE mixer behind one RMSNorm and one residual:

    x <- x + mixer_i(RMSNorm(x))

then a final RMSNorm and an untied head; the loss is the mean
next-token cross-entropy. Parameter names follow the published
checkpoints (``backbone.layers.{i}.mixer...``).

Device phases (``observability/spans.py``): ``ssm_in``/``ssm_scan``/
``ssm_out``, ``moe_router``/``moe_experts``/``moe_shared``,
``attn_in``/``attn_out``, ``embed``, ``head_loss``. The expert blocks'
routing counts of a step come back through ``step_counters()``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.moe import HeldExpertsMoE, sum_routing_counts
from ..distributed.parallel_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding import shard_activation
from ..kernels import flash_attention as fa
from ..nn import functional as F
from ..nn.layer.common import LayerList
from ..nn.layer.norm import RMSNorm
from .mamba import Mamba2Mixer


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*" \
                                   "EMEMEMEM*EMEMEMEME"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts: the router's width, and the (first, count) this chip holds
    n_routed_experts: int = 128
    held_experts: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    def __post_init__(self):
        bad = set(self.hybrid_override_pattern) - set("ME*")
        if bad:
            raise ValueError(f"hybrid_override_pattern holds {sorted(bad)}; "
                             "a block is M, E or *")

    @classmethod
    def tiny(cls, **kw):
        """Test config: every kind of block, four of sixteen experts."""
        for k, v in dict(
                vocab_size=256, hidden_size=64,
                hybrid_override_pattern="ME*", num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
                mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                chunk_size=16, n_routed_experts=16, held_experts=(0, 4),
                num_experts_per_tok=3, moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=64).items():
            kw.setdefault(k, v)
        return cls(**kw)


class NemotronHAttention(Layer):
    """Causal grouped-query attention, no bias, no positional term."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = cfg = config
        h, d = cfg.hidden_size, cfg.head_dim
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = ColumnParallelLinear(
            h, cfg.num_attention_heads * d, weight_attr=init, has_bias=False)
        self.k_proj = ColumnParallelLinear(
            h, cfg.num_key_value_heads * d, weight_attr=init, has_bias=False)
        self.v_proj = ColumnParallelLinear(
            h, cfg.num_key_value_heads * d, weight_attr=init, has_bias=False)
        self.o_proj = RowParallelLinear(
            cfg.num_attention_heads * d, h, weight_attr=init, has_bias=False)

    def forward(self, x):
        cfg = self.config
        b, s, _ = x.shape
        # no scope encloses the flash kernels' call site (spans.py)
        with jax.named_scope("attn_in"):
            q = self.q_proj(x).reshape(
                b, s, cfg.num_attention_heads, cfg.head_dim)
            k = self.k_proj(x).reshape(
                b, s, cfg.num_key_value_heads, cfg.head_dim)
            v = self.v_proj(x).reshape(
                b, s, cfg.num_key_value_heads, cfg.head_dim)
        if cfg.use_flash_attention:
            out = fa.flash_attention(q, k, v, causal=True,
                                     training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training)
        with jax.named_scope("attn_out"):
            return self.o_proj(out.reshape(b, s, -1))


class NemotronHBlock(Layer):
    """x + mixer(norm(x)); ``kind`` is the block's pattern character."""

    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        cfg = config
        self.kind = kind
        self.norm = RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        if kind == "M":
            self.mixer = Mamba2Mixer(
                cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel,
                cfg.chunk_size, cfg.layer_norm_epsilon,
                cfg.initializer_range, cfg.time_step_min,
                cfg.time_step_max, cfg.time_step_floor)
        elif kind == "E":
            self.mixer = HeldExpertsMoE(
                cfg.hidden_size, cfg.n_routed_experts,
                cfg.moe_intermediate_size, cfg.num_experts_per_tok,
                held=cfg.held_experts, activation=cfg.mlp_hidden_act,
                shared_hidden=cfg.moe_shared_expert_intermediate_size,
                routed_scale=cfg.routed_scaling_factor,
                init_std=cfg.initializer_range)
        else:
            self.mixer = NemotronHAttention(cfg)

    def forward(self, x):
        first, last = {"M": ("ssm_in", "ssm_out"),
                       "E": ("moe_router", "moe_shared"),
                       "*": ("attn_in", "attn_out")}[self.kind]
        with jax.named_scope(first):
            h = self.norm(x)
        h = self.mixer(h)
        with jax.named_scope(last):
            return x + h


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            NemotronHBlock(config, kind)
            for kind in config.hybrid_override_pattern])
        self.norm_f = RMSNorm(config.hidden_size, config.layer_norm_epsilon)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embeddings(input_ids)
            h = shard_activation(h, ("dp", "fsdp"), "sep", None)
        for layer in self.layers:
            h = layer(h)
        with jax.named_scope("head_loss"):
            return self.norm_f(h)


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size,
            weight_attr=I.Normal(0.0, config.initializer_range),
            has_bias=False)

    def forward(self, input_ids, labels=None):
        hidden = self.backbone(input_ids)
        with jax.named_scope("head_loss"):
            logits = self.lm_head(hidden)
            if labels is None:
                return logits
            return F.cross_entropy(logits[:, :-1, :], labels[:, 1:],
                                   ignore_index=-100)

    def step_counters(self):
        """The routing counts of the forward pass just traced, summed
        over the expert blocks (``moe_rows_max``: the fullest held
        expert of the worst block). ``TrainStep`` returns them beside
        the gradient norm; telemetry reads them on sampled steps."""
        return sum_routing_counts([
            blk.mixer.last_counts for blk in self.backbone.layers
            if blk.kind == "E"])
