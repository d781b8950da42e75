"""ERNIE-style MoE causal LM (parity: the "ERNIE-3.0 / ERNIE-Bot MoE
(expert-parallel via auto_parallel over ICI)" config in BASELINE.json):
a GPT-style backbone whose FFN is a gated mixture-of-experts every
``moe_every`` layers, trained with the GShard aux load-balance loss and
expert parallelism over the mesh."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.moe import MoELayer
from ..distributed.parallel_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding import shard_activation
from ..nn import functional as F
from ..nn.layer.common import Dropout, Embedding, LayerList
from ..nn.layer.norm import LayerNorm
from .gpt import GPTAttention, GPTConfig


@dataclasses.dataclass
class ErnieMoEConfig(GPTConfig):
    num_experts: int = 8
    moe_every: int = 2  # every Nth block uses MoE FFN
    top_k: int = 2
    capacity_factor: float = 1.25
    gate: str = "gshard"
    aux_loss_weight: float = 1e-2
    # dropless (no-token-drop) routing: grouped matmuls single-shard,
    # sort-based all-to-all dispatch when the mesh has ep>1
    moe_dropless: bool = False

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        kw.setdefault("num_experts", 4)
        kw.setdefault("moe_every", 1)
        return cls(**kw)


class ErnieMoEBlock(Layer):
    def __init__(self, config: ErnieMoEConfig, use_moe: bool):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.ln_1 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.use_moe = use_moe
        if use_moe:
            from ..distributed.moe import DroplessMoELayer

            moe_cls = (DroplessMoELayer if config.moe_dropless
                       else MoELayer)
            if config.moe_dropless:
                # dropless routing has no capacity knob; honor the gate
                # choice through its routing width (switch == top-1)
                kw = {"top_k": 1 if config.gate == "switch"
                      else config.top_k}
            else:
                kw = {"gate": config.gate, "top_k": config.top_k,
                      "capacity_factor": config.capacity_factor}
            self.moe = moe_cls(
                config.hidden_size, config.num_experts,
                d_hidden=config.intermediate_size,
                aux_loss_weight=config.aux_loss_weight,
                **kw,
            )
        else:
            self.fc_in = ColumnParallelLinear(
                config.hidden_size, config.intermediate_size,
                weight_attr=init,
            )
            self.fc_out = RowParallelLinear(
                config.intermediate_size, config.hidden_size,
                weight_attr=init,
            )
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        x = x + self.dropout(self.attn(self.ln_1(x)))
        h = self.ln_2(x)
        if self.use_moe:
            y, aux = self.moe(h)
            return x + self.dropout(y), aux
        y = self.fc_out(F.gelu(self.fc_in(h), approximate=True))
        return x + self.dropout(y), 0.0


class ErnieMoEForCausalLM(Layer):
    def __init__(self, config: ErnieMoEConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=init
        )
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=init,
        )
        self.blocks = LayerList([
            ErnieMoEBlock(
                config, use_moe=((i + 1) % config.moe_every == 0)
            )
            for i in range(config.num_hidden_layers)
        ])
        self.ln_f = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, weight_attr=init,
            has_bias=False,
        )

    def forward(self, input_ids, labels=None):
        b, s = input_ids.shape
        pos = jnp.arange(s)[None, :]
        x = self.embeddings(input_ids) + self.position_embeddings(pos)
        x = shard_activation(x, ("dp", "fsdp"), "sep", None)
        total_aux = 0.0
        for block in self.blocks:
            x, aux = block(x)
            total_aux = total_aux + aux
        x = self.ln_f(x)
        logits = self.lm_head(x)
        if labels is None:
            return logits
        lm_loss = F.cross_entropy(
            logits, F.shift_labels(labels), ignore_index=-100
        )
        return lm_loss + total_aux
