"""GPT-family causal LM (parity: PaddleNLP GPT / ERNIE dense configs
running under Fleet hybrid parallel — pre-LN transformer, learned
positions, GELU MLP; TP via Column/Row parallel projections)."""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import initializer as I
from ..core.module import Layer
from ..distributed.parallel_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding import shard_activation
from ..kernels import flash_attention as fa
from ..nn import functional as F
from ..nn.layer.common import Dropout, Embedding, LayerList
from ..nn.layer.norm import LayerNorm


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    # chunked fused head+CE (see LlamaConfig.fused_head_loss_chunk);
    # 0 = off — worth enabling for GPT's 50k vocab at long seq
    fused_head_loss_chunk: int = 0

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        h = config.hidden_size
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, weight_attr=init)
        self.out_proj = RowParallelLinear(h, h, weight_attr=init)
        self.dropout = Dropout(config.attention_probs_dropout_prob)

    def forward(self, x, kv_cache=None, cache_index=None):
        cfg = self.config
        b, s, _ = x.shape
        qkv = self.qkv_proj(x).reshape(
            b, s, 3, cfg.num_attention_heads, cfg.head_dim
        )
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if kv_cache is not None:
            # AOT-Predictor cache protocol: prefill writes the prompt
            # K/V at [0:s] (cache_index 0), a single-token step writes
            # at scalar cache_index and attends over the masked cache.
            # (llama.py additionally implements the per-slot vector
            # index + chunked forms the continuous-batching engine uses)
            if getattr(cache_index, "ndim", 0) == 1:
                raise ValueError(
                    "GPT decode cache supports scalar cache_index only "
                    "(the continuous-batching engine's per-slot vector "
                    "form is implemented for Llama)")
            if cache_index is None:
                cache_index = 0
            ck, cv = kv_cache
            k = k.astype(ck.dtype)
            v = v.astype(cv.dtype)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k, cache_index, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v, cache_index, 1)
            if s > 1 and isinstance(cache_index, int) and cache_index == 0:
                # prefill fast path: s×s causal attention over the
                # prompt only (the full-cache masked form below costs
                # O(s·L) for an L-slot cache)
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, training=False)
                return (self.out_proj(
                    out.reshape(b, s, cfg.hidden_size)), (ck, cv))
            # chunked form: query i sits at absolute position
            # cache_index + i and may attend to kv_idx <= that
            q_pos = cache_index + jnp.arange(s)              # [s]
            live = (jnp.arange(ck.shape[1])[None, :]
                    <= q_pos[:, None])                       # [s, L]
            bias = jnp.where(live, 0.0, -1e30)[None, None, :, :]
            out = F.scaled_dot_product_attention(
                q, ck, cv, attn_mask=bias, training=False)
            return (self.out_proj(out.reshape(b, s, cfg.hidden_size)),
                    (ck, cv))
        if cfg.use_flash_attention and not (
            self.training and cfg.attention_probs_dropout_prob > 0
        ):
            out = fa.flash_attention(q, k, v, causal=True,
                                     training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_probs_dropout_prob,
                training=self.training,
            )
        return self.out_proj(out.reshape(b, s, cfg.hidden_size))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.ln_1 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.fc_in = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, weight_attr=init
        )
        self.fc_out = RowParallelLinear(
            config.intermediate_size, config.hidden_size, weight_attr=init
        )
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, kv_cache=None, cache_index=None):
        if kv_cache is not None:
            a, kv_cache = self.attn(self.ln_1(x), kv_cache, cache_index)
            x = x + a
            h = self.fc_out(
                F.gelu(self.fc_in(self.ln_2(x)), approximate=True))
            return x + h, kv_cache
        x = x + self.dropout(self.attn(self.ln_1(x)))
        h = self.fc_out(F.gelu(self.fc_in(self.ln_2(x)), approximate=True))
        return x + self.dropout(h)


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
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
        self.drop = Dropout(config.hidden_dropout_prob)
        self.h = LayerList(
            [GPTBlock(config) for _ in range(config.num_hidden_layers)]
        )
        self.ln_f = LayerNorm(config.hidden_size, config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, kv_caches=None,
                cache_index=None):
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = jnp.arange(s)[None, :]
        x = self.embeddings(input_ids) + self.position_embeddings(position_ids)
        x = shard_activation(x, ("dp", "fsdp"), "sep", None)
        if kv_caches is not None:
            new_caches = []
            for block, cache in zip(self.h, kv_caches):
                x, cache = block(x, cache, cache_index)
                new_caches.append(cache)
            return self.ln_f(x), new_caches
        x = self.drop(x)
        for block in self.h:
            x = block(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size,
            weight_attr=I.Normal(0.0, config.initializer_range),
            has_bias=False,
        )

    def forward(self, input_ids, labels=None, position_ids=None,
                kv_caches=None, cache_index=None):
        if kv_caches is not None:
            hidden, caches = self.gpt(input_ids, position_ids,
                                      kv_caches, cache_index)
            return self.lm_head(hidden), caches
        hidden = self.gpt(input_ids, position_ids)
        if labels is not None and self.config.fused_head_loss_chunk:
            from ..incubate.nn.functional import fused_linear_cross_entropy

            return fused_linear_cross_entropy(
                hidden, self.lm_head.weight.value,
                F.shift_labels(labels), ignore_index=-100,
                seq_chunk=self.config.fused_head_loss_chunk)
        logits = self.lm_head(hidden)
        if labels is None:
            return logits
        return F.cross_entropy(
            logits, F.shift_labels(labels), ignore_index=-100
        )

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        cfg = self.config
        dtype = dtype or jnp.bfloat16
        return [
            (
                jnp.zeros((batch_size, max_len, cfg.num_attention_heads,
                           cfg.head_dim), dtype),
                jnp.zeros((batch_size, max_len, cfg.num_attention_heads,
                           cfg.head_dim), dtype),
            )
            for _ in range(cfg.num_hidden_layers)
        ]
