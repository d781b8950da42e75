"""Llama-family causal LM — the flagship pretraining model.

Parity: PaddleNLP's LlamaForCausalLM running under Fleet hybrid parallel
(the reference's BASELINE 7B/70B configs: paddlenlp/transformers/llama/
modeling.py with fused rope/rms_norm/flash-attn phi kernels,
ColumnParallelLinear/RowParallelLinear from fleet.meta_parallel).

TPU-first construction:
  - all parallelism is declared, not coded: TP via Parameter.spec on the
    qkv/gate/up (column) and o/down (row) projections, ZeRO-3 via the
    sharding engine's fsdp augmentation, sequence/context parallel via
    activation constraints — GSPMD emits the collectives;
  - attention runs through kernels.flash_attention (Pallas on TPU);
  - rope/rmsnorm are XLA-fused jnp (kernels/rope.py rationale);
  - activation recompute per decoder layer via jax.checkpoint with a
    dots-saveable policy (parity: fleet recompute with
    sequence-parallel-aware RNG handled by functional rng_context).
"""

from __future__ import annotations

import dataclasses
from functools import partial
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
from ..distributed.sharding import sequence_parallel_constraint, shard_activation
from ..kernels import flash_attention as fa
from ..kernels.rope import apply_rope, rope_frequencies
from ..nn import functional as F
from ..nn.layer.norm import RMSNorm


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    # sequence-parallel attention mode when mesh sep>1:
    #   "ulysses" — all-to-all heads↔seq exchange (SEP)
    #   "ring"    — ring attention with rotating KV (CP)
    sep_attention: str = "ulysses"
    use_recompute: bool = False
    recompute_policy: str = "dots_with_no_batch_dims_saveable"
    # chunked fused head+CE loss: full [b, s, vocab] f32 logits (the
    # largest train-step activation) never materialize. 0 = off. Leave
    # off when the model fits — the per-chunk dW accumulation + logits
    # recompute cost ~8% of step time at 876M/v5e; turn on (e.g. 512)
    # for large-vocab/long-seq configs where the head dominates peak HBM
    fused_head_loss_chunk: int = 0
    dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(hidden_size=4096, intermediate_size=11008,
                   num_hidden_layers=32, num_attention_heads=32, **kw)

    @classmethod
    def llama3_70b(cls, **kw):
        return cls(vocab_size=128256, hidden_size=8192,
                   intermediate_size=28672, num_hidden_layers=80,
                   num_attention_heads=64, num_key_value_heads=8,
                   rope_theta=500000.0, **kw)

    @classmethod
    def tiny(cls, **kw):
        """Test/dryrun config."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


def _chunk_history_mask(cache_index, s, ctx_len):
    """Chunked-prefill causal mask, shared by both cache modes: slot
    b's chunk occupies absolute rows ``cache_index[b] .. +s-1``, and
    query row r may attend every cache position ``<= r`` (its own
    chunk's earlier rows included — they were just appended). Returns
    ``(rows [b, s], kv_mask [b, 1, s, ctx_len])``."""
    rows = cache_index[:, None] + jnp.arange(
        s, dtype=cache_index.dtype)[None, :]
    kv_idx = jnp.arange(ctx_len)
    kv_mask = kv_idx[None, None, None, :] <= rows[:, None, :, None]
    return rows, kv_mask


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        d = config.head_dim
        init = I.Normal(0.0, config.initializer_range)
        self.q_proj = ColumnParallelLinear(
            h, config.num_attention_heads * d, weight_attr=init, has_bias=False
        )
        self.k_proj = ColumnParallelLinear(
            h, config.num_key_value_heads * d, weight_attr=init, has_bias=False
        )
        self.v_proj = ColumnParallelLinear(
            h, config.num_key_value_heads * d, weight_attr=init, has_bias=False
        )
        self.o_proj = RowParallelLinear(
            config.num_attention_heads * d, h, weight_attr=init, has_bias=False
        )

    def forward(self, x, cos, sin, position_ids=None, kv_cache=None,
                cache_index=None):
        cfg = self.config
        b, s, _ = x.shape
        # device phases are named scopes (observability/spans.py) and
        # never enclose an attention kernel's call site: a kernel's
        # event is named from its scope path, and the benchmark matches
        # the names the kernels have
        with jax.named_scope("attn_in"):
            q = self.q_proj(x).reshape(
                b, s, cfg.num_attention_heads, cfg.head_dim)
            k = self.k_proj(x).reshape(
                b, s, cfg.num_key_value_heads, cfg.head_dim)
            v = self.v_proj(x).reshape(
                b, s, cfg.num_key_value_heads, cfg.head_dim)
            # heads are tp-sharded; keep [b, s, h_tp, d] layout explicit
            q = shard_activation(q, ("dp", "fsdp"), "sep", "tp", None)
            k = shard_activation(k, ("dp", "fsdp"), "sep", "tp", None)
            v = shard_activation(v, ("dp", "fsdp"), "sep", "tp", None)
        if kv_cache is not None:
            from ..distributed.sharding import current_mesh
            from ..inference.paged import (PagedLayerCache, QuantizedKV,
                                           append_kv, dequantize_kv,
                                           paged_attention,
                                           quantize_kv_rows)
            from ..kernels import decode_attention as da

            paged_mode = isinstance(kv_cache[0], PagedLayerCache)
            per_slot = getattr(cache_index, "ndim", 0) == 1
            # fused single-pass decode (PT_FLAGS_fused_decode): RoPE +
            # KV-append + length-pruned attention in one kernel — no
            # separate append_kv program, no rotated-q/k HBM round-trip.
            # Single-token per-slot decode only; under a mesh the
            # GSPMD-partitioned reference path stays in charge.
            fused = s == 1 and (paged_mode or per_slot) \
                and current_mesh() is None
            if fused:
                minor = (kv_cache[0].k_pages.shape[2] if paged_mode
                         else da.contiguous_chunk(kv_cache[0].shape[1]))
                fused = da.fused_decode_active(
                    cfg.head_dim, minor, kv_cache[0].k_pages.dtype
                    if paged_mode else kv_cache[0].dtype)
            if not fused:
                q, k = apply_rope(q, k, cos, sin, position_ids)
            kvh = cfg.num_key_value_heads
            hd = cfg.head_dim
            if fused:
                lens = (kv_cache[1].seq_lens if paged_mode
                        else jnp.asarray(cache_index, jnp.int32))
                pos = (jnp.asarray(position_ids[:, 0], jnp.int32)
                       if position_ids is not None else lens)
                qg = q[:, 0].reshape(b, kvh, cfg.num_attention_heads
                                     // kvh, hd)
                rope_cos = cos.astype(jnp.float32)
                rope_sin = sin.astype(jnp.float32)
                if paged_mode:
                    from ..kernels.paged_attention import (
                        fused_paged_decode_attention,
                    )

                    cache, state = kv_cache
                    if cache.k_scale is not None:
                        # int8 pool: the kernel quantizes the appended
                        # row and returns updated scale arrays — they
                        # ride the cache pytree like the pages do
                        og, kp, vp, ksc, vsc = \
                            fused_paged_decode_attention(
                                qg, k[:, 0], v[:, 0], cache.k_pages,
                                cache.v_pages, state.block_tables,
                                state.seq_lens, pos, rope_cos,
                                rope_sin, k_scale=cache.k_scale,
                                v_scale=cache.v_scale)
                        new_cache = (PagedLayerCache(kp, vp, ksc, vsc),
                                     state)
                    else:
                        og, kp, vp = fused_paged_decode_attention(
                            qg, k[:, 0], v[:, 0], cache.k_pages,
                            cache.v_pages, state.block_tables,
                            state.seq_lens, pos, rope_cos, rope_sin)
                        new_cache = (PagedLayerCache(kp, vp), state)
                else:
                    ck, cv = kv_cache
                    if isinstance(ck, QuantizedKV):
                        og, ckq, cvq, ksc, vsc = \
                            da.fused_contiguous_decode_attention(
                                qg, k[:, 0], v[:, 0], ck.q, cv.q,
                                lens, pos, rope_cos, rope_sin,
                                k_scale=ck.scale, v_scale=cv.scale)
                        new_cache = (QuantizedKV(ckq, ksc),
                                     QuantizedKV(cvq, vsc))
                    else:
                        og, ck, cv = \
                            da.fused_contiguous_decode_attention(
                                qg, k[:, 0], v[:, 0], ck, cv, lens,
                                pos, rope_cos, rope_sin)
                        new_cache = (ck, cv)
                out = og.reshape(b, 1, cfg.num_attention_heads, hd)
            elif paged_mode and per_slot and s > 1:
                # chunked prefill (paged): scatter the chunk's rows
                # through the block table at each slot's own offset
                # (positions past the table drop — the engine points
                # non-participating slots at a max_len sentinel), then
                # attend over the gathered page view with a per-row
                # causal-history mask. Garbage rows past a slot's real
                # tokens sit at HIGHER positions than every real query,
                # so the mask hides them; decode overwrites them later.
                # KNOWN TRADE: gather_kv materializes the full dense
                # [slots, max_ctx] view per layer per chunk — the
                # static shape is what keeps this path at ONE compile
                # for every prompt length. A length-pruned Pallas
                # chunked-prefill kernel (PR-3 style) is the follow-up
                # that removes the traffic without re-specializing.
                from ..inference.paged import append_kv_chunk, gather_kv

                cache, state = kv_cache
                cache = append_kv_chunk(cache, state, k, v, cache_index)
                kg, vg = gather_kv(cache, state)
                _, kv_mask = _chunk_history_mask(
                    cache_index, s, kg.shape[1])
                out = F.scaled_dot_product_attention(
                    q, kg, vg, attn_mask=kv_mask, training=False)
                new_cache = (cache, state)
            elif paged_mode:
                # paged decode (s == 1): write this token's kv into its
                # slot's page, then attend over the gathered page view
                cache, state = kv_cache
                cache = append_kv(cache, state, k, v)
                out = paged_attention(q, cache, state)
                new_cache = (cache, state)
            else:
                ck, cv = kv_cache
                quant = isinstance(ck, QuantizedKV)
                if not quant:
                    k = k.astype(ck.dtype)
                    v = v.astype(cv.dtype)
                if per_slot and s > 1:
                    # chunked prefill (contiguous): slot b's chunk lands
                    # at rows cache_index[b]..+s-1; mode="drop" makes
                    # rows past max_len (the engine's "not prefilling
                    # this call" sentinel) dropped writes, not clamps
                    rows, kv_mask = _chunk_history_mask(
                        cache_index, s, ck.shape[1])
                    bidx = jnp.arange(b)[:, None]
                    if quant:
                        # quantize-on-append: payload + per-row scales
                        # scatter together (scale rows share the drop
                        # semantics of the sentinel rows)
                        kq, ks = quantize_kv_rows(k)
                        vq, vs = quantize_kv_rows(v)
                        ck = QuantizedKV(
                            ck.q.at[bidx, rows].set(kq, mode="drop"),
                            ck.scale.at[bidx, rows].set(ks, mode="drop"))
                        cv = QuantizedKV(
                            cv.q.at[bidx, rows].set(vq, mode="drop"),
                            cv.scale.at[bidx, rows].set(vs, mode="drop"))
                    else:
                        ck = ck.at[bidx, rows].set(k, mode="drop")
                        cv = cv.at[bidx, rows].set(v, mode="drop")
                elif per_slot:
                    # continuous batching: each slot writes at its own
                    # length (s == 1) and masks to its own history
                    bi = jnp.arange(b)
                    if quant:
                        kq, ks = quantize_kv_rows(k[:, 0])
                        vq, vs = quantize_kv_rows(v[:, 0])
                        ck = QuantizedKV(
                            ck.q.at[bi, cache_index].set(kq),
                            ck.scale.at[bi, cache_index].set(ks))
                        cv = QuantizedKV(
                            cv.q.at[bi, cache_index].set(vq),
                            cv.scale.at[bi, cache_index].set(vs))
                    else:
                        ck = ck.at[bi, cache_index].set(k[:, 0])
                        cv = cv.at[bi, cache_index].set(v[:, 0])
                    kv_idx = jnp.arange(ck.shape[1])
                    kv_mask = (kv_idx[None, :] <=
                               cache_index[:, None])[:, None, None, :]
                else:
                    # single shared index: insert current kv block
                    # (one-shot bucketed prefill — int8 caches never
                    # reach here: the engine requires chunked prefill
                    # for them at init)
                    ck = jax.lax.dynamic_update_slice_in_dim(
                        ck, k, cache_index, 1)
                    cv = jax.lax.dynamic_update_slice_in_dim(
                        cv, v, cache_index, 1)
                    # causal within the block AND limited to filled
                    # slots: query at absolute position cache_index+qi
                    # sees kv_idx <= it
                    q_pos = cache_index + jnp.arange(s)  # [s]
                    kv_idx = jnp.arange(ck.shape[1])
                    kv_mask = (kv_idx[None, :] <=
                               q_pos[:, None])[None, None, :, :]
                out = F.scaled_dot_product_attention(
                    q, dequantize_kv(ck), dequantize_kv(cv),
                    attn_mask=kv_mask, training=False
                )
                new_cache = (ck, cv)
        else:
            from ..distributed.sharding import current_mesh

            with jax.named_scope("attn_in"):
                q, k = apply_rope(q, k, cos, sin, position_ids)
            mesh = current_mesh()
            sep = mesh.shape.get("sep", 1) if mesh is not None else 1
            if sep > 1 and cfg.sep_attention == "ring":
                from ..kernels.ring_attention import ring_attention

                out = ring_attention(q, k, v, mesh=mesh, causal=True)
            elif sep > 1:
                from ..kernels.ulysses import ulysses_attention

                out = ulysses_attention(
                    q, k, v, causal=True, training=self.training,
                    use_flash=cfg.use_flash_attention,
                )
            elif cfg.use_flash_attention:
                out = fa.flash_attention(q, k, v, causal=True,
                                         training=self.training)
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, training=self.training
                )
            new_cache = None
        with jax.named_scope("attn_out"):
            out = out.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
            out = self.o_proj(out)
        return (out, new_cache) if kv_cache is not None else out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.gate_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, weight_attr=init,
            has_bias=False,
        )
        self.up_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, weight_attr=init,
            has_bias=False,
        )
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size, weight_attr=init,
            has_bias=False,
        )

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, config.rms_norm_eps
        )

    def forward(self, x, cos, sin, position_ids=None, kv_cache=None,
                cache_index=None):
        residual = x
        with jax.named_scope("attn_in"):
            h = self.input_layernorm(x)
        if kv_cache is not None:
            h, new_cache = self.self_attn(
                h, cos, sin, position_ids, kv_cache, cache_index
            )
        else:
            h = self.self_attn(h, cos, sin, position_ids)
            new_cache = None
        with jax.named_scope("attn_out"):
            x = residual + h
        with jax.named_scope("mlp"):
            residual = x
            h = self.post_attention_layernorm(x)
            h = self.mlp(h)
            x = residual + h
        return (x, new_cache) if kv_cache is not None else x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range),
        )
        from ..nn.layer.common import LayerList

        self.layers = LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = rope_frequencies(
            config.head_dim, config.max_position_embeddings, config.rope_theta
        )
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def forward(self, input_ids, position_ids=None, kv_caches=None,
                cache_index=None):
        cfg = self.config
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            h = shard_activation(h, ("dp", "fsdp"), "sep", None)
        cos = self._buffers["rope_cos"]
        sin = self._buffers["rope_sin"]
        new_caches = [] if kv_caches is not None else None
        for i, layer in enumerate(self.layers):
            if kv_caches is not None:
                h, nc = layer(h, cos, sin, position_ids, kv_caches[i],
                              cache_index)
                new_caches.append(nc)
            elif cfg.use_recompute and self.training:
                fn = partial(layer.__call__, cos=cos, sin=sin,
                             position_ids=position_ids)
                policy = getattr(
                    jax.checkpoint_policies, cfg.recompute_policy, None
                )
                h = jax.checkpoint(fn, policy=policy)(h)
            else:
                h = layer(h, cos, sin, position_ids)
        with jax.named_scope("head_loss"):
            h = self.norm(h)
        return (h, new_caches) if kv_caches is not None else h


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                weight_attr=I.Normal(0.0, config.initializer_range),
                has_bias=False,
            )

    def logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        w = self.model.embed_tokens.weight.value
        return shard_activation(
            hidden @ w.T, ("dp", "fsdp"), None, "tp"
        )

    def forward(self, input_ids, labels=None, position_ids=None,
                kv_caches=None, cache_index=None):
        if kv_caches is not None:
            hidden, new_caches = self.model(
                input_ids, position_ids, kv_caches, cache_index
            )
            return self.logits(hidden), new_caches
        hidden = self.model(input_ids, position_ids)
        if labels is None:
            return self.logits(hidden)
        with jax.named_scope("head_loss"):
            return self._lm_loss(hidden, labels)

    def _lm_loss(self, hidden, labels):
        # next-token LM loss over every position: the last one's target
        # is ignored, so neither the logits nor the hidden states are
        # sliced
        targets = F.shift_labels(labels, ignore_index=-100)
        if self.config.fused_head_loss_chunk:
            # chunked head+CE: math-identical to the full-logits path
            # (softmax is row-wise) but peak memory is one seq chunk
            from ..incubate.nn.functional import fused_linear_cross_entropy

            if self.lm_head is not None:
                return fused_linear_cross_entropy(
                    hidden, self.lm_head.weight.value, targets,
                    ignore_index=-100,
                    seq_chunk=self.config.fused_head_loss_chunk)
            return fused_linear_cross_entropy(
                hidden, self.model.embed_tokens.weight.value, targets,
                transpose_weight=True, ignore_index=-100,
                seq_chunk=self.config.fused_head_loss_chunk)
        # fp32 softmax over the (tp-sharded) vocab
        return F.cross_entropy(self.logits(hidden), targets, ignore_index=-100)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        cfg = self.config
        dtype = dtype or jnp.bfloat16
        if jnp.dtype(dtype) == jnp.int8:
            # quantized contiguous caches: int8 payload + per-row f32
            # dequant scales (see inference.paged.QuantizedKV). Zero
            # scales dequantize untouched rows to the same zeros a fp
            # cache starts with.
            from ..inference.paged import QuantizedKV

            def one():
                return QuantizedKV(
                    jnp.zeros((batch_size, max_len,
                               cfg.num_key_value_heads, cfg.head_dim),
                              jnp.int8),
                    jnp.zeros((batch_size, max_len,
                               cfg.num_key_value_heads), jnp.float32))
            return [(one(), one())
                    for _ in range(cfg.num_hidden_layers)]
        return [
            (
                jnp.zeros((batch_size, max_len, cfg.num_key_value_heads,
                           cfg.head_dim), dtype),
                jnp.zeros((batch_size, max_len, cfg.num_key_value_heads,
                           cfg.head_dim), dtype),
            )
            for _ in range(cfg.num_hidden_layers)
        ]


class LlamaPipeBlock(Layer):
    """Single-activation decoder layer for the SPMD pipeline trunk:
    recomputes the (tiny, XLA-constant-folded) rope tables internally so
    the pipelined inter-stage activation is just the hidden states —
    parity with fleet's LlamaForCausalLMPipe per-stage blocks, which
    likewise rebuild rotary tables per stage rather than shipping them."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.block = LlamaDecoderLayer(config)

    def forward(self, x):
        cfg = self.config
        cos, sin = rope_frequencies(
            cfg.head_dim, x.shape[1], cfg.rope_theta)
        return self.block(x, cos, sin)


def llama_pipeline_module(config: LlamaConfig, num_stages: int):
    """Build the flagship model as a PipelineModule (parity:
    PaddleNLP LlamaForCausalLMPipe): tied/untied embedding + L decoder
    blocks (the homogeneous trunk) + final norm + lm head. Drive with
    ``distributed.pipeline.PipelineTrainStep`` under a pp mesh; the loss
    head runs on the last stage inside the 1F1B schedule."""
    from ..distributed.pipeline import (
        LayerDesc,
        PipelineModule,
        SharedLayerDesc,
    )
    from ..nn.layer.norm import RMSNorm as _RMSNorm

    init = I.Normal(0.0, config.initializer_range)
    if config.tie_word_embeddings:
        embed = SharedLayerDesc(
            "embed", VocabParallelEmbedding, config.vocab_size,
            config.hidden_size, weight_attr=init)
        head = SharedLayerDesc(
            "embed", VocabParallelEmbedding, config.vocab_size,
            config.hidden_size, weight_attr=init,
            forward_func=lambda layer, x: x @ layer.weight.value.T)
    else:
        embed = LayerDesc(VocabParallelEmbedding, config.vocab_size,
                          config.hidden_size, weight_attr=init)
        head = LayerDesc(ColumnParallelLinear, config.hidden_size,
                         config.vocab_size, weight_attr=init,
                         has_bias=False)
    descs = (
        [embed]
        + [LayerDesc(LlamaPipeBlock, config)
           for _ in range(config.num_hidden_layers)]
        + [LayerDesc(_RMSNorm, config.hidden_size, config.rms_norm_eps),
           head]
    )
    return PipelineModule(descs, num_stages=num_stages)
