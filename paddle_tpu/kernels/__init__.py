"""Pallas TPU kernels and their XLA reference fallbacks.

Parity target: paddle/phi/kernels/fusion/ (flash_attn, fused_rope,
rms_norm, fused_groupnorm, masked_multihead_attention, moe dispatch) —
here implemented as Pallas kernels where XLA fusion is insufficient,
with pure-XLA fallbacks that are numerically the source of truth.

Modules: flash_attention (fwd + fused 1-pass bwd), pallas_attention,
ring_attention, paged_attention (block-table decode + fused
single-pass decode: in-kernel RoPE + KV-append + attention),
decode_attention (the contiguous-cache fused variant + dispatch gate +
lax references), group_norm (fused NHWC GroupNorm+SiLU, custom VJP),
selective_scan, ssd, grouped_experts (gated experts over a sorted row
buffer), quant_matmul, rope, ulysses.
"""
