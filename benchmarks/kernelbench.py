"""Counting models of the serving path: HBM bytes and FLOPs computed
from shapes, on any backend. Nothing here times or prints.

``decode_hbm_bytes`` (fused against unfused decode attention, both
KV-cache modes), ``prefill_flops`` / ``prefill_admission_flops`` (legacy
bucketed against chunked prefill, with and without a cached prefix),
``llama7b_weight_stream_bytes`` / ``quant_decode_model`` (weight-only
quantised decode) and ``spec_decode_model`` (speculative decoding's
bytes a token). The tests of the serving suites hold their orderings;
ROADMAP.md D5 cites the fused paged arm. They wait for the serve cell's
rooflines (``chipbench/opsbytes/``) to take over what they need.
"""

from __future__ import annotations


def decode_hbm_bytes(mode, fused, seq_lens, kvh, group, d,
                     page_size=None, max_len=None, cache_bytes=2,
                     act_bytes=2, n_tokens=1, cache_scale_bytes=0):
    """Modeled per-layer HBM bytes for one decode step's attention
    stage (RoPE + KV-append + attention over the cached KV) — the
    denominator of the decode roofline and the fused-vs-unfused A/B.

    Counts data crossing HBM↔VMEM (pure python, runs anywhere):
      - both paths read q/k_new/v_new once and write the attention out;
      - UNFUSED writes the new token's K/V row to the cache; FUSED
        writes back the sublane tile that holds it
        (``paged_attention.append_tile_rows``: 16 rows of bf16, 32 of
        int8 — the chip's compiler refuses a one-row block), which in
        paged mode outweighs the RoPE round-trip it saves: there the
        fused kernel's case is one launch instead of three, not bytes;
      - cache streaming: paged reads ceil((len+1)/page)·page rows per
        slot (length-pruned, both paths); contiguous FUSED reads
        ceil((len+1)/chunk)·chunk rows, contiguous UNFUSED reads the
        dense slots × max_len view (masked SDPA has no length pruning);
      - UNFUSED additionally materializes rotated q/k to HBM (the RoPE
        pass writes them, the append/attention programs re-read them) —
        the two activation round-trips in-kernel RoPE removes.

    ``n_tokens`` widens the pass to a MULTI-token step per slot — the
    spec-decode verify program's ``[slots, K+1]`` shape: activations,
    appends and the rope rows scale by it, and the cache stream rounds
    ``len + n_tokens`` up to the streaming granularity. The per-layer
    WEIGHT stream (the number spec decode amortizes) is not counted
    here — attention-stage traffic only, same as the n_tokens=1 rows.

    ``cache_bytes`` is the KV byte width (2 bf16, 1 int8);
    ``cache_scale_bytes`` adds the int8 pools' per-row f32 dequant
    scales (4): one scale per cached row per head streams with the K
    and V payloads, and each appended row writes one.
    """
    from paddle_tpu.kernels.decode_attention import contiguous_chunk
    from paddle_tpu.kernels.paged_attention import append_tile_rows

    slots = len(seq_lens)
    q_elems = slots * n_tokens * kvh * group * d
    kv_new_elems = slots * n_tokens * kvh * d
    total = (q_elems + 2 * kv_new_elems) * act_bytes   # q, k_new, v_new
    total += q_elems * act_bytes                       # out write
    total += slots * n_tokens * d * 4                  # cos+sin rows
    if mode == "paged":
        gran = page_size
    elif mode == "contiguous":
        gran = contiguous_chunk(max_len) if fused else None
    else:
        raise ValueError(f"unknown cache mode {mode!r}")
    if gran is not None:
        rows = sum(-(-(int(n) + n_tokens) // gran) * gran
                   for n in seq_lens)
    else:
        rows = slots * max_len
    # rows written per appended row: the fused kernels (one token a
    # slot only) write a sublane tile, the XLA scatter one row
    append_rows = (append_tile_rows(gran, cache_bytes)
                   if fused and n_tokens == 1 else 1)
    total += 2 * kv_new_elems * append_rows * cache_bytes  # append
    total += 2 * rows * kvh * d * cache_bytes          # K+V stream
    if cache_scale_bytes:
        # int8 pools: per-row scales stream with the payload and are
        # written with each appended row (K and V each)
        total += 2 * rows * kvh * cache_scale_bytes
        total += (2 * kv_new_elems // d * append_rows
                  * cache_scale_bytes)
    if not fused:
        # rope materialization round-trip: write q_rot+k_rot, re-read
        total += 2 * (q_elems + kv_new_elems) * act_bytes
    return total


def prefill_flops(n_tokens, ctx_len, hidden, inter, n_layers, vocab):
    """Modeled MXU FLOPs for a prefill pass computing ``n_tokens``
    rows attending over ``ctx_len`` context (pure python, runs
    anywhere): per-layer qkvo + gated-MLP matmuls per row, QK^T + PV
    attention per row × context, plus the lm head. GQA's smaller kv
    projections and causal halving are ignored — the A/B compares
    admission SCHEMES, and both sides share the constants."""
    lin = 2 * (4 * hidden * hidden + 3 * hidden * inter) * n_tokens
    attn = 2 * 2 * n_tokens * ctx_len * hidden
    head = 2 * n_tokens * hidden * vocab
    return n_layers * (lin + attn) + head


def prefill_admission_flops(prompt_len, prefix_len, chunk, buckets,
                            hidden=4096, inter=11008, n_layers=32,
                            vocab=32000, max_len=None):
    """Modeled prefill cost of one request under the three admission
    schemes — the shared-prefix A/B:

      - ``legacy_flops``: per-bucket prefill pads the prompt up to its
        seq bucket (a 260-token prompt pays a 512-token forward); a
        prompt past the largest bucket pays ``max_len``, the engine's
        ``_bucket`` fallback. When ``max_len`` is omitted the model
        assumes the largest bucket IS max_len (the engine's normalized
        bucket table never exceeds it);
      - ``chunked_flops``: single-program chunked prefill computes the
        prompt rounded up to the chunk;
      - ``chunked_prefix_flops``: prefix-cache hit computes only the
        SUFFIX rounded up to the chunk — cost ∝ suffix length, not
        bucket or prompt length.

    This is the MARGINAL cost of the request's own rows — what an
    admission wave pays per request when its chunks pack with other
    requests'. A lone request in the fixed ``[slots, chunk]`` program
    additionally pays the idle slots' sentinel rows (same trade as the
    engine's fixed-shape decode program), which packing amortizes away.
    """
    import bisect

    bs = sorted(buckets)
    i = bisect.bisect_left(bs, prompt_len)
    bucket = bs[i] if i < len(bs) else (max_len or bs[-1])
    dims = (hidden, inter, n_layers, vocab)
    suffix = max(prompt_len - prefix_len, 1)
    rows_full = -(-prompt_len // chunk) * chunk
    rows_suffix = -(-suffix // chunk) * chunk
    return {
        "prompt_len": prompt_len,
        "prefix_len": prefix_len,
        "bucket": bucket,
        "chunk": chunk,
        "legacy_flops": prefill_flops(bucket, bucket, *dims),
        "chunked_flops": prefill_flops(rows_full, prompt_len, *dims),
        "chunked_prefix_flops": prefill_flops(rows_suffix, prompt_len,
                                              *dims),
    }


def llama7b_weight_stream_bytes(weight_dtype="int8", group_size=128,
                                kvh=8, d=128, hidden=4096, inter=11008,
                                n_layers=32, vocab=32000):
    """Modeled HBM bytes of ONE full weight stream at the serve7b
    shape — the quantity EVERY decode pass re-reads, and what
    weight-only quantization shrinks. Linears (qkvo with GQA-sized kv,
    gated MLP, lm head) carry the chosen byte width plus group-wise
    f32 scales (params/group_size × 4, int8/int4). The embedding is
    NOT in the stream — decode reads one table row per token, not the
    table (it is reported separately for residency accounting). Pure
    python — runs anywhere."""
    linear = n_layers * (2 * hidden * hidden + 2 * hidden * kvh * d
                         + 3 * hidden * inter) + hidden * vocab
    dense = hidden * vocab  # embedding (HBM residency, not stream)
    widths = {"bf16": 2.0, "bfloat16": 2.0, "int8": 1.0, "int4": 0.5}
    if weight_dtype not in widths:
        raise ValueError(f"unknown weight_dtype {weight_dtype!r}")
    payload = linear * widths[weight_dtype]
    scales = (0 if weight_dtype in ("bf16", "bfloat16")
              else linear // group_size * 4)
    return {
        "weight_dtype": weight_dtype,
        "group_size": group_size,
        "linear_params": int(linear),
        "embed_params": int(dense),
        "stream_bytes": int(payload + scales),
        "scale_bytes": int(scales),
    }


def quant_decode_model(weight_dtype="int8", kv_dtype="bf16",
                       accept_rate=0.0, k=4, kvh=8, heads=32, d=128,
                       n_layers=32, group_size=128, seq_len=512,
                       slots=8, page_size=64):
    """THE compound quantized-serving model: bytes/token for a
    (weight dtype × KV dtype × spec-decode acceptance) serving config
    vs the bf16-weights / bf16-KV / no-spec baseline — pure python,
    runs on any backend. Weight and KV byte-widths multiply with spec
    decode's tokens-per-weight-stream, which is why int8-W alone
    models ~1.9× and int8-W × int8-KV × acceptance 0.6 models ~4.6×
    over plain bf16 decode."""
    group = heads // kvh
    lens = [seq_len] * slots
    kv_bytes = {"bf16": 2, "bfloat16": 2, "fp16": 2, "int8": 1,
                "fp32": 4, "float32": 4}[kv_dtype]
    scale_b = 4 if kv_dtype == "int8" else 0
    base_w = llama7b_weight_stream_bytes(
        "bf16", group_size, kvh=kvh, d=d, n_layers=n_layers)
    quant_w = llama7b_weight_stream_bytes(
        weight_dtype, group_size, kvh=kvh, d=d, n_layers=n_layers)
    attn_base = n_layers * decode_hbm_bytes(
        "paged", True, lens, kvh, group, d, page_size=page_size,
        cache_bytes=2)
    n_tok = (k + 1) if accept_rate > 0 else 1
    attn = n_layers * decode_hbm_bytes(
        "paged", True, lens, kvh, group, d, page_size=page_size,
        cache_bytes=kv_bytes, cache_scale_bytes=scale_b,
        n_tokens=n_tok)
    exp_tokens = (1.0 + sum(accept_rate ** j for j in range(1, k + 1))
                  if accept_rate > 0 else 1.0)
    base_bpt = (base_w["stream_bytes"] + attn_base) / slots
    bpt = (quant_w["stream_bytes"] + attn) / slots / exp_tokens
    return {
        "weight_dtype": weight_dtype,
        "kv_dtype": kv_dtype,
        "accept_rate": accept_rate,
        "k": k,
        "kvh": kvh,
        "group_size": group_size,
        "seq_len": seq_len,
        "slots": slots,
        "weight_stream_bytes": quant_w["stream_bytes"],
        "attn_bytes_per_pass": int(attn),
        "tokens_per_weight_stream": round(exp_tokens, 3),
        "bytes_per_token": int(bpt),
        "baseline_bf16_bytes_per_token": int(base_bpt),
        "modeled_speedup": round(base_bpt / bpt, 3),
    }


def spec_decode_model(accept_rate, k, kvh, heads=32, d=128, n_layers=32,
                      weight_bytes=None, seq_len=512, slots=8,
                      page_size=64, cache_bytes=2, weight_byte_width=1,
                      cache_scale_bytes=0):
    """Modeled tokens-per-weight-stream A/B: plain decode vs
    speculative decoding at a given per-draft acceptance rate (pure
    python, runs anywhere).

    Decode throughput is pinned by the per-pass HBM stream: every
    forward pass re-reads ALL model weights plus the attention-stage
    traffic. Plain decode buys 1 token per pass. A verify pass over K
    drafts buys ``1 + Σ_{j=1..K} a^j`` expected tokens (greedy
    acceptance is a PREFIX rule — draft j only counts if every earlier
    draft matched, so independent per-draft acceptance ``a`` compounds
    geometrically) while paying the same weight stream once and a
    modestly wider attention stage (``decode_hbm_bytes`` at
    ``n_tokens = K+1``). The n-gram drafter itself is host-side — zero
    device bytes. ``modeled_speedup`` is the bytes-per-token ratio;
    GQA (kvh) moves it by shrinking the attention share of the stream.
    """
    group = heads // kvh
    lens = [seq_len] * slots
    if weight_bytes is None:
        # serve7b-class weight-only stream: qkvo (GQA-sized kv)
        # + gated MLP per layer + the lm head, ``weight_byte_width``
        # bytes/param (1 = int8, the historical default; 2 = bf16,
        # 0.5 = packed int4)
        hidden, inter, vocab = 4096, 11008, 32000
        weight_bytes = (n_layers * (
            2 * hidden * hidden + 2 * hidden * kvh * d
            + 3 * hidden * inter) + hidden * vocab) * weight_byte_width
    kw = dict(page_size=page_size, cache_bytes=cache_bytes,
              cache_scale_bytes=cache_scale_bytes)
    attn_plain = n_layers * decode_hbm_bytes(
        "paged", True, lens, kvh, group, d, **kw)
    attn_verify = n_layers * decode_hbm_bytes(
        "paged", True, lens, kvh, group, d, n_tokens=k + 1, **kw)
    exp_tokens = 1.0 + sum(accept_rate ** j for j in range(1, k + 1))
    plain_bytes_per_tok = (weight_bytes + attn_plain) / slots
    spec_bytes_per_tok = (weight_bytes + attn_verify) / slots \
        / exp_tokens
    return {
        "accept_rate": accept_rate,
        "k": k,
        "kvh": kvh,
        "seq_len": seq_len,
        "slots": slots,
        "tokens_per_weight_stream": round(exp_tokens, 3),
        "weight_bytes": int(weight_bytes),
        "attn_bytes_plain": int(attn_plain),
        "attn_bytes_verify": int(attn_verify),
        "plain_bytes_per_token": int(plain_bytes_per_tok),
        "spec_bytes_per_token": int(spec_bytes_per_tok),
        "modeled_speedup": round(
            plain_bytes_per_tok / spec_bytes_per_tok, 3),
    }
