"""Kernel-level roofline micro-benches: Pallas flash attention (FLOP
roofline), fused GroupNorm+SiLU (HBM-bytes roofline) and fused decode
attention (HBM-bytes roofline, fused-vs-unfused A/B for both KV-cache
modes).

Flash: forward and forward+backward device time at the headline bench
shape, each against the chip's FLOP peak (target: "bwd kernel >= 45% of
roofline or a documented analysis").

FLOP accounting (causal): softmax(QK^T)V does 2 matmuls of
2*b*h*sq*sk*d FLOPs each, halved by causal masking. Backward does 5
tile-matmuls in the fused kernel (dv, dp, ds->dq, ds->dk, s recompute)
-> bwd FLOPs = 2.5x fwd. Elementwise VPU work is excluded from the
denominator, so the ratio is a true MXU roofline (VPU-bound kernels
show up as a low ratio, which is the point).

GroupNorm: bandwidth-bound (O(1) FLOPs/byte), so its roofline is HBM
bytes over peak bandwidth — fwd moves 2 activation passes (1 read + 1
write), fwd+bwd 5. Each SD-UNet-representative NHWC shape reports the
fused kernel's achieved fraction of that floor, plus the unfused
XLA-native NCHW GroupNorm at the same shape as the A/B (what the fusion
+ layout policy actually buys).

Usage: python benchmarks/kernelbench.py  (needs the real TPU; prints
one JSON line per shape).
"""

from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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


def prefill_cost_ab():
    """Print the modeled prefill-admission A/B at serve7b-class shapes
    (pure cost model — runs on any backend): one JSON line per
    (prompt_len, prefix_len) point, mirroring the groupnorm/decode
    rows' format."""
    points = [
        # (prompt_len, prefix_len): cold, warm system prompt, few-shot
        (260, 0), (260, 256), (1500, 0), (1500, 1280), (700, 512),
    ]
    for prompt_len, prefix_len in points:
        row = prefill_admission_flops(
            prompt_len, prefix_len, chunk=256,
            buckets=(128, 256, 512, 1024, 2048))
        row["kernel"] = "prefill_admission_model"
        print(json.dumps(row), flush=True)


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


def quant_cost_ab():
    """Print the modeled quantized-serving rows (pure cost models —
    runs on ANY backend, ahead of the TPU guard): the weight-only
    stream micro A/B at int8/int4 × group 64/128, and the compound
    decode model (weight dtype × KV dtype × spec acceptance) whose
    int8-W and int8-W×0.6-acceptance rows are the driver-ledger
    prediction for the next TPU window."""
    for wd in ("int8", "int4"):
        for g in (64, 128):
            row = llama7b_weight_stream_bytes(wd, group_size=g)
            row["kernel"] = "weight_only_stream_model"
            row["vs_bf16_x"] = round(
                llama7b_weight_stream_bytes("bf16")["stream_bytes"]
                / row["stream_bytes"], 3)
            print(json.dumps(row), flush=True)
    for wd, kv, a in (("int8", "bf16", 0.0), ("int4", "bf16", 0.0),
                      ("int8", "int8", 0.0), ("int8", "int8", 0.6),
                      ("int4", "int8", 0.6)):
        row = quant_decode_model(wd, kv, accept_rate=a)
        row["kernel"] = "quant_decode_model"
        print(json.dumps(row), flush=True)


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


def spec_decode_cost_ab():
    """Print the modeled spec-decode A/B at the serve7b decode shape
    (pure cost model — runs on any backend): one JSON line per
    (acceptance rate, GQA ratio) point, mirroring the prefill/decode
    rows' format. 0.3 ~ adversarial traffic, 0.6 ~ mixed, 0.9 ~
    repetitive (code/JSON/templated) — the regime the n-gram drafter
    targets."""
    for kvh in (1, 4, 8):
        for a in (0.3, 0.6, 0.9):
            row = spec_decode_model(a, k=4, kvh=kvh)
            row["kernel"] = "spec_decode_model"
            print(json.dumps(row), flush=True)


def decode_bench():
    """Fused single-pass decode attention vs the unfused reference
    (rope → append → attention), both cache modes, at the serve7b-class
    decode shape across GQA ratios — prints one JSON line per config
    with measured ms, modeled HBM bytes and the achieved fraction of
    the HBM roofline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.devtime import peak_hbm_bandwidth, traced_step_ms
    from paddle_tpu.inference.paged import (
        PagedLayerCache,
        PagedState,
        append_kv,
        paged_attention,
    )
    from paddle_tpu.kernels import decode_attention as da
    from paddle_tpu.kernels.paged_attention import (
        fused_paged_decode_attention,
    )
    from paddle_tpu.kernels.rope import rope_frequencies

    bw = peak_hbm_bandwidth(jax.devices()[0])
    slots, heads, d = 8, 32, 128
    page_size, max_len = 64, 1024
    cdt = jnp.bfloat16
    rng = np.random.default_rng(0)
    lens = np.array([937, 512, 768, 120, 240, 64, 1000, 333], np.int32)
    cos, sin = rope_frequencies(d, max_len + 1)

    for kvh in (1, 4, 8):
        group = heads // kvh
        q = jnp.asarray(
            rng.standard_normal((slots, kvh, group, d)), jnp.bfloat16)
        kn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.bfloat16)
        vn = jnp.asarray(rng.standard_normal((slots, kvh, d)), jnp.bfloat16)
        lens_j = jnp.asarray(lens)

        def measure(label, f, k0, v0, bytes_):
            # one measured A/B row: time f while threading the donated
            # cache buffers through, emit the JSON line, hand the live
            # buffers back for the next variant
            buf = {"k": k0, "v": v0}

            def step():
                out, k2, v2 = f(q, kn, vn, buf["k"], buf["v"])
                buf["k"], buf["v"] = k2, v2
                return out

            jax.device_get(step())
            t = traced_step_ms(step, n_steps=20)
            ms = t.device_step_ms or t.step_ms
            print(json.dumps({
                "kernel": label,
                "shape": f"s{slots}xh{heads}xkvh{kvh}xd{d}",
                "ms": round(ms, 4),
                "modeled_hbm_bytes": bytes_,
                "hbm_roofline": round((bytes_ / (ms / 1e3)) / bw, 3),
                "peak_hbm_gbps": round(bw / 1e9, 1),
            }), flush=True)
            return buf["k"], buf["v"]

        # ---- paged ----
        n_pages = slots * (max_len // page_size) + 1
        kp = jnp.asarray(
            rng.standard_normal((kvh, n_pages, page_size, d)), cdt)
        vp = jnp.asarray(
            rng.standard_normal((kvh, n_pages, page_size, d)), cdt)
        bt = jnp.asarray(
            1 + np.arange(slots * (max_len // page_size)).reshape(
                slots, -1), jnp.int32)

        # caches are DONATED (as the engine's decode does): without
        # donation the aliased in-place append degrades to a full-pool
        # copy per step, which would swamp the traffic being measured
        fused_p = jax.jit(lambda q, kn, vn, kp, vp: (
            fused_paged_decode_attention(
                q, kn, vn, kp, vp, bt, lens_j, lens_j, cos, sin)),
            donate_argnums=(3, 4))

        def unfused_p(q, kn, vn, kp, vp):
            qr, kr = _rope_one(q, kn, lens_j, cos, sin)
            cache = PagedLayerCache(kp, vp)
            state = PagedState(bt, lens_j)
            cache = append_kv(cache, state, kr[:, None], vn[:, None])
            out = paged_attention(
                qr.reshape(slots, 1, heads, d), cache, state)
            return out, cache.k_pages, cache.v_pages
        unfused_p = jax.jit(unfused_p, donate_argnums=(3, 4))

        for name, f, fused in (("fused", fused_p, True),
                               ("unfused", unfused_p, False)):
            kp, vp = measure(
                f"decode_attn_paged_{name}", f, kp, vp,
                decode_hbm_bytes("paged", fused, lens, kvh, group, d,
                                 page_size=page_size, cache_bytes=2,
                                 act_bytes=2))

        # ---- contiguous ----
        ck = jnp.asarray(
            rng.standard_normal((slots, max_len, kvh, d)), cdt)
        cv = jnp.asarray(
            rng.standard_normal((slots, max_len, kvh, d)), cdt)
        fused_c = jax.jit(lambda q, kn, vn, ck, cv: (
            da.fused_contiguous_decode_attention(
                q, kn, vn, ck, cv, lens_j, lens_j, cos, sin)),
            donate_argnums=(3, 4))

        def unfused_c(q, kn, vn, ck, cv):
            # the PRE-FUSION engine path (models/llama.py per-slot
            # branch), not the f32 repeat-materializing parity oracle:
            # rope → row scatter → masked SDPA over the kvh-head cache —
            # the traffic decode_hbm_bytes prices for the unfused side
            from paddle_tpu.nn import functional as F

            qr, kr = _rope_one(q, kn, lens_j, cos, sin)
            ck = ck.at[jnp.arange(slots), lens_j].set(
                kr.astype(ck.dtype))
            cv = cv.at[jnp.arange(slots), lens_j].set(
                vn.astype(cv.dtype))
            mask = (jnp.arange(max_len)[None, :] <=
                    lens_j[:, None])[:, None, None, :]
            out = F.scaled_dot_product_attention(
                qr.reshape(slots, 1, heads, d), ck, cv,
                attn_mask=mask, training=False)
            return out, ck, cv
        unfused_c = jax.jit(unfused_c, donate_argnums=(3, 4))
        for name, f, fused in (("fused", fused_c, True),
                               ("unfused", unfused_c, False)):
            ck, cv = measure(
                f"decode_attn_contig_{name}", f, ck, cv,
                decode_hbm_bytes("contiguous", fused, lens, kvh, group,
                                 d, max_len=max_len, cache_bytes=2,
                                 act_bytes=2))


def _rope_one(q, k_new, positions, cos, sin):
    """Unfused-path rope for the A/B: one token per slot, via the same
    helper the parity oracle uses (kernels/decode_attention)."""
    from paddle_tpu.kernels.decode_attention import _rope_rotate

    slots, kvh, group, d = q.shape
    return (_rope_rotate(q.reshape(slots, kvh * group, d), positions,
                         cos, sin),
            _rope_rotate(k_new, positions, cos, sin))


def main():
    # the modeled prefill + spec-decode + quantized-serving A/Bs are
    # pure Python — emit them on ANY backend, before the kernel
    # timings, which need the chip (they are the only output a CPU
    # host gets from this CLI, which then exits non-zero)
    prefill_cost_ab()
    spec_decode_cost_ab()
    quant_cost_ab()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.devtime import peak_flops, traced_step_ms
    from paddle_tpu.kernels.flash_attention import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"kernelbench: kernel timings need a TPU; JAX found "
            f"{dev.platform!r}")
    peak = peak_flops(dev)

    # headline bench shape + a long-seq point
    shapes = [
        # (batch, seq, heads, head_dim)
        (4, 2048, 24, 128),
        (1, 8192, 24, 128),
    ]
    rng = np.random.default_rng(0)
    for (b, s, h, d) in shapes:
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)

        fwd = jax.jit(functools.partial(flash_attention, causal=True))

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        # warmup/compile
        jax.device_get(fwd(q, k, v))
        jax.device_get(jax.tree_util.tree_leaves(bwd(q, k, v))[0][0, 0])

        t_fwd = traced_step_ms(lambda: fwd(q, k, v), n_steps=10)
        t_bwd = traced_step_ms(lambda: bwd(q, k, v), n_steps=10)

        fwd_flops = 2 * 2 * b * h * s * s * d * 0.5  # causal
        # fused bwd: 5 tile matmuls vs fwd's 2 (incl. s recompute)
        bwd_flops = fwd_flops * 2.5
        fwd_ms = t_fwd.device_step_ms or t_fwd.step_ms
        tot_ms = t_bwd.device_step_ms or t_bwd.step_ms
        # grad-of-sum runs fwd (for residuals) + bwd kernels
        bwd_ms = max(tot_ms - fwd_ms, 1e-6)
        out = {
            "kernel": "flash_attention",
            "shape": f"b{b}xs{s}xh{h}xd{d}",
            "fwd_ms": round(fwd_ms, 3),
            "fwd_bwd_ms": round(tot_ms, 3),
            "bwd_ms_est": round(bwd_ms, 3),
            "fwd_roofline": round(fwd_flops / (fwd_ms / 1e3) / peak, 3),
            "bwd_roofline": round(bwd_flops / (bwd_ms / 1e3) / peak, 3),
            "peak_flops": peak,
        }
        print(json.dumps(out), flush=True)

    groupnorm_bench()
    decode_bench()


def groupnorm_bench():
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.devtime import peak_hbm_bandwidth, traced_step_ms
    from paddle_tpu.kernels import group_norm as gn
    from paddle_tpu.nn import functional as F

    bw = peak_hbm_bandwidth(jax.devices()[0])
    eps = 1e-5
    # SD-UNet block shapes at the bench config (b4, sample 32): the
    # widest level-0 tensor and a deep narrow one
    shapes = [
        # (batch, h, w, channels, groups)
        (4, 32, 32, 320, 32),
        (4, 8, 8, 1280, 32),
    ]
    rng = np.random.default_rng(0)
    for (b, h, w, c, g) in shapes:
        x = jnp.asarray(rng.standard_normal((b, h, w, c)), jnp.bfloat16)
        gamma = jnp.asarray(rng.standard_normal(c), jnp.float32)
        beta = jnp.asarray(rng.standard_normal(c), jnp.float32)
        x_nchw = jnp.transpose(x, (0, 3, 1, 2))

        fused = jax.jit(functools.partial(
            gn.fused_group_norm, num_groups=g, epsilon=eps,
            activation="silu"))

        def fused_loss(x, ga, be):
            return gn.fused_group_norm(
                x, ga, be, g, eps, "silu").astype(jnp.float32).sum()

        def unfused_loss(x, ga, be):
            y = F.group_norm(x, g, ga, be, eps, "NCHW")
            return F.silu(y).astype(jnp.float32).sum()

        fused_bwd = jax.jit(jax.grad(fused_loss, argnums=(0, 1, 2)))
        unfused = jax.jit(
            lambda x, ga, be: F.silu(F.group_norm(x, g, ga, be, eps,
                                                  "NCHW")))
        unfused_bwd = jax.jit(jax.grad(unfused_loss, argnums=(0, 1, 2)))

        for f, args in ((fused, (x, gamma, beta)),
                        (fused_bwd, (x, gamma, beta)),
                        (unfused, (x_nchw, gamma, beta)),
                        (unfused_bwd, (x_nchw, gamma, beta))):
            jax.device_get(jax.tree_util.tree_leaves(f(*args))[0])

        t_f = traced_step_ms(lambda: fused(x, gamma, beta), n_steps=20)
        t_fb = traced_step_ms(lambda: fused_bwd(x, gamma, beta),
                              n_steps=20)
        t_u = traced_step_ms(lambda: unfused(x_nchw, gamma, beta),
                             n_steps=20)
        t_ub = traced_step_ms(lambda: unfused_bwd(x_nchw, gamma, beta),
                              n_steps=20)

        elems = b * h * w * c
        bpe = x.dtype.itemsize
        fwd_bytes = 2 * elems * bpe           # 1 read + 1 write
        fwd_bwd_bytes = 5 * elems * bpe       # + bwd: 2 reads + 1 write
        fwd_ms = t_f.device_step_ms or t_f.step_ms
        tot_ms = t_fb.device_step_ms or t_fb.step_ms
        out = {
            "kernel": "group_norm_silu",
            "shape": f"b{b}x{h}x{w}xc{c}g{g}",
            "fwd_ms": round(fwd_ms, 4),
            "fwd_bwd_ms": round(tot_ms, 4),
            "fwd_hbm_roofline": round(
                (fwd_bytes / (fwd_ms / 1e3)) / bw, 3),
            "fwd_bwd_hbm_roofline": round(
                (fwd_bwd_bytes / (tot_ms / 1e3)) / bw, 3),
            "unfused_nchw_fwd_ms": round(
                t_u.device_step_ms or t_u.step_ms, 4),
            "unfused_nchw_fwd_bwd_ms": round(
                t_ub.device_step_ms or t_ub.step_ms, 4),
            "peak_hbm_gbps": round(bw / 1e9, 1),
        }
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
