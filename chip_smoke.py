#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: kernels, train, serve
    python chip_smoke.py --chips 4   one four-chip host: the ZeRO-3 step only

Drives the two main paths once, through the entry points a user calls,
at llama2-7B widths with depth cut to fit one 16 GB chip and random
weights from a seed:

* kernels — the flash kernel and the fused paged decode kernel against
  their lax references on a small input;
* train — ``LlamaForCausalLM`` → ``dist.build_mesh`` →
  ``TrainStep(...).run(batch)`` (README Quickstart), a few steps on one
  fixed batch: loss finite and falling;
* serve — ``ContinuousBatchingEngine`` behind ``start_api_server`` on
  loopback, streamed ``POST /v1/completions``; greedy ids over HTTP
  equal the library path's (``engine.run``) for the same prompt.

Train and serve each read their own compiled program back and fail if it
holds no ``tpu_custom_call`` (the Pallas kernel). No failure is caught:
an exception ends the run non-zero. This one process is the only one
that touches JAX. Every time printed is an observation of this run, not
a benchmark.

The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before it builds anything.
``--rehearse-cpu`` runs the same control flow at a toy size on the CPU
(Pallas in interpret mode) to find wrong paths before chip time is
spent; it names ``cpu`` and never prints that line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import os
import sys
import time
import urllib.request

FULL_DEPTH = 32  # llama2-7B


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run builds. The depth cuts come from
    ``memory_analysis()`` of the same programs compiled for a described
    v5e chip (16 GB), see CHANGES.md PR 23:
    train 3 layers x batch 2 = 11.3 GB state + 1.9 GB temp (4 layers
    only fit batch 1); serve 16 layers = 6.5 GB weights + 2.0 GB KV pool
    + 4.1 GB prefill temp; the four-chip comparison needs batch 4 on the
    one-device reference, which 2 layers fit (8.7 + 3.3 GB)."""

    widths: dict
    train_layers: int = 3
    train_batch: int = 2
    sharded_layers: int = 2
    sharded_batch: int = 4
    serve_layers: int = 16
    seq: int = 2048
    train_steps: int = 5
    slots: int = 8
    max_len: int = 1024
    page_size: int = 64
    n_requests: int = 6
    prompt_len: int = 120
    new_tokens: int = 32


REAL = Sizes(widths={})  # LlamaConfig.llama2_7b as published
REHEARSAL = Sizes(
    widths=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=4,
                max_position_embeddings=256),
    train_layers=2, sharded_layers=2, serve_layers=2, seq=128,
    train_steps=3, slots=4, max_len=128, page_size=16, n_requests=4,
    prompt_len=40, new_tokens=8)

# one-device vs four-device loss, relative: bf16 compute, and the batch
# mean is reduced in another order across shards
SHARDED_LOSS_RTOL = 5e-3


def say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def build_model(sizes: Sizes, layers: int, seed: int):
    """llama2-7B widths, ``layers`` deep, bf16, random from ``seed``.
    Built abstract and materialised in bf16: initialising in fp32 and
    casting afterwards would hold 4 bytes a parameter (14 GB at 16
    layers) on a 16 GB chip."""
    import paddle_tpu as pt
    from paddle_tpu.core import meta
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = dataclasses.replace(
        LlamaConfig.llama2_7b(dtype="bfloat16", use_flash_attention=True),
        num_hidden_layers=layers, **sizes.widths)
    with meta.meta_init():
        model = LlamaForCausalLM(cfg)
    model.to(pt.bfloat16)
    meta.materialize(model, seed=seed)
    say(f"model: hidden {cfg.hidden_size}, mlp {cfg.intermediate_size}, "
        f"{cfg.num_attention_heads} heads of {cfg.head_dim}, vocab "
        f"{cfg.vocab_size}, bf16; num_hidden_layers: {layers} of "
        f"{FULL_DEPTH}")
    return model


def print_memory(devices, label: str) -> list:
    """Each device's ``memory_stats()``; returns their bytes_in_use
    (None where the backend reports none, as the CPU does)."""
    in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        in_use.append(stats.get("bytes_in_use"))
        say(f"memory {label} device {d.id}: bytes_in_use={in_use[-1]} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return in_use


def require_kernel(text: str, program: str, rehearsal: bool) -> int:
    n = text.count("tpu_custom_call")
    say(f"{program}: {n} tpu_custom_call in the compiled program")
    if not rehearsal and n == 0:
        raise RuntimeError(
            f"no Pallas kernel (tpu_custom_call) in the compiled {program}")
    return n


# ---------------------------------------------------------------- kernels
def kernels_phase():
    """The two Pallas kernels this smoke depends on, compiled and run
    against their lax references on a small input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import decode_attention as da
    from paddle_tpu.kernels.flash_attention import _reference_attention
    from paddle_tpu.kernels.paged_attention import (
        fused_paged_decode_attention,
    )
    from paddle_tpu.kernels.pallas_attention import mha
    from paddle_tpu.kernels.rope import rope_frequencies

    bf16 = jnp.bfloat16
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf16)

    q, k, v = rand(1, 512, 4, 128), rand(1, 512, 4, 128), rand(1, 512, 4, 128)
    out = jax.jit(lambda *a: mha(*a, causal=True))(q, k, v)
    ref = _reference_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    say(f"kernels: flash fwd vs lax reference, max abs err {err:.4f}")
    if not err < 5e-2:
        raise RuntimeError(f"flash kernel disagrees with reference: {err}")

    # fused paged decode: ragged lengths, one slot at a page boundary
    slots, kvh, group, d, page, max_pages = 4, 4, 2, 128, 64, 4
    n_pages = slots * max_pages + 1
    lens = jnp.asarray([0, 63, 64, 200], jnp.int32)
    bt = jnp.asarray(
        1 + np.arange(slots * max_pages).reshape(slots, max_pages),
        jnp.int32)
    kp, vp = rand(kvh, n_pages, page, d), rand(kvh, n_pages, page, d)
    cos, sin = rope_frequencies(d, 1024)
    args = (rand(slots, kvh, group, d), rand(slots, kvh, d),
            rand(slots, kvh, d), kp, vp, bt, lens, lens, cos, sin)
    out, kp2, vp2 = jax.jit(fused_paged_decode_attention)(*args)
    ref, kpr, vpr = jax.jit(da.fused_paged_decode_reference)(*args)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    # the appended rows, then every other row of the pool: the tile the
    # kernel writes back must leave its neighbours bit-identical
    lens_np = np.asarray(lens)
    rows = (np.arange(kvh)[None, :],
            np.asarray(bt)[np.arange(slots), lens_np // page][:, None],
            (lens_np % page)[:, None])
    row_err = 0.0
    for new, old, want in ((kp2, kp, kpr), (vp2, vp, vpr)):
        new, old, want = (np.array(x.astype(jnp.float32))
                          for x in (new, old, want))
        row_err = max(row_err, float(np.abs(new[rows] - want[rows]).max()))
        new[rows] = old[rows]
        if not np.array_equal(new, old):
            raise RuntimeError(
                "fused decode append changed rows it does not own")
    say(f"kernels: fused paged decode vs lax reference, max abs err "
        f"{err:.4f}; appended rows {row_err:.4f}; other rows bit-identical")
    if not (err < 5e-2 and row_err < 5e-2):
        raise RuntimeError("fused decode kernel disagrees with reference")


# ------------------------------------------------------------------ train
def train_phase(devices, sizes: Sizes, layers: int, batch_size: int,
                rehearsal: bool):
    """README Quickstart on ``devices``: one device is the plain step,
    several are the ZeRO-3 step (``sharding_degree=n``, ``fsdp=n``).
    Returns the per-step losses and each device's bytes in use while
    the step's state is live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.distributed.strategy import (
        DistributedStrategy,
        HybridConfig,
    )
    from paddle_tpu.trainer import TrainStep

    n = len(devices)
    model = build_model(sizes, layers, seed=0)
    vocab = model.config.vocab_size
    strategy = DistributedStrategy()
    if n > 1:
        strategy.hybrid_configs = HybridConfig(sharding_degree=n)
        strategy.sharding = True
        strategy.sharding_configs.stage = 3  # ZeRO-3
        mesh = dist.build_mesh(fsdp=n, devices=devices)
    else:
        mesh = dist.build_mesh(devices=devices)
    optimizer = opt.AdamW(learning_rate=1e-4, multi_precision=True,
                          grad_clip=opt.ClipGradByGlobalNorm(1.0))
    ts = TrainStep(model, optimizer, mesh, strategy)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, vocab, (batch_size, sizes.seq)), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}

    losses = []
    for step in range(sizes.train_steps):
        t0 = time.perf_counter()
        loss = ts.run(batch)
        loss.block_until_ready()
        ms = (time.perf_counter() - t0) * 1e3
        losses.append(float(loss))
        say(f"train[{n} dev] step {step + 1}: loss {losses[-1]:.4f}  "
            f"wall {ms:.0f} ms" + ("  (includes compile)" if not step else ""))
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    require_kernel(ts.lower(batch).compile().as_text(),
                   f"train step [{n} dev]", rehearsal)
    return losses, print_memory(devices, f"after train[{n} dev]")


# ------------------------------------------------------------------ serve
def _stream_completion(url: str, prompt, max_tokens: int) -> dict:
    """One streamed POST /v1/completions; returns status, ids, TTFT."""
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": max_tokens, "stream": True}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    ids, ttft, finish = [], None, None
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as resp:
        status = resp.status
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            event = json.loads(line[len("data: "):])
            if "error" in event:
                raise RuntimeError(f"server error: {event['error']}")
            choice = event["choices"][0]
            if choice["token_ids"] and ttft is None:
                ttft = time.perf_counter() - t0
            ids += choice["token_ids"]
            finish = choice["finish_reason"] or finish
    return {"status": status, "ids": ids, "ttft_s": ttft,
            "finish": finish, "wall_s": time.perf_counter() - t0}


def serve_phase(devices, sizes: Sizes, rehearsal: bool):
    import numpy as np

    from paddle_tpu.analysis import program_audit
    from paddle_tpu.inference import ContinuousBatchingEngine, EngineConfig
    from paddle_tpu.kernels import decode_attention as da
    from paddle_tpu.serving_api import start_api_server

    model = build_model(sizes, sizes.serve_layers, seed=1)
    mcfg = model.config
    engine = ContinuousBatchingEngine(model, EngineConfig(
        paged=True, page_size=sizes.page_size, max_slots=sizes.slots,
        max_len=sizes.max_len))
    pool_dtype = engine.layer_caches[0].k_pages.dtype
    if da.fused_decode_active(mcfg.head_dim, sizes.page_size, pool_dtype):
        path = "fused_paged_decode_attention (RoPE + append + attention)"
    else:
        path = "append_kv + paged_decode_attention / lax reference"
    say(f"serve: KV pool {pool_dtype}, page {sizes.page_size}, "
        f"{sizes.slots} slots, max_len {sizes.max_len}; decode path: {path}")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, mcfg.vocab_size, (sizes.prompt_len,))
               for _ in range(sizes.n_requests)]
    # the library path first: it is the reference for the HTTP ids, and
    # it compiles the two programs the server then reuses
    t0 = time.perf_counter()
    reference = engine.run([prompts[0]], max_new_tokens=sizes.new_tokens)
    say(f"serve: engine.run reference, {len(reference[0].output)} tokens "
        f"in {time.perf_counter() - t0:.1f} s (includes compile)")

    with start_api_server(engine) as srv:
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(_stream_completion, srv.url, p,
                                   sizes.new_tokens) for p in prompts]
            results = [f.result() for f in futures]
        wall = time.perf_counter() - t0
    for i, r in enumerate(results):
        ok = (r["status"] == 200 and len(r["ids"]) == sizes.new_tokens
              and all(0 <= t < mcfg.vocab_size for t in r["ids"]))
        say(f"serve: request {i}: HTTP {r['status']}, {len(r['ids'])} ids, "
            f"finish {r['finish']}, ttft {r['ttft_s'] * 1e3:.0f} ms")
        if not ok:
            raise RuntimeError(f"bad completion for request {i}: {r}")
    if results[0]["ids"] != [int(t) for t in reference[0].output]:
        raise RuntimeError(
            f"HTTP greedy ids differ from engine.run: {results[0]['ids']} "
            f"vs {list(reference[0].output)}")
    say("serve: HTTP greedy ids equal engine.run for the same prompt")
    # the engine's recovery turns a device error into a replay; on a
    # smoke run any recovery is a failure
    res = engine.resilience_stats
    if any(res[k] for k in ("recoveries", "failed", "nan_steps", "rebuilds")):
        raise RuntimeError(f"engine recovered from faults: {res}")
    total = sum(len(r["ids"]) for r in results)
    say(f"serve: {len(results)} concurrent streams, {total} tokens in "
        f"{wall:.2f} s = {total / wall:.1f} tok/s (observation)")

    # the decode program as the server ran it (max_chunk 8)
    probe = program_audit.program_probe(engine, "decode_chunk")
    args = list(probe.args)
    args[probe.static_argnums[0]] = srv.front_door.max_chunk  # K
    n = require_kernel(probe.fn.lower(*args).compile().as_text(),
                       "decode_chunk", rehearsal)
    if not rehearsal and n < sizes.serve_layers:
        raise RuntimeError(
            f"{n} Pallas calls for {sizes.serve_layers} decoder layers")
    print_memory(devices, "after serve")


# ---------------------------------------------------------------- driver
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the ZeRO-3 step, one device then four")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on the CPU; never a result")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        # an explicit request, never something found out: the CPU
        # backend with as many virtual devices as the phase needs, and
        # the Pallas call sites in interpret mode
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
        os.environ["PADDLE_TPU_FORCE_PALLAS"] = "1"
        os.environ["PT_FLAGS_fused_decode"] = "on"

    import jax

    from benchmarks.compile_cache import CacheCounter, enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.rehearse_cpu else "tpu"
    if platform != want or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} {want} device(s); JAX found "
              f"{len(devices)} x {platform}", file=sys.stderr)
        return 1
    sizes = REHEARSAL if args.rehearse_cpu else REAL
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    say(f"device: {platform} {devices[0].device_kind} x {len(devices)}"
        + ("  ** CPU REHEARSAL: not a chip run **" if args.rehearse_cpu
           else ""))

    if args.chips == 4:
        one, _ = train_phase(devices[:1], sizes, sizes.sharded_layers,
                             sizes.sharded_batch, args.rehearse_cpu)
        gc.collect()
        four, in_use = train_phase(devices[:4], sizes, sizes.sharded_layers,
                                   sizes.sharded_batch, args.rehearse_cpu)
        # a model left whole on device 0 shows as one device holding
        # several times what the others do
        if None not in in_use and max(in_use) > 1.5 * min(in_use):
            raise RuntimeError(f"ZeRO-3 state is not spread evenly: {in_use}")
        for step, (a, b) in enumerate(zip(one, four), 1):
            rel = abs(a - b) / max(abs(a), 1e-6)
            say(f"sharded: step {step} loss 1 dev {a:.4f} vs 4 dev {b:.4f} "
                f"(rel {rel:.1e}, tolerance {SHARDED_LOSS_RTOL:.0e})")
            if not rel <= SHARDED_LOSS_RTOL:
                raise RuntimeError("ZeRO-3 loss disagrees with one device")
    else:
        kernels_phase()
        train_phase(devices[:1], sizes, sizes.train_layers,
                    sizes.train_batch, args.rehearse_cpu)
        gc.collect()
        serve_phase(devices[:1], sizes, args.rehearse_cpu)

    say(f"compile cache {cache_dir}: {cache.hits} hits, {cache.misses} "
        f"misses in this process")
    if args.rehearse_cpu:
        say("rehearsal on the CPU finished: control flow only, no result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
