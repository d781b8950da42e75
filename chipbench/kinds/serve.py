"""Kind "serve": closed-loop clients against ``start_api_server``.

Set-up builds the model, a paged ``ContinuousBatchingEngine`` and the
HTTP front door with their defaults, makes every request of the run
from the seed, warms every program the traffic can reach with a short
burst through the same front door, and seals the engine's program set.
The window is the closed loop of ``generators/closed_loop_http.py``.
After it the server is shut down, the peak is read, the engine and the
model are freed, and the plain reference runs once, layer by layer,
over a sample of the finished requests (drawn from the seed, the
longest among them): each prompt with its served tokens.

Reading ``logit_gap``: over every served token of the sample, the
widest gap by which the reference's logit of the served token lies
below the reference's best at that position. Greedy decoding serves
the program's own best token, so the gap is the program's rounding and
nothing else. ``--mode control`` reads the same gap for the token a
W8A8 copy of the reference puts first at each position.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np


def reference_gaps(ctx, layers: int, sample: list, control: bool) -> dict:
    """Run the reference over each (prompt, served ids) of the sample,
    one layer's weights on the device at a time. Returns the served
    tokens' widest gap and, if asked, the control's."""
    import jax
    import jax.numpy as jnp

    weights, ref = ctx.part("weights"), ctx.part("reference")
    w = ctx.widths()
    longest = max(len(p) + len(o) for p, o in sample)
    length = -(-longest // 128) * 128
    ids = np.zeros((len(sample), length), np.int32)
    for r, (p, o) in enumerate(sample):
        ids[r, :len(p) + len(o)] = np.concatenate([p, o])
    # served token j of a request is predicted at position len(p)-1+j
    rows = np.concatenate([np.full(len(o), r) for r, (p, o)
                           in enumerate(sample)])
    cols = np.concatenate([len(p) - 1 + np.arange(len(o))
                           for p, o in sample])
    served = np.concatenate([np.asarray(o, np.int32) for _, o in sample])
    cos, sin = ref.rope_tables(w["head_dim"], length, w["rope_theta"])
    top = {n: v.astype(jnp.float32)
           for n, v in weights.make_top(w, ctx.seed).items()}

    def logits_of(mm):
        layer = jax.jit(lambda x, lp, cos, sin: ref.decoder_layer(
            x, lp, w, cos, sin, mm))
        x = top["model.embed_tokens.weight"][jnp.asarray(ids)]
        for i in range(layers):
            lp = {n: v.astype(jnp.float32) for n, v in
                  weights.make_layer(w, ctx.seed, i).items()}
            x = layer(x, lp, cos, sin)
        # the weights go in as arguments: closed over, they would be
        # constants of the program, half a gigabyte to compile and cache
        head = jax.jit(lambda h, top: ref.head_logits(h, top, w, mm))
        return head(x[rows, cols], top)  # [served tokens, vocab]

    logits = logits_of(ref.f32_mm)
    best = jnp.max(logits, axis=-1)
    at = jnp.asarray(served)[:, None]
    gaps = best - jnp.take_along_axis(logits, at, axis=-1)[:, 0]
    out = {"logit_gap": float(jnp.max(gaps)), "tokens": int(served.size),
           "logit_std": float(jnp.std(logits[0]))}
    if control:
        low = jnp.argmax(logits_of(ref.int8_mm), axis=-1)[:, None]
        out["control_gap"] = float(jnp.max(
            best - jnp.take_along_axis(logits, low, axis=-1)[:, 0]))
    return out


def _plant_fault(ctx, engine, vocab: int):
    """``alter_token``: every 40th token (every 5th at the rehearsal's
    size, whose sample is a few dozen tokens) is altered where the
    engine commits it, before the front door streams it."""
    if ctx.fault != "alter_token":
        raise SystemExit(f"chipbench: kind serve has no fault "
                         f"{ctx.fault!r}")
    real = engine._maybe_finish
    count, period = [0], 5 if ctx.rehearse else 40

    def maybe_finish(slot, tok):
        count[0] += 1
        req = engine._slot_req.get(slot)
        if count[0] % period == 0 and req is not None and req.output:
            req.output[-1] = (int(req.output[-1]) + 1) % vocab
        return real(slot, tok)

    engine._maybe_finish = maybe_finish


class _Sampler:
    """Reads the engine's ``active`` mask and lengths every few
    milliseconds while the traced window runs (plain reads of two numpy
    arrays; the engine's own counters are host clocks and are not
    read)."""

    def __init__(self, engine, period_s=0.005):
        self.engine, self.period = engine, period_s
        self.active, self.ctx = [], []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        while not self._stop.wait(self.period):
            act = np.asarray(self.engine.active, bool)
            self.active.append(int(act.sum()))
            self.ctx.append(int(np.asarray(
                self.engine.seq_lens)[act].sum()))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(5)


def run(ctx) -> dict:
    import jax

    from paddle_tpu.inference import ContinuousBatchingEngine, EngineConfig
    from paddle_tpu.serving_api import start_api_server

    from chipbench import run as harness

    gen, flops_of = ctx.generator(), ctx.part("flops")
    sizes = ctx.sizes()
    w = ctx.widths()
    layers, vocab = w["num_hidden_layers"], w["vocab_size"]
    eng_cfg = dict(ctx.config["engine"])
    if ctx.rehearse:
        eng_cfg.update(ctx.config["rehearsal"]["engine"])
    slots = eng_cfg["max_slots"]
    pool = gen.request_pool(sizes, vocab, ctx.seed, sizes["pool"])
    rng = np.random.default_rng([ctx.seed, 13])

    # ---- set-up
    model = ctx.part("program").build_model(ctx, layers)
    engine = ContinuousBatchingEngine(model, EngineConfig(
        paged=True, page_size=eng_cfg["page_size"], max_slots=slots,
        max_len=eng_cfg["max_len"]))
    pool_dtype = engine.layer_caches[0].k_pages.dtype
    ctx.say(f"engine: paged, page {eng_cfg['page_size']}, {slots} slots, "
            f"max_len {eng_cfg['max_len']}, KV pool {pool_dtype}")
    if ctx.fault:
        _plant_fault(ctx, engine, vocab)
    srv = start_api_server(engine)
    seconds = min(ctx.seconds, sizes["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    # the sampler's thread takes the interpreter every 5 ms: it runs in
    # traced runs only, whose window sets no end-to-end metric
    sampler = _Sampler(engine) if ctx.trace else None
    window = jax.profiler.TraceAnnotation("chipbench.window")
    opened = {}

    def on_open():
        # the loop is in full swing and has reached every program
        engine.seal_programs()
        opened["misses"] = harness_cache_misses()
        if ctx.trace:
            harness.start_trace()
        opened["setup_s"] = time.perf_counter() - ctx.t_start
        ctx.say("warmed up; programs sealed; the window opens")
        window.__enter__()
        if sampler:
            sampler.__enter__()

    try:
        loop = gen.run_closed_loop(
            srv.url, pool, slots, seconds, sizes["drain_seconds"],
            jax.profiler.TraceAnnotation if ctx.trace else gen.NoSpan,
            warm_completions=sizes["warm_completions"],
            stagger_s=sizes["stagger_seconds"], on_open=on_open)
        t_close = time.perf_counter()  # the drain is inside the trace
        if sampler:
            sampler.__exit__()
        window.__exit__(None, None, None)
        if ctx.trace:
            harness.stop_trace()
        recompiles = engine.recompile_snapshot()
        resil = dict(engine.resilience_stats)
    finally:
        srv.shutdown()
    recs, t0, t_end = loop["records"], loop["t0"], loop["t_end"]
    still_open, setup_s, before = loop["still_open"], opened["setup_s"], \
        opened["misses"]
    peak = harness.memory_peak(ctx.devices)
    summary = gen.summarize(recs, t0, t_end)
    failed = [r for r in recs if r["error"]]  # the warm phase's too
    sent = summary.pop("sent")
    ctx.say(f"{len(recs) - len(sent)} requests sent before the window; "
            f"window: {len(sent)} requests sent, {len(failed)} failed, "
            f"{still_open} still open after the drain; "
            f"{summary['out_tokens']} tokens in {summary['wall_s']:.2f} s; "
            f"ttft p50 {summary['ttft_p50_ms']:.1f} ms p95 "
            f"{summary['ttft_p95_ms']:.1f} ms; tpot p50 "
            f"{summary['tpot_p50_ms']:.2f} ms p95 "
            f"{summary['tpot_p95_ms']:.2f} ms over {summary['samples']} "
            f"requests; peak {peak} B")
    ctx.say("requests sent in the window, in order (prompt length, "
            "max_tokens, ttft ms, tpot ms): " + str([
                (len(r["prompt"]), r["max_tokens"],
                 round((r["arrivals"][0][0] - r["send"]) * 1e3),
                 round((r["arrivals"][-1][0] - r["arrivals"][0][0]) * 1e3
                       / max(len(r["ids"]) - 1, 1)))
                for r in sent if r["arrivals"]]))
    for r in failed[:3]:
        ctx.say(f"failed request {r['index']}: {r['error']}")
    post_seal = sum((recompiles.get("recompiles") or {}).values())
    missed = harness_cache_misses() - before
    faults = sum(resil.get(k, 0) for k in
                 ("recoveries", "failed", "nan_steps", "rebuilds"))
    ctx.say(f"post-seal recompiles {post_seal}, compile-cache misses in "
            f"the window {missed}, engine recoveries {faults}")

    # ---- free the program, then the reference over a sample
    good = [r for r in recs if r["error"] is None]
    n = min(sizes["check_requests"], len(good))
    sample = []
    if good:
        longest = max(good, key=lambda r: len(r["prompt"]) + len(r["ids"]))
        others = [r for r in good if r is not longest]
        picks = rng.permutation(len(others))[:max(0, n - 1)]
        sample = [longest] + [others[int(i)] for i in picks]
    del engine, model, srv
    gc.collect()
    t_ref = time.perf_counter()
    if sample:
        got = reference_gaps(
            ctx, layers, [(np.asarray(r["prompt"]), r["ids"])
                          for r in sample], ctx.mode == "control")
    else:
        got = {"logit_gap": float("inf"), "tokens": 0}
    ctx.say(f"reference over {len(sample)} requests, {got['tokens']} "
            f"served tokens, in {time.perf_counter() - t_ref:.1f} s: {got}")
    value = got["control_gap"] if ctx.mode == "control" \
        else got["logit_gap"]
    compared = {
        "logit_gap": {"value": value, "limit": ctx.limits["logit_gap"]},
        "served_gap": {"value": got["logit_gap"],
                       "limit": ctx.limits["logit_gap"]},
        "post_seal_recompiles": {"value": post_seal + missed, "limit": 0},
        "engine_recoveries": {"value": faults, "limit": 0},
        "failed_requests": {"value": len(failed) + still_open, "limit": 0},
    }
    if ctx.mode != "control":
        del compared["served_gap"]
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for n, c in compared.items() if n != "served_gap")

    # the work the TRACED span required (window and drain): the prompt
    # of each request sent in it, and each token served in it at its own
    # context
    flops, span_tokens = 0.0, 0
    for r in recs:
        p = len(r["prompt"])
        n_before = sum(k for t, k in r["arrivals"] if t < t0)
        n_in = len(r["ids"]) - n_before
        if r["send"] >= t0 and r["ids"]:  # its prefill is in the span
            flops += flops_of.forward(w, layers, p, (p + 1) / 2)
        if n_in:
            flops += flops_of.forward(
                w, layers, n_in, p + n_before + (n_in + 1) / 2)
        span_tokens += n_in
    counters = {"wall_s": t_close - t0, "required_flops": flops,
                "out_tokens": span_tokens, "requests": len(sent)}
    if sampler and sampler.active:
        counters["slots_active_mean"] = float(np.mean(sampler.active))
        busy = [c for a, c in zip(sampler.active, sampler.ctx) if a]
        counters["ctx_tokens_mean"] = float(np.mean(busy)) if busy else 0.0
    facts = {"counters": counters,
             "shapes": {"layers": layers, "slots": slots, **w},
             "no_span": "engine driver thread (no span)"}
    return {
        "end_to_end": {
            "serve_out_tokens_per_s": summary["out_tokens_per_s"],
            "ttft_p95_ms": summary["ttft_p95_ms"],
            "tpot_p95_ms": summary["tpot_p95_ms"],
            "setup_s": setup_s},
        "attempted": len(sent) + still_open,
        "failed": len(failed) + still_open,
        "correct": correct, "compared": compared,
        "memory_peak_bytes": peak, "facts": facts}


def harness_cache_misses() -> int:
    """Persistent-cache misses so far in this process (a miss is a
    program compiled here), from the harness's own counter."""
    from chipbench import run as harness

    return harness.CACHE.misses if harness.CACHE else 0
