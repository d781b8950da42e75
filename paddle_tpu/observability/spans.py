"""The program's spans and device phases on the profiler's clock.

Host spans are ``jax.profiler.TraceAnnotation`` (``pt.train.step`` a
``StepTraceAnnotation``): in any ``jax.profiler`` trace they are events
of the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds the
device's operations, on one clock; with no trace running an annotation
is a flag check. Device phases are ``jax.named_scope``: metadata on the
operations (``op_name``), the compiled program computes the same thing.
Counts ride as a span's arguments, read where and when the work
happened. There is no flag, no thread and no buffer behind any of it.

Call sites name their span with the literal, as ``chipbench`` does; this
table is the one list of the names (PERF.md section 3 prints it, and
``tests/test_program_spans.py`` holds every ``"pt.`` literal in the
package against it). No scope sits around a ``pallas_call`` site: a
kernel's event is named from its scope path, and the benchmark's
roofline metrics match the names the kernels have.
"""

from __future__ import annotations

# name -> (layer, what it covers, its arguments, the metrics that read it)
SPANS = {
    "pt.train.step": (
        "trainer host", "the whole of TrainStep.run (a step marker: "
        "step_num is the step's number)", ("step_num", "tokens"),
        ("telemetry_idle_ms.train", "idle_attributed_share.train")),
    "pt.train.shard_batch": (
        "trainer host", "the batch's host-to-device copy", (),
        ("idle_attributed_share.train",)),
    "pt.train.dispatch": (
        "trainer host", "the compiled step's call: cache lookup and "
        "dispatch; the runtime holds it back when about six steps are "
        "queued. Once a TrainTelemetry counts compiles, a call that "
        "traced, lowered, compiled or loaded a program from the "
        "persistent cache carries how many backend compiles (a cache "
        "load is one) and their milliseconds; a steady step carries no "
        "argument",
        ("compiles", "compile_ms"), ("idle_attributed_share.train",)),
    "pt.train.sample_fetch": (
        "trainer host", "a sampled step only: telemetry's read of the "
        "loss and the gradient norm, which waits for every step "
        "dispatched ahead, then gauges and the watchdog; for a model "
        "with expert layers also that step's routing counts, summed "
        "over the expert blocks (moe_rows_max: the fullest held "
        "expert of the worst block; moe_rows_walked, from gated layers: "
        "the rows the held experts multiplied, padding included); "
        "gc_ms, gc_count, compile_ms, compiles: the garbage collector's "
        "pauses and the programs traced, compiled or loaded in the "
        "process since the sample before",
        ("interval_steps", "moe_rows_routed", "moe_rows_held",
         "moe_rows_max", "moe_rows_walked", "gc_ms", "gc_count",
         "compile_ms", "compiles"),
        ("telemetry_idle_ms.train", "moe_held_rows_share.train",
         "moe_expert_load_max_over_mean.train",
         "swiglu_experts_rows_share.train",
         "swiglu_experts_load_max_over_mean.train", "host_gc_ms.train",
         "compile_ms.train")),
    "pt.train.sync_to_model": (
        "trainer host", "rebinding the model's parameters to the "
        "step's outputs", (), ("idle_attributed_share.train",)),
    "pt.gc": (
        "trainer host", "a pause of Python's cyclic garbage collector, "
        "on the thread that triggered it and inside whichever span was "
        "open there (any process that built a TrainTelemetry: a serving "
        "engine's ticks too); collected is set at its end",
        ("generation", "collected"),
        ("gc_idle_ms.train", "idle_attributed_share.train",
         "idle_attributed_share.serve")),
    "pt.engine.tick": (
        "engine", "one scheduler tick, step() or step_chunk(), "
        "epilogue included; the arguments are the state the tick "
        "found, before its own admission",
        ("active", "queued", "pages_used", "pages_total"),
        ("engine_host_ms_per_tick.serve", "kv_pages_used_share.serve",
         "tick_slots_active.serve", "idle_attributed_share.serve")),
    "pt.engine.admit": (
        "engine", "admission: the blocking _admit(), and in the "
        "overlapped paths one span around the claim and prefill "
        "dispatch behind the decode chunk and one around the first "
        "tokens' read, which carries the arguments (fresh admissions, "
        "submit to the engine's admit instant)",
        ("admitted", "queue_wait_ms_sum"), ("queue_wait_ms.serve",)),
    "pt.engine.dispatch": (
        "engine", "one compiled program's call, up to its return "
        "(decode_step, decode_chunk, spec_verify, prefill_chunk; the "
        "legacy per-bucket prefill has none)",
        ("program",), ("idle_attributed_share.serve",)),
    "pt.engine.sync": (
        "engine", "a wait for the device: the step's tokens, and each "
        "admitted request's first token", (),
        ("engine_host_ms_per_tick.serve",)),
    "pt.engine.emit": (
        "engine", "the per-slot loop after the sync: outputs, "
        "finishes, stream queues", ("tokens",),
        ("idle_attributed_share.serve",)),
    "pt.engine.wait": (
        "front door", "the driver thread's wait for work", (),
        ("idle_attributed_share.serve",)),
}

# scope -> (phase the benchmark reports it under, what it covers)
SCOPES = {
    "master_cast": ("unscoped", "the compute-dtype view cast from the "
                    "float32 masters"),
    "grad_norm": ("unscoped", "telemetry's pre-clip global gradient "
                  "norm, the step's extra output"),
    "optimizer": ("optimizer", "optimizer.update, the clip included"),
    "embed": ("head", "the token embedding"),
    "attn_in": ("attention", "input norm, q/k/v projections, the heads' "
                "q/k norm, RoPE"),
    "attn_out": ("attention", "output projection and residual"),
    "mlp": ("mlp", "post-attention norm, MLP, residual"),
    "head_loss": ("head", "final norm, lm_head, the loss"),
    "ssm_in": ("ssm", "a Mamba-2 block's norm, in_proj, causal conv, "
               "softplus of dt"),
    "ssm_scan": ("ssm", "the chunked SSD (kernels/ssd.py)"),
    "ssm_out": ("ssm", "gated group norm, out_proj, residual"),
    "moe_router": ("moe", "an expert block's norm, router scores, "
                   "top-k, weights"),
    "moe_experts": ("moe", "the held experts' rows: sort, gather, the "
                    "grouped products (two an expert, three for gated "
                    "experts), the weighted combine; the residual where "
                    "there is no shared expert"),
    "moe_shared": ("moe", "the shared expert, residual"),
    "s6_in": ("s6", "a Mamba-1 block's norm, in_proj, causal conv, "
              "x_proj, dt_proj, softplus"),
    "s6_scan": ("s6", "the chunked selective scan's kernel pair "
                "(kernels/selective_scan.py: the D skip and every sum "
                "over channels inside) and what XLA still makes around "
                "it: A's transpose, y's rounding, its cotangent widened"),
    "s6_out": ("s6", "the gate, out_proj, residual"),
    "gmu": ("gmu", "a gated memory unit: norm, both products, the gate "
            "on another layer's scan output, residual"),
    "sconv_in": ("sconv", "a gated short convolution's norm and in_proj "
                 "(shortconv_device_ms.train)"),
    "sconv_mix": ("sconv", "between its projections, in float32: B * x, "
                  "the causal taps, C * z; no weight product "
                  "(shortconv_device_ms.train, "
                  "shortconv_mix_device_ms.train)"),
    "sconv_out": ("sconv", "out_proj, residual "
                  "(shortconv_device_ms.train)"),
}
