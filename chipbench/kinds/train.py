"""Kind "train": batches from the seed through ``TrainStep.run``.

Set-up builds ONE ``TrainStep`` (``dist.build_mesh`` -> ``TrainStep``,
the README's path), drives it from the seed through its first steps,
reading between them what the comparison needs, warms it up, and hands
that same object to the window. The window makes a fresh batch on the
host every step, calls ``TrainStep.run``, and reads the loss back every
few steps as a logging loop does. After the window the program's state
is freed and the plain reference follows the first steps in float32.

Readings (program against reference, see PERF.md section 2):
  loss_gap    each checked step's loss, worst relative gap
  grad_gap    ||first gradient as the optimizer got it||, per leaf, from
              moment1 after one step; worst leaf, gap of norms over the
              larger of the reference's norm of that leaf and of the
              median leaf
  delta_gap   ||parameters' change after the checked steps||, per leaf,
              against the float32 masters; same measure; leaves whose
              reference gradient is under a thousandth of the median
              leaf's are left out
  grad_weighted_gap
              the leaves' gradient gaps averaged with each leaf's number
              of parameters for weight: what the typical parameter's
              gradient is off by, steady where the worst leaf swings
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np


def _leaf_norms(ctx, ts, layers, what: str) -> dict:
    """Per-leaf norms read from the program's optimizer state: the
    first moment ("moment1"), or the float32 master's distance from
    the seed's weights ("delta")."""
    import jax
    import jax.numpy as jnp

    weights = ctx.part("weights")
    if what == "moment1":
        fn = jax.jit(lambda slots: {
            n: jnp.linalg.norm(s["moment1"].astype(jnp.float32).ravel())
            for n, s in slots.items()})
        return {n: float(v) for n, v in
                fn(ts.opt_state["slots"]).items()}
    w = ctx.widths()
    dist = jax.jit(lambda ms, p0: {
        n: jnp.linalg.norm((ms[n] - p0[n].astype(jnp.float32)).ravel())
        for n in p0})
    master = ts.opt_state["master"]
    out = {}
    groups = [("", weights.make_top(w, ctx.seed))] + [
        (f"model.layers.{i}.", weights.make_layer(w, ctx.seed, i))
        for i in range(layers)]
    for pre, p0 in groups:
        got = dist({n: master[pre + n] for n in p0}, p0)
        out.update({pre + n: float(v) for n, v in got.items()})
    return out


def leaf_sizes(ctx, layers: int) -> dict:
    """Each leaf's number of parameters, by the program's full name."""
    import math

    weights = ctx.part("weights")
    w = ctx.widths()
    out = {n: math.prod(s) for n, s in weights.top_shapes(w).items()}
    for i in range(layers):
        out.update({f"model.layers.{i}.{n}": math.prod(s)
                    for n, s in weights.layer_shapes(w).items()})
    return out


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    return {n: abs(prog[n] - r) / max(r, med) for n, r in ref.items()
            if keep is None or n in keep}


def reference_steps(ctx, layers, batches, hp, mm_name="f32_mm"):
    """The plain reference through ``len(batches)`` AdamW steps in
    float32: per-step loss, the first step's clipped gradient norms and
    the parameters' change, per leaf. One gradient tree lives on the
    device at a time: earlier clipped gradients wait on the host and the
    moments are rebuilt from them leaf by leaf."""
    import jax
    import jax.numpy as jnp

    weights, ref = ctx.part("weights"), ctx.part("reference")
    w = ctx.widths()
    mm = getattr(ref, mm_name)
    p = {n: v.astype(jnp.float32)
         for n, v in weights.make_all(w, ctx.seed, layers).items()}
    grad = jax.jit(jax.value_and_grad(
        lambda p, ids: ref.lm_loss(p, ids, w, layers, mm)))
    sq = jax.jit(lambda g: {n: jnp.sum(v * v) for n, v in g.items()})
    def update(step):
        return jax.jit(lambda p, hist: ref.adamw_from_history(
            p, hist, step, hp), donate_argnums=(0,))

    losses, first_norms, host_hist = [], None, []
    for step, ids in enumerate(batches, 1):
        loss, g = grad(p, jnp.asarray(ids))
        losses.append(float(loss))
        ctx.say(f"reference ({mm_name}): step {step} gradient done")
        sqn = sq(g)
        scale = float(ref.clip_scale(sqn, hp["clip_global_norm"]))
        if first_norms is None:
            first_norms = {n: scale * float(v) ** 0.5
                           for n, v in sqn.items()}
        upd, on_host = update(step), {}
        for n in list(p):
            ghat = g.pop(n) * scale
            hist = [jnp.asarray(h[n]) for h in host_hist] + [ghat]
            p[n] = upd(p[n], hist)
            if step < len(batches):  # a later step needs it again
                on_host[n] = np.asarray(ghat)
        host_hist.append(on_host)
    dist = jax.jit(lambda a, b: jnp.linalg.norm(
        (a - b.astype(jnp.float32)).ravel()))
    p0 = weights.make_all(w, ctx.seed, layers)
    delta = {n: float(dist(p[n], p0[n])) for n in p}
    del p, p0
    gc.collect()
    return {"loss": losses, "grad": first_norms, "delta": delta}


def compare(prog: dict, ref: dict, limits: dict, n_params: dict) -> dict:
    """The numbers of the module's docstring; ``n_params`` weighs the
    leaves of ``grad_weighted_gap``."""
    small = 1e-3 * statistics.median(ref["grad"].values())
    moving = {n for n, v in ref["grad"].items() if v >= small}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    grad = leaf_gaps(prog["grad"], ref["grad"])
    delta = leaf_gaps(prog["delta"], ref["delta"], moving)
    worst = {k: max(g, key=g.get) for k, g in
             (("grad", grad), ("delta", delta))}
    got = {
        "loss_gap": {"value": loss_gap},
        "grad_gap": {"value": grad[worst["grad"]], "leaf": worst["grad"]},
        "grad_weighted_gap": {"value": sum(
            n_params[n] * g for n, g in grad.items())
            / sum(n_params[n] for n in grad)},
        "delta_gap": {"value": delta[worst["delta"]],
                      "leaf": worst["delta"]},
    }
    # a number whose limit the file leaves null is read and printed,
    # not held (PERF.md section 2 says why)
    for name, entry in got.items():
        entry["limit"] = limits[name]
    got["grad_gap"]["by_leaf"] = grad  # for the look, when one reads high
    return got


def is_correct(compared: dict) -> bool:
    return all(np.isfinite(c["value"]) and (
        c["limit"] is None or c["value"] <= c["limit"])
        for c in compared.values())


def _plant_fault(ctx, ts):
    """Break the timed path underneath (tests, and the limits' upper
    readings): the call the window drives is replaced on the object."""
    import jax

    real = ts.run
    if ctx.fault == "half_batch":
        # half of the rows left out, the mean taken over the rest
        def run(batch, **kw):
            return real({k: v[: max(1, v.shape[0] // 2)]
                         for k, v in batch.items()}, **kw)
    elif ctx.fault == "frozen_state":
        # a step that returns its state unchanged
        def run(batch, **kw):
            keep = jax.tree_util.tree_map(
                lambda x: x.copy(), (ts.params, ts.opt_state))
            loss = real(batch, **kw)
            ts.params, ts.opt_state = keep
            ts.sync_to_model()
            return loss
    else:
        raise SystemExit(f"chipbench: kind train has no fault "
                         f"{ctx.fault!r}")
    ts.run = run


def run(ctx) -> dict:
    import jax

    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.trainer import TrainStep

    from chipbench import run as harness

    gen_mod = ctx.generator()
    sizes = ctx.sizes()
    hp = ctx.config["trainer"]
    layers = ctx.widths()["num_hidden_layers"]
    n_checked = sizes["checked_steps"]
    vocab = ctx.widths()["vocab_size"]
    tokens_per_step = sizes["batch"] * sizes["sequence"]

    if ctx.mode == "control":
        # the reference, one precision down, in the program's place
        if ctx.fault:
            raise SystemExit("chipbench: a fault is planted in the "
                             "program, not in the control")
        batches = gen_mod.Batches(sizes, vocab, ctx.seed)
        first = [batches.next() for _ in range(n_checked)]
        low = reference_steps(ctx, layers, first, hp, "int8_mm")
        ref = reference_steps(ctx, layers, first, hp)
        compared = compare(low, ref, ctx.limits, leaf_sizes(ctx, layers))
        return {"end_to_end": {}, "attempted": n_checked, "failed": 0,
                "correct": is_correct(compared), "compared": compared,
                "memory_peak_bytes": harness.memory_peak(ctx.devices)}

    # ---- set-up: one object, driven from the seed, then warmed up
    model = ctx.part("program").build_model(ctx, layers)
    mesh = dist.build_mesh(devices=ctx.devices)
    optimizer = opt.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"], multi_precision=True,
        grad_clip=opt.ClipGradByGlobalNorm(hp["clip_global_norm"]))
    ts = TrainStep(model, optimizer, mesh, DistributedStrategy())
    ctx.say("TrainStep built (optimizer state made on the device)")
    if ctx.fault:
        _plant_fault(ctx, ts)
    batches = gen_mod.Batches(sizes, vocab, ctx.seed)

    def feed():
        with jax.profiler.TraceAnnotation("make_batch"):
            ids = batches.next()
        with jax.profiler.TraceAnnotation("TrainStep.run"):
            return ts.run({"input_ids": ids, "labels": ids})

    prog = {"loss": []}
    for step in range(1, n_checked + 1):
        prog["loss"].append(float(feed()))
        if step == 1:
            prog["grad"] = {n: v / (1 - hp["beta1"]) for n, v in
                            _leaf_norms(ctx, ts, layers, "moment1").items()}
    prog["delta"] = _leaf_norms(ctx, ts, layers, "delta")
    ctx.say(f"first steps' losses {prog['loss']}")
    for _ in range(sizes["warmup_steps"]):
        loss = feed()
    loss.block_until_ready()
    ctx.say(f"warmed up: {n_checked + sizes['warmup_steps']} steps")

    # ---- the window
    seconds = min(ctx.seconds, sizes["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    every = sizes["loss_readback_every"]
    if ctx.trace:
        harness.start_trace()
    setup_s = time.perf_counter() - ctx.t_start
    # the longest single pass of the loop, (seconds, step), apart for a
    # pass that only dispatches and for one that waits for a loss: a run
    # that reads far off says which side stalled, the host or the device
    steps, last = 0, None
    longest = {"dispatch": (0.0, 0), "readback": (0.0, 0)}
    with jax.profiler.TraceAnnotation("chipbench.window"):
        t0 = t_prev = time.perf_counter()
        while t_prev - t0 < seconds:
            loss = feed()
            steps += 1
            what = "dispatch"
            if steps % every == 0:
                last, what = float(loss), "readback"
            now = time.perf_counter()
            longest[what] = max(longest[what], (now - t_prev, steps))
            t_prev = now
        last = float(loss)  # ends in block_until_ready, as a window must
        wall = time.perf_counter() - t0
    if ctx.trace:
        harness.stop_trace()
    peak = harness.memory_peak(ctx.devices)
    ok_loss = bool(np.isfinite(last))
    ctx.say(f"window: {steps} steps of {tokens_per_step} tokens in "
            f"{wall:.3f} s, last loss {last:.4f}, peak {peak} B; longest "
            + ", ".join(f"{k} pass {v * 1e3:.1f} ms at step {n}"
                        for k, (v, n) in longest.items())
            + f" (a readback waits for up to {every} steps)")

    # ---- free the program, then the reference follows the first steps
    del ts, model, optimizer, loss
    gc.collect()
    t_ref = time.perf_counter()
    first = gen_mod.Batches(sizes, vocab, ctx.seed)
    ref = reference_steps(
        ctx, layers, [first.next() for _ in range(n_checked)], hp)
    compared = compare(prog, ref, ctx.limits, leaf_sizes(ctx, layers))
    ctx.say(f"reference: {n_checked} float32 steps in "
            f"{time.perf_counter() - t_ref:.1f} s; losses {ref['loss']}")

    step_flops = ctx.part("flops").train_step(
        ctx.widths(), layers, sizes["batch"], sizes["sequence"])
    facts = {
        "counters": {"steps": steps, "wall_s": wall,
                     "tokens": steps * tokens_per_step,
                     "required_flops": steps * step_flops},
        "shapes": {"batch": sizes["batch"], "sequence": sizes["sequence"],
                   "layers": layers, **ctx.widths()},
        "no_span": "host, outside make_batch and TrainStep.run",
    }
    return {
        "end_to_end": {
            "train_tokens_per_s": steps * tokens_per_step / wall,
            "setup_s": setup_s},
        "attempted": steps, "failed": 0 if ok_loss else steps,
        "correct": is_correct(compared) and ok_loss,
        "compared": compared, "memory_peak_bytes": peak, "facts": facts}
