"""Sharded train-step builder — the Fleet engine's hot loop.

Parity: the composite of fleet.distributed_model + HybridParallelOptimizer
+ the 1-step path of PipelineParallel/GroupSharded wrappers (SURVEY.md
§3.3). One call builds a single jitted XLA program that contains forward,
backward, gradient reduction, clipping, and the sharded optimizer update —
the work the reference splits across Reducer hooks, sharding-stage
wrappers and fused-kernel optimizers, all scheduled by XLA with
comm/compute overlap.

Donation: params and optimizer state are donated, so the update is
in-place in HBM (parity: in-place fused adamw).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import flags, observability
from ..core.functional import extract_param_objs, functional_call
from ..core.module import Layer
from ..distributed.sharding import (
    batch_spec,
    mesh_context,
    opt_slot_partition_spec,
    param_partition_spec,
)
from ..distributed.strategy import DistributedStrategy
from ..optimizer.optimizer import Optimizer


def _batch_tokens(batch) -> int:
    """Telemetry unit count: tokens for LM batches (first integer 2-D
    leaf), else the leading batch dim (samples)."""
    sample = 0
    for v in batch.values():
        if not hasattr(v, "ndim") or v.ndim == 0:
            continue
        if not sample:
            sample = int(v.shape[0])
        dt = getattr(v, "dtype", None)
        if v.ndim == 2 and dt is not None and \
                jnp.issubdtype(dt, jnp.integer):
            return int(v.shape[0]) * int(v.shape[1])
    return sample


def _param_shardings(param_objs, mesh, strategy):
    return {
        name: NamedSharding(
            mesh, param_partition_spec(name, p.shape, p.spec, strategy)
        )
        for name, p in param_objs.items()
    }


def _state_shardings(state_shape, param_objs, mesh, strategy):
    """Mirror the optimizer state structure with shardings: any leaf whose
    shape equals its parameter's shape gets the opt-slot spec; scalars and
    odd-shaped leaves are replicated."""
    repl = NamedSharding(mesh, P())

    def slot_sharding(name, leaf):
        p = param_objs[name]
        if tuple(leaf.shape) == tuple(p.shape):
            return NamedSharding(
                mesh, opt_slot_partition_spec(name, p.shape, p.spec, strategy)
            )
        return repl

    out = {"step": repl, "slots": {}}
    for name, slots in state_shape["slots"].items():
        out["slots"][name] = {
            k: slot_sharding(name, v) for k, v in slots.items()
        }
    if "master" in state_shape:
        out["master"] = {
            name: slot_sharding(name, leaf)
            for name, leaf in state_shape["master"].items()
        }
    return out


class TrainStep:
    """Compiled train step + its sharded state.

    Usage:
        ts = TrainStep(model, optimizer, mesh, strategy, loss_fn)
        metrics = ts.run(batch)          # one optimizer step
        ts.sync_to_model()               # write params back into Layers
    """

    def __init__(
        self,
        model: Layer,
        optimizer: Optimizer,
        mesh: Mesh,
        strategy: Optional[DistributedStrategy] = None,
        loss_fn: Optional[Callable] = None,
        batch_seq_axis: Optional[int] = 1,
        donate: bool = True,
        rng_seed: int = 0,
        abstract: bool = False,
        master_residency: str = "paired",
        telemetry=None,
    ):
        """``abstract=True`` builds the full sharded step WITHOUT
        materializing parameters or optimizer state — params may be
        ``jax.ShapeDtypeStruct`` (core.meta.meta_init). Use ``lower()``
        for AOT compilation / per-device memory planning of configs far
        larger than host memory (the 70B north-star path); ``run()`` is
        unavailable.

        ``master_residency``: ``"paired"`` (default) keeps params at
        model dtype alongside fp32 masters in optimizer state — the
        classic layout. ``"master_only"`` drops the persistent
        low-precision copies: the fp32 master is the ONLY resident form
        of each bf16/fp16 parameter, and the compute-dtype view is cast
        transiently inside the step. Numerics are bit-identical to
        "paired" (the stored bf16 param is exactly cast(master) after
        every update), but steady HBM residency shrinks by
        itemsize(model_dtype) bytes/param — ~1.75 GB on the 876M
        headline — which is what buys the larger batch (parity intent:
        fleet GroupShardedOptimizerStage2 master-weight handling, which
        likewise keeps one authoritative fp32 copy).

        ``telemetry``: ``None`` (default) auto-wires an
        ``observability.TrainTelemetry`` when ``PT_FLAGS_telemetry`` is
        on; ``False`` disables instrumentation for this step; or pass a
        preconfigured ``TrainTelemetry`` (custom sampling cadence /
        flight-recorder window). When enabled, the compiled step also
        emits the global gradient norm — sampled steps publish loss /
        grad-norm / tokens-per-sec / MFU / memory through the registry
        and feed the flight recorder + NaN watchdog; non-sampled steps
        never force an extra host sync."""
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.strategy = strategy or DistributedStrategy()
        self.loss_fn = loss_fn
        self.batch_seq_axis = batch_seq_axis
        self.abstract = abstract

        self.master_residency = master_residency
        self._master_dtypes: Dict[str, jnp.dtype] = {}

        self._param_objs = extract_param_objs(model, trainable_only=True)
        self.param_shardings = _param_shardings(
            self._param_objs, mesh, self.strategy
        )
        if abstract:
            self.params = {
                n: (p.value if isinstance(p.value, jax.ShapeDtypeStruct)
                    else jax.ShapeDtypeStruct(
                        tuple(p.value.shape), p.value.dtype))
                for n, p in self._param_objs.items()
            }
        else:
            # place params
            self.params = {
                n: jax.device_put(p.value, self.param_shardings[n])
                for n, p in self._param_objs.items()
            }
        # sharded optimizer state, created on-device under jit
        state_shape = jax.eval_shape(optimizer.init, self.params)
        self.state_shardings = _state_shardings(
            state_shape, self._param_objs, mesh, self.strategy
        )
        if abstract:
            self.opt_state = state_shape
        else:
            with mesh_context(mesh):
                self.opt_state = jax.jit(
                    optimizer.init, out_shardings=self.state_shardings
                )(self.params)

            # keep the Layer tree pointing at the live arrays: device_put
            # may alias the original buffers, and step donation would
            # otherwise leave Parameters referencing deleted arrays
            self.sync_to_model()

        # master-only residency: the fp32 master in optimizer state is
        # the single persistent copy; drop the model-dtype duplicates
        # from the step's carried params
        if master_residency not in ("paired", "master_only"):
            raise ValueError(
                f"master_residency must be 'paired' or 'master_only', "
                f"got {master_residency!r}")
        master_names = set(state_shape.get("master", {}))
        if master_residency == "master_only" and not master_names:
            raise ValueError(
                "master_residency='master_only' needs fp32 masters: use "
                "an optimizer with multi_precision=True and bf16/fp16 "
                "parameters")
        if master_residency == "master_only":
            self._master_dtypes = {
                n: self.params[n].dtype for n in master_names
            }
            for n in master_names:
                # release the Layer tree's reference too, or the bf16
                # device buffer stays alive and nothing is saved; the
                # Parameter holds a meta struct until sync_to_model()
                v = self.params[n]
                if not isinstance(v, jax.ShapeDtypeStruct):
                    self._param_objs[n].value = jax.ShapeDtypeStruct(
                        tuple(v.shape), v.dtype)
            self.params = {n: v for n, v in self.params.items()
                           if n not in master_names}
        carried_param_shardings = {
            n: s for n, s in self.param_shardings.items()
            if n in self.params
        }
        master_dtypes = self._master_dtypes

        self.step_count = 0
        self._rng_key = jax.random.PRNGKey(rng_seed)

        # telemetry: the grad-norm output is baked into the compiled
        # step only when instrumentation is live, so telemetry-off
        # compiles the exact pre-telemetry program (zero overhead).
        # abstract mode keeps the same program shape (AOT memory plans
        # must match what a real run would compile) but holds no
        # telemetry object.
        want_tel = (observability.enabled() if telemetry is None
                    else bool(telemetry))
        # check_nan_inf promises a grad-norm check: it needs the gnorm
        # output even when telemetry is off (flag read at BUILD time —
        # the program's output arity is a compile-time shape)
        emit_gnorm = want_tel or bool(flags.flag("check_nan_inf"))
        self._emit_gnorm = emit_gnorm
        # a model with expert layers hands back its step's routing
        # counts (``step_counters()``: device scalars of the forward
        # pass just traced); they leave the compiled step beside the
        # gradient norm. A model without the method compiles the
        # program it always did.
        merge_k = (self.strategy.gradient_merge_k_steps
                   if getattr(self.strategy, "gradient_merge", False) else 1)
        emit_counters = (emit_gnorm and merge_k <= 1
                         and callable(getattr(model, "step_counters", None)))
        self._emit_counters = emit_counters
        self.telemetry = None
        if want_tel and not abstract:
            self.telemetry = (
                telemetry
                if isinstance(telemetry, observability.TrainTelemetry)
                else observability.TrainTelemetry())

        model_ref = model
        loss_ref = loss_fn
        self.gradient_merge_k = merge_k

        def loss_of(p, batch, rng):
            rngs = {"dropout": rng, "default": rng}
            if loss_ref is None:
                # model computes its own scalar loss from the batch dict
                return functional_call(model_ref, p, **batch, rngs=rngs)
            out = functional_call(model_ref, p, batch["input"], rngs=rngs)
            return loss_ref(out, batch["label"])

        def step_fn(params, opt_state, batch, rng):
            if master_dtypes:
                # rebuild the compute-dtype view from the resident fp32
                # masters; XLA sees cast(master) feeding the matmuls and
                # may rematerialize the casts under memory pressure
                # instead of keeping 2 bytes/param alive across the step
                params = dict(params)
                with jax.named_scope("master_cast"):
                    for n, dt in master_dtypes.items():
                        params[n] = opt_state["master"][n].astype(dt)
            if emit_counters:
                def loss_and_counters(p, batch, rng):
                    loss = loss_of(p, batch, rng)
                    return loss, model_ref.step_counters()

                (loss, counters), grads = jax.value_and_grad(
                    loss_and_counters, has_aux=True)(params, batch, rng)
            elif merge_k <= 1:
                loss, grads = jax.value_and_grad(loss_of)(
                    params, batch, rng)
            else:
                # gradient merge (parity: fleet gradient_merge /
                # accumulate_steps): split the global batch into k
                # micro-batches and scan — one live micro-batch of
                # activations at a time, fp32 grad accumulators, a single
                # optimizer update. One compiled program, no host loop.
                def is_batched(v):
                    return hasattr(v, "ndim") and v.ndim > 0

                static_part = {k: v for k, v in batch.items()
                               if not is_batched(v)}

                def reshape_mb(v):
                    b = v.shape[0]
                    if b % merge_k:
                        raise ValueError(
                            f"gradient_merge: batch {b} not divisible by "
                            f"k_steps {merge_k}")
                    return v.reshape(merge_k, b // merge_k, *v.shape[1:])

                mbatch = {k: reshape_mb(v) for k, v in batch.items()
                          if is_batched(v)}
                rngs_k = jax.random.split(rng, merge_k)
                zero = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def body(carry, xs):
                    acc, loss_sum = carry
                    mb, r = xs
                    mb = {**mb, **static_part}
                    mb = jax.tree_util.tree_map(
                        lambda v: jax.lax.with_sharding_constraint(
                            v, NamedSharding(mesh, batch_spec(
                                v.ndim, self.batch_seq_axis
                                if v.ndim > 1 else None, self.strategy)))
                        if hasattr(v, "ndim") and v.ndim > 0 else v, mb)
                    loss, grads = jax.value_and_grad(loss_of)(params, mb, r)
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), acc, grads)
                    return (acc, loss_sum + loss), None

                (acc, loss_sum), _ = jax.lax.scan(
                    body, (zero, jnp.zeros((), jnp.float32)),
                    (mbatch, rngs_k))
                grads = jax.tree_util.tree_map(
                    lambda a: a / merge_k, acc)
                loss = loss_sum / merge_k
            if emit_gnorm:
                # pre-clip global grad norm, fp32 accumulation — a
                # single reduction pass, negligible next to fwd+bwd
                with jax.named_scope("grad_norm"):
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)))
            with jax.named_scope("optimizer"):
                new_params, new_state = optimizer.update(
                    grads, opt_state, params)
            if master_dtypes:
                # the low-precision copies are not carried: drop them so
                # XLA dead-code-eliminates the cast-back
                new_params = {n: v for n, v in new_params.items()
                              if n not in master_dtypes}
            if emit_counters:
                return new_params, new_state, loss, gnorm, counters
            if emit_gnorm:
                return new_params, new_state, loss, gnorm
            return new_params, new_state, loss

        donate_argnums = (0, 1) if donate else ()
        repl = NamedSharding(mesh, P())
        out_shardings = (carried_param_shardings, self.state_shardings,
                         repl)
        if emit_gnorm:
            out_shardings = out_shardings + (repl,)
        if emit_counters:
            out_shardings = out_shardings + (repl,)  # a dict of scalars
        self._step = jax.jit(
            step_fn,
            in_shardings=(
                carried_param_shardings,
                self.state_shardings,
                None,  # batch shardings resolve from committed inputs
                NamedSharding(mesh, P()),
            ),
            out_shardings=out_shardings,
            donate_argnums=donate_argnums,
        )

    # ------------------------------------------------------------------
    def shard_batch(self, batch: Dict[str, jax.Array]):
        out = {}
        for k, v in batch.items():
            seq_ax = self.batch_seq_axis if (
                hasattr(v, "ndim") and v.ndim > 1
            ) else None
            sh = NamedSharding(
                self.mesh, batch_spec(getattr(v, "ndim", 1), seq_ax,
                                      self.strategy)
            )
            out[k] = jax.device_put(v, sh)
        return out

    def lower(self, batch_shapes: Dict):
        """AOT-lower the full sharded train step against abstract inputs.

        ``batch_shapes``: dict of arrays or ShapeDtypeStructs. Returns a
        ``jax.stages.Lowered``; ``.compile().memory_analysis()`` gives
        the per-device argument/temp byte plan (parity: the memory
        estimation pass of the reference's static auto-parallel engine,
        distributed/auto_parallel/static/engine.py)."""
        batch = {
            k: jax.ShapeDtypeStruct(
                tuple(v.shape), v.dtype,
                sharding=NamedSharding(
                    self.mesh,
                    batch_spec(
                        len(v.shape),
                        self.batch_seq_axis if len(v.shape) > 1 else None,
                        self.strategy,
                    ),
                ),
            )
            for k, v in batch_shapes.items()
        }
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        with mesh_context(self.mesh):
            return self._step.lower(self.params, self.opt_state, batch, rng)

    def run(self, batch: Dict, sharded: bool = False):
        if self.abstract:
            raise RuntimeError(
                "TrainStep(abstract=True) holds no real parameters; "
                "use lower() for AOT compilation, or rebuild without "
                "abstract for execution")
        # spans on the profiler's clock (observability/spans.py): a
        # flag check each when no trace is running
        tokens = _batch_tokens(batch)
        with jax.profiler.StepTraceAnnotation(
                "pt.train.step", step_num=self.step_count + 1,
                tokens=tokens):
            return self._run(batch, sharded, tokens)

    def _run(self, batch: Dict, sharded: bool, tokens: int):
        tel = self.telemetry
        bench = bool(flags.flag("benchmark"))
        t0 = time.perf_counter() if tel is not None or bench else 0.0
        t_ns = time.time_ns() if tel is not None else 0
        if not sharded:
            with jax.profiler.TraceAnnotation("pt.train.shard_batch"):
                batch = self.shard_batch(batch)
        self._rng_key, sub = jax.random.split(self._rng_key)
        gnorm = counters = None
        t1 = time.perf_counter() if tel is not None else 0.0
        # a dispatch that traced, compiled or loaded a program says so
        host = observability.train.HOST_EVENTS
        compiles, compile_ms = host["compiles"], host["compile_ms"]
        with jax.profiler.TraceAnnotation("pt.train.dispatch") as span, \
                mesh_context(self.mesh):
            if self._emit_counters:
                self.params, self.opt_state, loss, gnorm, counters = \
                    self._step(self.params, self.opt_state, batch, sub)
            elif self._emit_gnorm:
                self.params, self.opt_state, loss, gnorm = self._step(
                    self.params, self.opt_state, batch, sub
                )
            else:
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, batch, sub
                )
            if host["compile_ms"] != compile_ms:
                span.set_metadata(
                    compiles=host["compiles"] - compiles,
                    compile_ms=host["compile_ms"] - compile_ms)
        t2 = time.perf_counter() if tel is not None else 0.0
        self.step_count += 1
        if bench or flags.flag("check_nan_inf"):
            # debug knobs — BOTH force a host sync on the step's
            # outputs, which is their documented cost (the telemetry
            # path below never syncs off-sample; these flags exist for
            # the runs where per-step truth beats throughput)
            loss_f = float(jnp.asarray(loss))
            gnorm_f = (float(jnp.asarray(gnorm))
                       if gnorm is not None else None)
            if bench:
                wall_ms = (time.perf_counter() - t0) * 1e3
                print(f"[pt-benchmark] step {self.step_count}: "
                      f"{wall_ms:.2f} ms  loss={loss_f:.6g}"
                      + (f"  grad_norm={gnorm_f:.6g}"
                         if gnorm_f is not None else ""),
                      flush=True)
            if flags.flag("check_nan_inf"):
                import math as _math

                if gnorm_f is None and not self._emit_gnorm:
                    # flag flipped on AFTER build: output arity is a
                    # compile-time shape, so only loss is checkable —
                    # say so once instead of silently half-checking
                    if not getattr(self, "_warned_nan_loss_only", False):
                        self._warned_nan_loss_only = True
                        import warnings

                        warnings.warn(
                            "PT_FLAGS_check_nan_inf was enabled after "
                            "this TrainStep was built: grad-norm is "
                            "not emitted, so only the loss is checked "
                            "— rebuild the TrainStep to check "
                            "gradients too", stacklevel=2)
                bad = [n for n, v in (("loss", loss_f),
                                      ("grad_norm", gnorm_f))
                       if v is not None and not _math.isfinite(v)]
                if bad:
                    raise FloatingPointError(
                        f"PT_FLAGS_check_nan_inf: non-finite "
                        f"{'/'.join(bad)} at step {self.step_count} "
                        f"(loss={loss_f}, grad_norm={gnorm_f})")
        if tel is not None:
            # loss/gnorm stay async device futures unless this is a
            # sampled step (TrainTelemetry fetches them only then)
            tel.on_step(
                self.step_count, loss, gnorm, tokens=tokens,
                wall_s=time.perf_counter() - t0, counters=counters,
                t_ns=t_ns, shard_s=t1 - t0, dispatch_s=t2 - t1)
        with jax.profiler.TraceAnnotation("pt.train.sync_to_model"):
            if not self._master_dtypes:
                self.sync_to_model()
            else:
                # master_only: skip the write-back ONLY for
                # master-backed params (re-materializing them defeats
                # the mode; call sync_to_model() explicitly before
                # eval/export). Carried params (fp32, no master) were
                # donated and MUST be rebound or their Parameters point
                # at deleted buffers.
                for n in self.params:
                    self._param_objs[n].value = self.params[n]
        if self.optimizer._lr_scheduler is not None:
            self.optimizer._lr_scheduler.step()
        return loss

    def _materialized_params(self):
        """Full param dict at model dtype; in master_only mode the
        dropped copies are cast back from the fp32 masters on demand."""
        params = dict(self.params)
        for n, dt in self._master_dtypes.items():
            params[n] = self.opt_state["master"][n].astype(dt)
        return params

    def sync_to_model(self):
        """Write the (sharded) param values back into the Layer tree."""
        for n, p in self._param_objs.items():
            if n in self._master_dtypes:
                p.value = self.opt_state["master"][n].astype(
                    self._master_dtypes[n])
            elif n in self.params:
                p.value = self.params[n]

    def state_dict(self):
        return {
            "params": self._materialized_params(),
            "opt_state": self.opt_state,
            "step": self.step_count,
        }

    def set_state_dict(self, sd):
        # merge, don't replace: a partial restore must not wipe params
        # absent from sd (the carried-params pytree has to keep matching
        # the compiled step's structure)
        new_params = dict(self.params)
        for n, v in sd["params"].items():
            if n not in self._master_dtypes:
                new_params[n] = jax.device_put(v, self.param_shardings[n])
        self.params = new_params
        if "opt_state" not in sd and self.opt_state.get("master"):
            # params-only restore with live fp32 masters (either mode):
            # the masters are what the next update reads — refresh them
            # or the restore is silently overwritten on the first step
            new_master = dict(self.opt_state["master"])
            for n in new_master:
                if n in sd["params"]:
                    new_master[n] = jax.device_put(
                        jnp.asarray(sd["params"][n]).astype(jnp.float32),
                        self.state_shardings["master"][n])
            self.opt_state = {**self.opt_state, "master": new_master}
        if "opt_state" in sd:
            self.opt_state = jax.device_put(
                sd["opt_state"], self.state_shardings
            )
        # a checkpoint round-trip returns 'step' as a 0-d array; keep the
        # counter a python int (log lines, ckpt filenames format it)
        self.step_count = int(sd.get("step", 0))


def build_train_step(model, optimizer, mesh, strategy=None, loss_fn=None,
                     **kw) -> TrainStep:
    return TrainStep(model, optimizer, mesh, strategy, loss_fn, **kw)
