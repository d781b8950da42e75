"""The sharding engine: every Fleet parallelism strategy as GSPMD rules.

Parity map (SURVEY.md §2.2):
  - DP (paddle.DataParallel + imperative::Reducer bucketed allreduce) →
    batch-axis sharding over "dp"; XLA emits the gradient reduce and
    overlaps it with backward compute (the Reducer's whole job).
  - Sharding stage 1/2 (DygraphShardingOptimizer / GroupShardedStage2,
    fleet/meta_parallel/sharding/) → optimizer-state (and transient-grad)
    sharding over "fsdp": params stay replicated, moments/master are
    sharded; XLA inserts reduce-scatter before the update and keeps the
    weight all-gather out of it.
  - Sharding stage 3 (GroupShardedStage3: param shards, pre-forward
    allgather, post-backward release) → parameters themselves sharded
    over "fsdp"; XLA schedules the all-gather just-in-time per layer and
    frees gathered copies — the prefetch/release hooks fall out of the
    compiler's liveness analysis.
  - TP (ColumnParallelLinear etc., mp_layers.py) → per-dim "tp" entries in
    Parameter.spec (see parallel_layers/mp_layers.py here).
  - Megatron-SP (sequence_parallel_utils.py) → activation constraints
    sharding the sequence dim over "tp" between TP regions.
  - SEP/Ulysses (topology "sep" axis) → sequence dim sharded over "sep",
    all-to-all around attention (kernels/ulysses.py).

No per-parameter communication code exists anywhere: the *only* artifacts
are PartitionSpecs. That is the TPU-native translation of ~30k lines of
group-sharded python/C++ in the reference.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .strategy import DistributedStrategy

_mesh_var: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_mesh", default=None
)


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    """Make ``mesh`` the ambient mesh for shard_activation constraints.

    Must be active at *trace* time (the trainer wraps jit calls in it).
    """
    tok = _mesh_var.set(mesh)
    try:
        yield mesh
    finally:
        _mesh_var.reset(tok)


def current_mesh() -> Optional[Mesh]:
    m = _mesh_var.get()
    if m is not None:
        return m
    from .topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return hcg.mesh if hcg is not None else None

# params smaller than this stay unsharded under ZeRO-3 (parity:
# GroupShardedStage3 segment_size keeps small params whole)
MIN_SIZE_TO_SHARD = 2**13


def _normalize_logical_spec(spec, ndim) -> Tuple:
    if spec is None:
        return tuple([None] * ndim)
    spec = tuple(spec)
    if len(spec) < ndim:
        spec = spec + tuple([None] * (ndim - len(spec)))
    return spec


def _axes_used(spec) -> set:
    used = set()
    for e in spec:
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            used.update(e)
        else:
            used.add(e)
    return used


def fsdp_augment(spec: Tuple, shape, axis_name: str = "fsdp",
                 axis_size: int = 1) -> Tuple:
    """Add the fsdp axis to the best unsharded dim (prefer dim 0; prefer
    divisible dims; fall back to the largest)."""
    if axis_name in _axes_used(spec):
        return spec
    candidates = [i for i, e in enumerate(spec) if e is None and shape[i] > 1]
    if not candidates:
        # compose onto an already-sharded dim if divisible
        for i, e in enumerate(spec):
            if e is not None and shape[i] % max(axis_size, 1) == 0:
                cur = e if isinstance(e, tuple) else (e,)
                out = list(spec)
                out[i] = cur + (axis_name,)
                return tuple(out)
        return spec
    divisible = [i for i in candidates if shape[i] % max(axis_size, 1) == 0]
    pool = divisible or candidates
    dim = min(pool)  # prefer leading dim (weight rows / vocab / out_c)
    out = list(spec)
    out[dim] = axis_name
    return tuple(out)


def param_partition_spec(
    name: str,
    shape,
    logical_spec,
    strategy: DistributedStrategy,
) -> P:
    """Final PartitionSpec for a parameter array."""
    ndim = len(shape)
    spec = _normalize_logical_spec(logical_spec, ndim)
    stage = strategy.sharding_stage
    size = int(np.prod(shape)) if ndim else 1
    if stage >= 3 and strategy.fsdp > 1 and size >= MIN_SIZE_TO_SHARD:
        spec = fsdp_augment(spec, shape, "fsdp", strategy.fsdp)
    return P(*spec)


def opt_slot_partition_spec(
    name: str,
    shape,
    logical_spec,
    strategy: DistributedStrategy,
) -> P:
    """PartitionSpec for optimizer moments / master weights: sharded over
    fsdp from stage 1 up (ZeRO-1's entire point)."""
    ndim = len(shape)
    spec = _normalize_logical_spec(logical_spec, ndim)
    stage = strategy.sharding_stage
    size = int(np.prod(shape)) if ndim else 1
    if stage >= 1 and strategy.fsdp > 1 and size >= MIN_SIZE_TO_SHARD:
        spec = fsdp_augment(spec, shape, "fsdp", strategy.fsdp)
    return P(*spec)


def batch_spec(ndim: int = 2, seq_axis: Optional[int] = 1,
               strategy: Optional[DistributedStrategy] = None) -> P:
    """Input batch sharding: batch over (dp, fsdp), sequence over sep."""
    entries = [None] * ndim
    entries[0] = ("dp", "fsdp")
    if seq_axis is not None and ndim > seq_axis and (
        strategy is None or strategy.sep > 1
    ):
        entries[seq_axis] = "sep"
    return P(*entries)


def model_shardings(
    model,
    mesh: Mesh,
    strategy: DistributedStrategy,
    filter_to_mesh: bool = False,
) -> Dict[str, NamedSharding]:
    """NamedSharding per parameter (keys = qualified names).

    ``filter_to_mesh``: drop logical axes the mesh doesn't carry (the
    serving engine's placement path — the same model runs under any
    topology)."""
    out = {}
    for name, p in model.named_parameters():
        spec = param_partition_spec(name, p.shape, p.spec, strategy)
        if filter_to_mesh:
            spec = P(*_filter_spec_for_mesh(tuple(spec), mesh))
        out[name] = NamedSharding(mesh, spec)
    return out


def opt_state_shardings(optimizer, params_meta, mesh, strategy):
    """Build the sharding pytree matching Optimizer.init's state layout.

    ``params_meta``: {name: (shape, logical_spec)}.
    """
    slot_shardings = {}
    master = {}
    for name, (shape, lspec) in params_meta.items():
        spec = opt_slot_partition_spec(name, shape, lspec, strategy)
        sh = NamedSharding(mesh, spec)
        # probe slot structure with a zero-init (shapes only)
        import jax.numpy as jnp

        class _Meta:
            pass

        meta = _Meta()
        meta.shape = shape
        meta.dtype = jnp.float32
        slots = optimizer._init_slot(meta)
        slot_shardings[name] = {
            k: (sh if getattr(v, "shape", ()) == tuple(shape)
                else NamedSharding(mesh, P()))
            for k, v in slots.items()
        }
        master[name] = sh
    state_shardings = {
        "step": NamedSharding(mesh, P()),
        "slots": slot_shardings,
    }
    if optimizer.multi_precision:
        # master entries exist only for low-precision params; caller prunes
        state_shardings["master"] = master
    return state_shardings


def _filter_spec_for_mesh(spec_entries, mesh: Mesh):
    names = set(mesh.axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return e if e in names else None

    return tuple(keep(e) for e in spec_entries)


_suppress_var: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_suppress_constraints", default=False
)


@contextlib.contextmanager
def suppress_constraints():
    """Disable shard_activation hints while tracing — needed inside
    manual-axis shard_map regions (the 1F1B pipeline): a GSPMD
    with_sharding_constraint cannot be applied to a pp-varying value
    against a mesh whose pp axis is Auto-typed. Constraints are hints;
    GSPMD still propagates shardings from the operands without them."""
    tok = _suppress_var.set(True)
    try:
        yield
    finally:
        _suppress_var.reset(tok)


def constraints_suppressed() -> bool:
    """True while tracing inside a manual-axis region (see
    ``suppress_constraints``)."""
    return _suppress_var.get()


def shard_activation(x, *spec_entries):
    """with_sharding_constraint against the ambient mesh; no-op when no
    mesh is active (single-device eager use) or when constraints are
    suppressed (inside manual-axis pipeline bodies). Axis names absent
    from the mesh are dropped, so the same model code runs under any
    topology."""
    mesh = current_mesh()
    if mesh is None or _suppress_var.get():
        return x
    spec = _filter_spec_for_mesh(spec_entries, mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


def sequence_parallel_constraint(x):
    """Megatron-SP: shard [batch, seq, hidden] activations' sequence dim
    over the tp axis between TP regions (parity:
    fleet/utils/sequence_parallel_utils.py AllGather/ReduceScatter ops —
    GSPMD derives those collectives from this constraint)."""
    return shard_activation(x, ("dp", "fsdp"), ("sep", "tp"), None)


def place_params_on_mesh(model, mesh, strategy):
    """Eagerly reshard a model's parameter values onto the mesh (host →
    sharded device arrays). Parity: the initial broadcast/scatter
    DataParallel & GroupShardedStage3 do at wrap time."""
    for name, p in model.named_parameters():
        spec = param_partition_spec(name, p.shape, p.spec, strategy)
        p.value = jax.device_put(p.value, NamedSharding(mesh, spec))
    return model


def recompute(function, *args, **kwargs):
    """Parity: paddle.distributed.fleet.utils.recompute — run ``function``
    without saving intermediate activations; recompute them in backward.
    TPU-native: this IS ``jax.checkpoint`` (XLA rematerialization);
    ``use_reentrant``/``preserve_rng_state`` knobs are meaningless under
    functional RNG and accepted for signature parity."""
    kwargs.pop("use_reentrant", None)
    kwargs.pop("preserve_rng_state", None)
    return jax.checkpoint(function)(*args, **kwargs)


def group_sharded_parallel(model, optimizer, level="p_g_os", scaler=None,
                           **kw):
    """Parity: paddle.distributed.sharding.group_sharded_parallel.

    level: "os" (ZeRO-1: optimizer state), "os_g" (ZeRO-2: +grads),
    "p_g_os" (ZeRO-3: +params). The reference wraps model/optimizer in
    GroupSharded* classes; here sharding is a property of the compiled
    program, so this returns (model, optimizer, strategy) — hand the
    strategy to ``TrainStep`` (or ``fleet.distributed_model``), which
    emits the partition specs the level implies. ``scaler`` passes
    through untouched (bf16 needs no loss scaling on TPU)."""
    from .strategy import DistributedStrategy

    stage = {"os": 1, "os_g": 2, "p_g_os": 3}.get(level)
    if stage is None:
        raise ValueError(
            f"unknown group_sharded level {level!r}; one of os/os_g/p_g_os")
    strategy = DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs.stage = stage
    # fixed arity regardless of scaler — a conditional return shape is a
    # porting trap (scaler is None when not supplied)
    return model, optimizer, strategy, scaler
