"""Pipeline parallelism.

Parity: fleet/meta_parallel/pipeline_parallel.py (``PipelineParallel``
1F1B / F-then-B schedules), pp_layers.py (``PipelineLayer`` /
``LayerDesc`` segmentation + seg_method cost balancing),
pp_utils/p2p_communication.py (send/recv with shape-header protocol),
and the C++ FleetExecutor actor runtime that orchestrates static PP
(paddle/fluid/distributed/fleet_executor/).

TPU-native design: a *single SPMD program*. Stage parameters are stacked
on a leading [pp] dim sharded over the "pp" mesh axis; microbatches march
through stages with ``jax.lax.ppermute`` rotations inside a
``shard_map`` over the pp axis only (tp/fsdp/sep stay with GSPMD via
auto axes). There is no p2p protocol code because activations never
leave the compiled program.

Two schedules, selected by ``strategy.pipeline_configs.schedule_mode``:

- **F-then-B** (GPipe): ``pipeline_apply`` — one scanned loop of
  ``n_micro + pp - 1`` ticks; autodiff through the shard_map yields the
  reverse-rotation backward. Residual memory ∝ n_micro (each stage
  stashes every microbatch's boundary activation for the global backward
  phase), mitigated by ``jax.checkpoint``.
- **1F1B** (+interleaved VPP): ``pipeline_1f1b_step`` — forward AND
  backward live inside one scanned loop of paired F/B ticks, so a
  microbatch's backward starts as soon as its forward leaves the last
  (virtual) stage. Residuals (stage inputs; internals are recomputed at
  backward, the reference's remat policy) live in a ring buffer of
  2·(V−1−v) slots per virtual stage — peak activation memory ∝ pp·vpp,
  INDEPENDENT of n_micro, the property that lets gradient accumulation
  scale. The schedule: F of virtual stage v, microbatch f fires at pair
  tick v+f; B of (v, b) at pair tick 2(V−1)−v+b — the lockstep-SPMD form
  of the reference's 1F1B steady state (fleet pipeline_parallel.py).
  VPP: V = vpp·pp virtual stages placed round-robin (virtual stage v on
  device v mod pp — Megatron/fleet interleaved placement), activations
  lap the ring vpp times; each device holds vpp param chunks and runs
  one F and one B chunk-unit per lap per tick.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast as _pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import initializer as I
from ..core.module import Layer


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    n_micro: int,
    axis: str = "pp",
    remat: bool = True,
):
    """Run ``y = stage_{pp-1}(...stage_0(x))`` pipelined over microbatches.

    stage_fn(params_slice, x_mb) -> y_mb — one stage's compute; activations
    must keep the same shape/dtype across stages (transformer trunk).
    stage_params: pytree whose leaves have leading dim pp (sharded P("pp")).
    x: [n_micro, mb, ...] microbatched input (replicated over pp).
    """
    pp = mesh.shape[axis]
    total_ticks = n_micro + pp - 1

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    def per_stage(params, xs):
        # inside shard_map: params leaves have leading dim 1 (this stage's
        # slice); xs: [1, n_micro, mb, ...] — real data only on stage 0
        # (other stages' blocks are the zero padding added below), so the
        # input is never all-gathered/replicated across pp
        stage = jax.lax.axis_index(axis)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        xs = xs[0]
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf = carry  # activation arriving at this stage this tick
            # stage 0 ingests microbatch t (if in range); others take buf
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False),
                buf,
            )
            out = body(my_params, inp)
            # rotate stage i → i+1 (last stage's output falls off the ring)
            nxt = jax.lax.ppermute(
                out, axis, [(i, i + 1) for i in range(pp - 1)]
            )
            # last stage emits its result at ticks [pp-1, total)
            emit = jnp.where(
                stage == pp - 1,
                out,
                jnp.zeros_like(out),
            )
            return nxt, emit

        # mark the carry as pp-varying so scan's carry types line up with
        # the ppermute output
        init = _pcast(
            jnp.zeros((*mb_shape,), xs.dtype), axis, to="varying"
        )
        _, emits = jax.lax.scan(
            tick, init, jnp.arange(total_ticks)
        )  # emits: [total_ticks, mb, ...] (nonzero only on last stage)
        # keep the last n_micro ticks' outputs. No psum: only the last
        # stage's block is real, and the caller slices exactly that block
        # out of the pp-stacked output — zero broadcast traffic.
        return emits[pp - 1:]

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    # stage-0-only input: block 0 is the real data, blocks 1..pp-1 are
    # zeros that only exist to give shard_map a pp-divisible leading dim
    # (each non-0 stage receives a zero block, not a replica)
    xs_blocks = jnp.concatenate(
        [x[None], jnp.zeros((pp - 1, *x.shape), x.dtype)], axis=0
    )
    fn = shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_params, P(axis)),
        out_specs=P(axis),
        axis_names={axis},
    )
    ys = fn(stage_params, xs_blocks)  # [pp * n_micro, mb, ...] stacked
    return ys[(pp - 1) * n_micro:]


# ---------------------------------------------------------------------------
# 1F1B (+ interleaved VPP) — forward and backward in one scanned schedule
# ---------------------------------------------------------------------------
def pipeline_1f1b_step(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    first_params: Any,
    stage_params: Any,
    last_params: Any,
    x_mbs: Any,
    aux_mbs: Any,
    *,
    mesh: Mesh,
    axis: str = "pp",
    vpp: int = 1,
):
    """One pipelined loss+grad evaluation under the 1F1B schedule.

    - ``first_fn(first_params, x_mb) -> h`` — stage-0 prologue (embedding);
      raw per-microbatch inputs (token ids) are replicated over pp (cheap:
      they are int ids, ~1000x smaller than activations — activations
      themselves never replicate).
    - ``stage_fn(chunk_params, h) -> h`` — one VIRTUAL stage (chunk) of the
      trunk; activations keep shape/dtype across chunks.
    - ``last_fn(last_params, y_mb, aux_mb) -> scalar`` — head + loss
      (mean over the microbatch), evaluated on the last stage the tick a
      microbatch's forward completes; its dy feeds backward immediately.
    - ``stage_params``: pytree with leading dim V = vpp*pp (virtual-stage
      order). Virtual stage v lives on device ``v % pp`` (interleaved
      round-robin — Megatron/fleet VPP placement), so each device holds
      ``vpp`` chunks.
    - ``x_mbs``/``aux_mbs``: pytrees with leading dim n_micro.

    Returns ``(loss_mean, dfirst, dstage, dlast)`` where grads are summed
    over microbatches (divide by n_micro for the mean-loss convention —
    done here so the result matches grad-of-mean).

    Memory: each virtual stage v keeps a ring of 2(V−1−v)+1 saved stage
    INPUTS (internals recomputed at backward); peak ∝ pp·vpp,
    independent of n_micro — the 1F1B property. Schedule (pair tick τ):
    F(v, f) at τ = v + f; B(v, b) at τ = 2(V−1) − v + b. Dependencies:
    F(v−1, f) at τ−1; B(v+1, b) at τ−1; B(V−1, b) in the same tick as
    F(V−1, b).
    """
    pp = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stage_params)
    V = leaves[0].shape[0] if leaves else pp * vpp
    if V != pp * vpp:
        raise ValueError(
            f"stage_params leading dim {V} != pp*vpp = {pp}*{vpp}")
    n_micro = jax.tree_util.tree_leaves(x_mbs)[0].shape[0]
    T = n_micro + 2 * (V - 1)
    R = max(2 * V, 1)  # residual ring slots (≥ max in-flight 2(V-1)+1)

    # virtual-stage order [V, ...] -> device-major [pp, vpp, ...]
    dev_major = jax.tree_util.tree_map(
        lambda p: p.reshape(vpp, pp, *p.shape[1:]).swapaxes(0, 1),
        stage_params,
    )

    x0 = jax.tree_util.tree_map(lambda a: a[0], x_mbs)
    h_sds = jax.eval_shape(first_fn, first_params, x0)

    def per_device(sp, fp, lp, xs, auxs):
        s_idx = jax.lax.axis_index(axis)
        # fp/lp arrive pp-invariant; vjp of an invariant input against a
        # varying output would insert an implicit psum over pp, polluting
        # each device's cotangent with every OTHER device's (masked-out)
        # phantom contribution. Cast to varying so cotangents stay
        # per-device; the caller slices the real device's block.
        fp = jax.tree_util.tree_map(
            lambda p: _pcast(p, (axis,), to="varying"), fp)
        lp = jax.tree_util.tree_map(
            lambda p: _pcast(p, (axis,), to="varying"), lp)
        chunks = jax.tree_util.tree_map(lambda p: p[0], sp)  # [vpp, ...]

        def chunk_params(c):
            return jax.tree_util.tree_map(lambda p: p[c], chunks)

        def vary(x):
            # scan carries become pp-varying through the ppermute/axis_index
            # data flow; the zero-init must carry the same vma type.
            # Idempotent: already-varying values pass through.
            if axis in jax.typeof(x).vma:
                return x
            return _pcast(x, (axis,), to="varying")

        zero_h = vary(jnp.zeros(h_sds.shape, h_sds.dtype))
        carry0 = {
            "fbuf": [zero_h for _ in range(vpp)],
            "bbuf": [zero_h for _ in range(vpp)],
            "res": [vary(jnp.zeros((R, *h_sds.shape), h_sds.dtype))
                    for _ in range(vpp)],
            "dstage": [jax.tree_util.tree_map(jnp.zeros_like, chunk_params(c))
                       for c in range(vpp)],
            "dfirst": jax.tree_util.tree_map(
                lambda p: vary(jnp.zeros_like(p)), fp),
            "dlast": jax.tree_util.tree_map(
                lambda p: vary(jnp.zeros_like(p)), lp),
            "loss_sum": vary(jnp.zeros((), jnp.float32)),
        }

        def take_mb(tree, i):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False),
                tree,
            )

        def macc(acc, g, active):
            return jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(active, b, 0).astype(a.dtype),
                acc, g,
            )

        def tick(carry, t):
            fbuf, bbuf = carry["fbuf"], carry["bbuf"]
            res, dstage = carry["res"], carry["dstage"]
            dfirst, dlast = carry["dfirst"], carry["dlast"]
            loss_sum = carry["loss_sum"]

            # embedding for the microbatch entering v=0 this tick
            f0 = jnp.clip(t, 0, n_micro - 1)
            a_embed = first_fn(fp, take_mb(xs, f0))

            f_out = [None] * vpp
            b_out = [None] * vpp
            dy_stash = zero_h
            new_fbuf, new_bbuf, new_res = list(fbuf), list(bbuf), list(res)
            new_dstage = list(dstage)

            for c in range(vpp):
                v = c * pp + s_idx  # traced (device-dependent)
                params_c = chunk_params(c)

                # ---- F slot ----
                f = t - v
                active_f = (f >= 0) & (f < n_micro)
                fsafe = jnp.clip(f, 0, n_micro - 1)
                a_in = jnp.where(v == 0, a_embed, fbuf[c])
                slot_f = fsafe % R
                new_res[c] = jnp.where(
                    active_f,
                    jax.lax.dynamic_update_index_in_dim(
                        new_res[c], a_in, slot_f, 0),
                    new_res[c],
                )
                out_f = stage_fn(params_c, a_in)
                f_out[c] = out_f

                # last virtual stage: head+loss now; dy feeds B this tick.
                # v == V-1 requires c == vpp-1 (v = c*pp + s, s < pp), so
                # the head forward+VJP — the vocab-size matmul, usually
                # the most expensive per-tick op — is built ONLY for the
                # final lap, not masked-out for every lap.
                if c == vpp - 1:
                    is_last_v = v == V - 1
                    aux_f = take_mb(auxs, fsafe)
                    loss_f, head_vjp = jax.vjp(
                        lambda lp_, y_: last_fn(lp_, y_, aux_f), lp, out_f)
                    ct_one = _pcast(jnp.ones((), loss_f.dtype),
                                           (axis,), to="varying")
                    dlast_f, dy_f = head_vjp(ct_one)
                    keep = active_f & is_last_v
                    loss_sum = loss_sum + jnp.where(
                        keep, loss_f, 0.0).astype(jnp.float32)
                    dlast = macc(dlast, dlast_f, keep)
                    dy_stash = jnp.where(is_last_v, dy_f, dy_stash)

                # ---- B slot ----
                b = t - (2 * (V - 1) - v)
                active_b = (b >= 0) & (b < n_micro)
                bsafe = jnp.clip(b, 0, n_micro - 1)
                # dy feeds B only where v can be V-1 (the final lap)
                ct_in = (jnp.where(v == V - 1, dy_stash, bbuf[c])
                         if c == vpp - 1 else bbuf[c])
                a_saved = jax.lax.dynamic_index_in_dim(
                    new_res[c], bsafe % R, 0, keepdims=False)
                _, stage_vjp = jax.vjp(stage_fn, params_c, a_saved)
                dp_c, da = stage_vjp(ct_in)
                new_dstage[c] = macc(new_dstage[c], dp_c, active_b)

                # v == 0 (only possible on lap 0): backprop through the
                # prologue (embedding scatter-grad built once, not per lap)
                if c == 0:
                    _, first_vjp = jax.vjp(first_fn, fp, take_mb(xs, bsafe))
                    dfirst_b, _ = first_vjp(da)
                    dfirst = macc(dfirst, dfirst_b, active_b & (v == 0))
                b_out[c] = da

            # ---- rotations ----
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
            bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
            f_stack = jnp.stack(f_out)  # [vpp, ...]
            b_stack = jnp.stack(b_out)
            f_rot = jax.lax.ppermute(f_stack, axis, fwd_perm)
            b_rot = jax.lax.ppermute(b_stack, axis, bwd_perm)
            # wraparound lap shift: device 0 receives lap c data into
            # lap c+1 slots (fwd); device pp-1 receives lap c into c-1
            # (bwd). Lap 0 @ device 0 / lap vpp-1 @ device pp-1 take the
            # embed / dy paths instead, so their stale values are unused.
            f_shift = jnp.roll(f_rot, 1, axis=0)
            b_shift = jnp.roll(b_rot, -1, axis=0)
            f_next = jnp.where(s_idx == 0, f_shift, f_rot)
            b_next = jnp.where(s_idx == pp - 1, b_shift, b_rot)
            for c in range(vpp):
                new_fbuf[c] = f_next[c]
                new_bbuf[c] = b_next[c]

            return {
                "fbuf": new_fbuf, "bbuf": new_bbuf, "res": new_res,
                "dstage": new_dstage, "dfirst": dfirst, "dlast": dlast,
                "loss_sum": loss_sum,
            }, None

        final, _ = jax.lax.scan(tick, carry0, jnp.arange(T))

        inv = 1.0 / n_micro  # mean-loss convention
        dstage_local = jax.tree_util.tree_map(
            lambda *gs: jnp.stack(gs) * inv, *final["dstage"]
        )  # [vpp, ...]
        dfirst_out = jax.tree_util.tree_map(
            lambda g: (g * inv)[None], final["dfirst"])
        dlast_out = jax.tree_util.tree_map(
            lambda g: (g * inv)[None], final["dlast"])
        loss_out = (final["loss_sum"] * inv)[None]
        dstage_out = jax.tree_util.tree_map(
            lambda g: g[None], dstage_local)  # [1, vpp, ...] for P(axis)
        return loss_out, dfirst_out, dstage_out, dlast_out

    spec_sp = jax.tree_util.tree_map(lambda _: P(axis), dev_major)
    repl = jax.tree_util.tree_map(lambda _: P(), first_params)
    repl_l = jax.tree_util.tree_map(lambda _: P(), last_params)
    repl_x = jax.tree_util.tree_map(lambda _: P(), x_mbs)
    repl_a = jax.tree_util.tree_map(lambda _: P(), aux_mbs)
    out_spec = (
        P(axis),
        jax.tree_util.tree_map(lambda _: P(axis), first_params),
        jax.tree_util.tree_map(lambda _: P(axis), dev_major),
        jax.tree_util.tree_map(lambda _: P(axis), last_params),
    )
    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(spec_sp, repl, repl_l, repl_x, repl_a),
        out_specs=out_spec,
        axis_names={axis},
    )
    loss_st, dfirst_st, dstage_st, dlast_st = fn(
        dev_major, first_params, last_params, x_mbs, aux_mbs)
    # loss/dlast are real only on the last device's block; dfirst on the
    # first's — slice, never broadcast
    loss = loss_st[-1]
    dfirst = jax.tree_util.tree_map(lambda g: g[0], dfirst_st)
    dlast = jax.tree_util.tree_map(lambda g: g[-1], dlast_st)
    dstage = jax.tree_util.tree_map(
        lambda g: g.swapaxes(0, 1).reshape(V, *g.shape[2:]), dstage_st
    )
    return loss, dfirst, dstage, dlast


class SegmentPlan:
    """A concrete stage/chunk partition of an L-layer trunk.

    The stacked-parameter SPMD trunk stores all L layers on one leading
    dim; lockstep ticks need every stage to scan the SAME number of
    slots. A non-uniform partition (cost-balanced, or just L % parts
    != 0) is realized by PADDING each chunk to M = max chunk size:

    - ``pad_idx`` [parts, M]: gather indices into the logical [L] stack.
      Real slot j < size_c maps to layer bounds[c]+j; padding slots
      repeat the chunk's last layer (finite compute, output discarded).
    - inside the scan, slot j applies its layer only when j < n_active
      (``jnp.where`` to the carried activation otherwise), so padded
      slots are exact no-ops forward AND backward (zero cotangent).
    - ``unpad_idx`` [L]: positions of the real slots in the flattened
      [parts*M] padded stack — the transpose mapping for gradients. The
      duplicated padding indices receive only zeros under scatter-add,
      so gather(grads, unpad_idx) is exact.

    Parity: fleet pp_layers ``segment_layers`` with seg_method
    "layer:.*" / cost_fn — the reference assigns whole layers to stages
    (naturally ragged); here raggedness becomes masked padding because
    stages march in SPMD lockstep.
    """

    def __init__(self, costs, parts: int):
        import numpy as np

        self.bounds = segment_layers(costs, parts)
        self.parts = parts
        self.sizes = [b - a for a, b in
                      zip(self.bounds, self.bounds[1:])]
        self.M = max(self.sizes)
        self.uniform = min(self.sizes) == self.M
        L = self.bounds[-1]
        pad = np.zeros((parts, self.M), np.int32)
        unpad = np.zeros((L,), np.int32)
        for c, (a, s) in enumerate(zip(self.bounds, self.sizes)):
            for j in range(self.M):
                pad[c, j] = a + min(j, s - 1)
            for j in range(s):
                unpad[a + j] = c * self.M + j
        self.pad_idx = pad
        self.unpad_idx = unpad
        self.sizes_f32 = np.asarray(self.sizes, np.float32)

    def pack(self, tree):
        """Logical [L, ...] stacked leaves → padded [parts, M, ...] with
        a ``__n_active__`` [parts] leaf for the in-scan mask. Uniform
        plans reshape (no gather, no mask leaf) — the existing fast
        path."""
        if self.uniform:
            return jax.tree_util.tree_map(
                lambda v: v.reshape(self.parts, self.M, *v.shape[1:]),
                tree)
        out = jax.tree_util.tree_map(lambda v: v[self.pad_idx], tree)
        out["__n_active__"] = jnp.asarray(self.sizes_f32)
        return out

    def unpack_grads(self, tree):
        """Padded [parts, M, ...] grads → logical [L, ...] (drops the
        ``__n_active__`` cotangent)."""
        if self.uniform:
            return jax.tree_util.tree_map(
                lambda v: v.reshape(self.parts * self.M, *v.shape[2:]),
                tree)
        return {
            k: v.reshape(self.parts * self.M,
                         *v.shape[2:])[self.unpad_idx]
            for k, v in tree.items() if k != "__n_active__"
        }


def masked_chunk_scan(apply_one, chunk_params, h):
    """Scan ``apply_one`` over a chunk's stacked layer params, honoring
    the plan's padding mask: slot j is an exact identity (forward and
    backward) when j >= chunk_params["__n_active__"]. Without the mask
    leaf this is a plain scan (uniform plans)."""
    n_act = chunk_params.get("__n_active__") \
        if isinstance(chunk_params, dict) else None
    if n_act is None:
        def one(carry, lp):
            return apply_one(lp, carry), None

        out, _ = jax.lax.scan(one, h, chunk_params)
        return out
    weights = {k: v for k, v in chunk_params.items()
               if k != "__n_active__"}
    M = next(iter(weights.values())).shape[0]

    def one(carry, xs):
        j, lp = xs
        out = apply_one(lp, carry)
        return jnp.where(j < n_act, out, carry), None

    out, _ = jax.lax.scan(
        one, h, (jnp.arange(M, dtype=jnp.float32), weights))
    return out


def segment_layers(costs, num_stages: int):
    """Cost-balanced contiguous segmentation (parity: fleet pp_layers
    ``segment_layers`` with seg_method="layer:.*"/"uniform" — here the
    general balanced-partition form): split ``costs`` into
    ``num_stages`` contiguous groups minimizing the max group cost.
    Returns stage boundary indices [0, b1, ..., L]."""
    costs = list(costs)
    L = len(costs)
    if num_stages <= 0 or L < num_stages:
        raise ValueError(f"cannot split {L} layers into {num_stages} stages")
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def greedy(cap):
        """Fill stages up to ``cap`` each (always leaving ≥1 layer per
        remaining stage). Returns bounds or None if infeasible."""
        bounds = [0]
        i = 0
        for stage in range(num_stages):
            start = i
            last_possible = L - (num_stages - stage - 1)
            while (i < last_possible
                   and (prefix[i + 1] - prefix[start] <= cap or i == start)):
                i += 1
            bounds.append(i)
        return bounds if bounds[-1] == L else None

    lo, hi = max(costs), prefix[-1]
    for _ in range(60):  # binary search the bottleneck stage cost
        mid = (lo + hi) / 2
        if greedy(mid) is not None:
            hi = mid
        else:
            lo = mid
    return greedy(hi)


class LayerDesc:
    """Parity: fleet LayerDesc — a deferred layer constructor."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Parity: fleet SharedLayerDesc — tied weights across stages (e.g.
    embedding/lm-head). All descs with the same ``key`` resolve to ONE
    built layer (one parameter set); a later occurrence may override the
    call with ``forward_func(layer, x)`` (the fleet convention for
    reusing the embedding matrix as the lm head). In the SPMD pipeline
    tied layers live outside the pipelined trunk (pre/post segments), so
    the shared parameter is one array with grads summed from both uses —
    no cross-stage weight sync step is needed (the reference needs an
    explicit allreduce between the tied stages)."""

    def __init__(self, key, layer_cls, *args, forward_func=None, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.key = key
        self.forward_func = forward_func


class PipelineLayer(Layer):
    """Parity: fleet PipelineLayer — segments a homogeneous trunk of
    LayerDescs into pp stages with layers_per_stage chunks each.

    TPU-native storage: ONE prototype layer defines the per-layer pytree;
    parameters for all L layers are stacked on a leading [L] dim
    (spec ("pp",) + the prototype's own spec shifted right), giving XLA
    the stacked layout pipeline_apply needs with zero copying.

    forward(x, n_micro) runs the pipelined trunk when a mesh with pp>1 is
    active, else a plain sequential scan (identical numerics).
    """

    def __init__(self, layer_desc: LayerDesc, num_layers: int,
                 num_stages: Optional[int] = None, seg_method="uniform",
                 costs=None):
        super().__init__()
        self.num_layers = num_layers
        self.num_stages = num_stages
        # per-layer costs for seg balancing (PipelineModule sets these
        # from cost_fn / seg_method); None → uniform
        self.costs = list(costs) if costs is not None else None
        self._plan_cache = {}
        self.prototype = layer_desc.build()
        # stack per-layer params: [L, *shape]
        protos = list(self.prototype.named_parameters())
        import numpy as np

        from ..core import random as random_mod
        from ..core.parameter import Parameter

        self._stacked_names = []
        for name, p in protos:
            init = p.init_fn or I.XavierNormal()
            if isinstance(p.value, jax.ShapeDtypeStruct):
                # meta-initialized prototype (core.meta.meta_init): the
                # stacked trunk stays abstract — 80×70B-scale layers
                # describable without allocating a byte (AOT memory
                # planning path)
                stacked = jax.ShapeDtypeStruct(
                    (num_layers,) + tuple(p.value.shape), p.value.dtype)
            else:
                vals = [p.value]
                for _ in range(num_layers - 1):
                    key = random_mod.next_rng_key("params")
                    vals.append(init(key, p.shape, p.dtype))
                stacked = jnp.stack(vals, axis=0)
            spec = ("pp",) + tuple(
                p.spec if p.spec is not None else [None] * p.ndim
            )
            flat = name.replace(".", "__")
            self.add_parameter(
                flat, Parameter(stacked, name=flat, spec=spec)
            )
            self._stacked_names.append((flat, name))

    def stage_params(self):
        return {flat: self._parameters[flat].value
                for flat, _ in self._stacked_names}

    def _apply_one(self, layer_params, x):
        """Run the prototype with one layer's params bound."""
        from ..core.functional import bind_params

        unflat = {orig: layer_params[flat]
                  for flat, orig in self._stacked_names}
        with bind_params(self.prototype, unflat):
            return self.prototype(x)

    def forward(self, x, n_micro: int = 1, mesh: Optional[Mesh] = None):
        from .sharding import current_mesh

        mesh = mesh or current_mesh()
        params = self.stage_params()
        pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        if mesh is not None and pp > 1:
            if pp not in self._plan_cache:
                self._plan_cache[pp] = SegmentPlan(
                    self.costs or [1.0] * self.num_layers, pp)
            plan = self._plan_cache[pp]

            def stage_fn(stage_params, mb):
                return masked_chunk_scan(self._apply_one,
                                         stage_params, mb)

            # leading dim [L] -> padded [pp, M] (reshape when uniform)
            stacked = plan.pack(params)
            if x.shape[0] % n_micro == 0:
                mbs = x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])
            else:
                raise ValueError("batch not divisible by n_micro")
            ys = pipeline_apply(
                stage_fn, stacked, mbs, mesh=mesh, n_micro=n_micro
            )
            return ys.reshape(x.shape[0], *ys.shape[2:])
        # sequential fallback — same math, no pipeline
        def one(h, layer_params):
            return self._apply_one(layer_params, h), None

        h, _ = jax.lax.scan(one, x, params)
        return h


class PipelineModule(Layer):
    """Parity: fleet pp_layers.PipelineLayer taking a heterogeneous
    ``LayerDesc`` list — e.g. ``[SharedLayerDesc("embed", Embedding, ...),
    LayerDesc(Block, ...) * L, LayerNorm, SharedLayerDesc("embed", ...,
    forward_func=...)]``.

    TPU-native segmentation: the maximal homogeneous run of descs becomes
    the pipelined trunk (stacked params, SPMD ring — ``PipelineLayer``
    storage); everything before/after runs on the first/last (virtual)
    stage under plain GSPMD. ``segment_layers`` balances trunk layers per
    stage by cost. SharedLayerDescs with equal keys build once — tied
    parameters are genuinely one array.
    """

    def __init__(self, descs, num_stages: Optional[int] = None,
                 seg_method: str = "uniform", cost_fn=None):
        super().__init__()
        if seg_method != "uniform" and not seg_method.startswith("layer:"):
            raise ValueError(
                f"seg_method={seg_method!r}: expected 'uniform' or "
                "'layer:<regex>' (fleet pp_layers convention)")
        self.num_stages = num_stages
        self._shared = {}
        self._shared_fwd = {}

        sig = [self._sig(d) for d in descs]
        lo, hi = self._longest_run(sig)
        if hi - lo < 2:
            raise ValueError(
                "PipelineModule needs a homogeneous run of >=2 LayerDescs "
                "to pipeline (the transformer trunk)")
        self.trunk_range = (lo, hi)
        self.pre_descs = descs[:lo]
        self.post_descs = descs[hi:]
        # per-layer costs drive cost-balanced (possibly non-uniform)
        # segmentation — realized as masked padding in the SPMD trunk
        # (SegmentPlan); fleet seg_method="layer:<regex>" counts descs
        # whose class name matches, cost_fn overrides
        if cost_fn is not None:
            self.trunk_costs = [float(cost_fn(d)) for d in descs[lo:hi]]
            if not any(self.trunk_costs):
                raise ValueError(
                    "cost_fn returned 0 for every trunk layer — the "
                    "balanced partition is degenerate")
        elif seg_method.startswith("layer:"):
            import re

            pat = re.compile(seg_method[len("layer:"):])
            self.trunk_costs = [
                1.0 if pat.search(d.layer_cls.__name__) else 0.0
                for d in descs[lo:hi]]
            if not any(self.trunk_costs):
                raise ValueError(
                    f"seg_method={seg_method!r} matches no trunk layer "
                    f"({descs[lo].layer_cls.__name__})")
        else:
            self.trunk_costs = [1.0] * (hi - lo)
        self.trunk = PipelineLayer(descs[lo], hi - lo,
                                   num_stages=num_stages,
                                   costs=self.trunk_costs)
        self.pre = [self._build(d, f"pre_{i}")
                    for i, d in enumerate(self.pre_descs)]
        self.post = [self._build(d, f"post_{i}")
                     for i, d in enumerate(self.post_descs)]
        if num_stages:
            self.segments = segment_layers(self.trunk_costs, num_stages)

    @staticmethod
    def _sig(d):
        return (d.layer_cls, repr(d.args), repr(sorted(d.kwargs.items())),
                isinstance(d, SharedLayerDesc))

    @staticmethod
    def _longest_run(sig):
        best = (0, 0)
        i = 0
        while i < len(sig):
            j = i
            while j < len(sig) and sig[j] == sig[i] and not sig[i][3]:
                j += 1
            if j - i > best[1] - best[0]:
                best = (i, j)
            i = max(j, i + 1)
        return best

    def _build(self, desc, attr):
        if isinstance(desc, SharedLayerDesc):
            if desc.key not in self._shared:
                layer = desc.build()
                self._shared[desc.key] = layer
                self.add_sublayer(f"shared_{desc.key}", layer)
            self._shared_fwd[attr] = desc.forward_func
            return ("shared", desc.key, attr)
        layer = desc.build()
        self.add_sublayer(attr, layer)
        return ("own", attr, attr)

    def _apply_seq(self, entries, x):
        for kind, key, attr in entries:
            if kind == "shared":
                layer = self._shared[key]
                fwd = self._shared_fwd.get(attr)
                x = fwd(layer, x) if fwd is not None else layer(x)
            else:
                x = getattr(self, key)(x)
        return x

    def forward(self, x, n_micro: int = 1, mesh: Optional[Mesh] = None):
        """F-then-B (GPipe) forward — pre → pipelined trunk → post.
        Backward is jax autodiff (use ``PipelineTrainStep`` for 1F1B)."""
        x = self._apply_seq(self.pre, x)
        x = self.trunk(x, n_micro=n_micro, mesh=mesh)
        return self._apply_seq(self.post, x)


class PipelineTrainStep:
    """1F1B/VPP training step over a ``PipelineModule``.

    Parity: fleet PipelineParallel.train_batch with
    ``schedule_mode="1F1B"`` / ``vpp_degree`` (strategy.pipeline_configs)
    — here one jitted SPMD program per step built on
    ``pipeline_1f1b_step``. ``schedule_mode="F-then-B"`` falls back to
    autodiff through the GPipe forward.

    loss_fn(out_mb, aux_mb) -> scalar (mean over the microbatch).
    """

    def __init__(self, module: PipelineModule, optimizer, mesh: Mesh,
                 strategy=None, loss_fn=None, abstract: bool = False):
        self.module = module
        self.optimizer = optimizer
        self.mesh = mesh
        self.strategy = strategy
        self.loss_fn = loss_fn or (lambda out, aux: out.mean())
        pcfg = getattr(strategy, "pipeline_configs", None)
        self.schedule = getattr(pcfg, "schedule_mode", "1F1B")
        self.vpp = max(1, getattr(pcfg, "vpp_degree", 1))
        self.n_micro = max(1, getattr(pcfg, "accumulate_steps", 1))
        pp = mesh.shape["pp"]
        L = module.trunk.num_layers
        # cost-balanced chunking (SegmentPlan): uniform when L divides
        # evenly and costs are flat (zero-overhead reshape), masked
        # padding otherwise — L need not divide pp*vpp
        costs = getattr(module, "trunk_costs", None) or [1.0] * L
        self._plan_v = SegmentPlan(costs, pp * self.vpp)
        self._plan_pp = SegmentPlan(costs, pp)

        # flat param dicts (optimizer-compatible)
        pre_names = self._seq_param_names(module.pre)
        post_names = self._seq_param_names(module.post)
        trunk_p = module.trunk.stage_params()
        all_params = dict(module.named_parameters())
        self.params = {}
        for n in pre_names | post_names:
            self.params[n] = all_params[n].value
        for k, v in trunk_p.items():
            self.params[f"trunk.{k}"] = v
        self._pre_names, self._post_names = pre_names, post_names
        # pp × tp/fsdp composition: place every param according to its
        # logical spec over the mesh's non-pp axes BEFORE jit — the
        # shard_map handles the pp axis manually, GSPMD propagates the
        # rest through it (the trunk's stacked leading dim carries the
        # "pp" spec entry from PipelineLayer, so trunk weights live
        # pre-sharded per stage too). With strategy.sharding stage>=3 the
        # ZeRO-3 fsdp axis is folded in exactly as TrainStep does
        # (param_partition_spec), so stage-3×tp×pp composes.
        from .sharding import _filter_spec_for_mesh, param_partition_spec

        use_zero3 = (
            strategy is not None
            and getattr(strategy, "sharding", False)
            and getattr(strategy, "sharding_stage", 0) >= 3
            and "fsdp" in mesh.shape and mesh.shape["fsdp"] > 1
        )
        self.abstract = abstract
        self.param_shardings = {}
        for n in self.params:
            # trunk params appear in named_parameters() under the same
            # "trunk.<flat>" keys stage_params() uses
            obj = all_params.get(n)
            spec = getattr(obj, "spec", None)
            if spec is None:
                spec = (None,) * jnp.ndim(self.params[n])
            active_plan = (self._plan_v if self.schedule.upper()
                           in ("1F1B", "VPP") else self._plan_pp)
            if (n.startswith("trunk.") and not active_plan.uniform
                    and tuple(spec)[:1] == ("pp",)):
                # non-uniform plan: the logical [L] stack is not
                # pp-divisible — keep it replicated on the leading dim;
                # the in-jit pack() gather lands it in the shard_map's
                # P("pp") layout
                spec = (None,) + tuple(spec)[1:]
            spec = _filter_spec_for_mesh(tuple(spec), mesh)
            if use_zero3:
                pspec = param_partition_spec(
                    n, tuple(self.params[n].shape), spec, strategy)
            else:
                pspec = P(*spec)
            sh = NamedSharding(mesh, pspec)
            self.param_shardings[n] = sh
            if abstract:
                v = self.params[n]
                self.params[n] = jax.ShapeDtypeStruct(
                    tuple(v.shape), v.dtype, sharding=sh)
            else:
                self.params[n] = jax.device_put(self.params[n], sh)
        if abstract:
            # mirror the eager path's sharding semantics: zeros_like on a
            # committed array inherits its sharding, so any state leaf
            # shaped like its parameter gets the parameter's sharding
            state_shape = jax.eval_shape(optimizer.init, self.params)

            def _attach(name, leaf):
                sh = self.param_shardings.get(name)
                if sh is not None and tuple(leaf.shape) == tuple(
                        self.params[name].shape):
                    return jax.ShapeDtypeStruct(
                        tuple(leaf.shape), leaf.dtype, sharding=sh)
                return jax.ShapeDtypeStruct(
                    tuple(leaf.shape), leaf.dtype,
                    sharding=NamedSharding(mesh, P()))

            self.opt_state = {"step": jax.ShapeDtypeStruct(
                tuple(state_shape["step"].shape), state_shape["step"].dtype,
                sharding=NamedSharding(mesh, P()))}
            self.opt_state["slots"] = {
                n: {k: _attach(n, v) for k, v in slots.items()}
                for n, slots in state_shape["slots"].items()}
            if "master" in state_shape:
                self.opt_state["master"] = {
                    n: _attach(n, v)
                    for n, v in state_shape["master"].items()}
        else:
            self.opt_state = optimizer.init(self.params)
        self._step = jax.jit(self._make_step())

    def lower(self, x_shapes, aux_shapes):
        """AOT-lower the pipelined step with abstract inputs (use with
        ``abstract=True``); ``.compile().memory_analysis()`` yields the
        per-device byte plan for configs larger than host memory."""
        from .sharding import mesh_context

        def _sds(v, shard_batch):
            entries = [None] * len(v.shape)
            if shard_batch and len(v.shape) and "dp" in self.mesh.shape:
                entries[0] = "dp"
            return jax.ShapeDtypeStruct(
                tuple(v.shape), v.dtype,
                sharding=NamedSharding(self.mesh, P(*entries)))

        x = jax.tree_util.tree_map(lambda v: _sds(v, True), x_shapes)
        aux = jax.tree_util.tree_map(lambda v: _sds(v, True), aux_shapes)
        with mesh_context(self.mesh):
            return self._step.lower(self.params, self.opt_state, x, aux)

    def _seq_param_names(self, entries):
        names = set()
        all_params = dict(self.module.named_parameters())
        for kind, key, attr in entries:
            prefix = f"shared_{key}." if kind == "shared" else f"{attr}."
            names |= {n for n in all_params if n.startswith(prefix)}
        return names

    def _make_step(self):
        module = self.module
        mesh, vpp = self.mesh, self.vpp
        pp = mesh.shape["pp"]
        V = pp * vpp
        loss_fn = self.loss_fn
        from ..core.functional import bind_params

        def first_fn(first_params, x_mb):
            with bind_params(module, first_params):
                return module._apply_seq(module.pre, x_mb)

        # strategy.recompute → per-LAYER jax.checkpoint inside the chunk
        # scan. The chunk-level remat in pipeline_1f1b_step alone is not
        # enough at scale: the chunk's backward re-materializes every
        # layer's internals at once (attention scores, MLP intermediates
        # for all layers_per_stage layers live simultaneously). Nesting a
        # checkpoint per scanned layer caps the peak at one layer's
        # internals + the chunk's layer-boundary activations — the
        # memory shape the reference's per-layer RecomputeLayer gives its
        # pipeline (fleet.meta_parallel pp_layers + recompute).
        per_layer_remat = bool(getattr(self.strategy, "recompute", False))
        apply_one = (jax.checkpoint(module.trunk._apply_one)
                     if per_layer_remat else module.trunk._apply_one)

        def stage_fn(chunk_params, h):
            # chunk leaves: [per_chunk(+pad), ...] — scan the prototype
            # over them, honoring the plan's padding mask if present
            return masked_chunk_scan(apply_one, chunk_params, h)

        def last_fn(last_params, y, aux):
            with bind_params(module, last_params):
                out = module._apply_seq(module.post, y)
            return loss_fn(out, aux)

        n_micro = self.n_micro
        schedule = self.schedule

        def step_fn(params, opt_state, x, aux):
            from .sharding import suppress_constraints

            # GSPMD activation hints inside the model body cannot apply
            # to pp-varying values in the manual shard_map region — trace
            # the whole step with hints off
            with suppress_constraints():
                return _step_body(params, opt_state, x, aux)

        def _step_body(params, opt_state, x, aux):
            first_params = {n: params[n] for n in self._pre_names}
            last_params = {n: params[n] for n in self._post_names}
            trunk_params = {
                k[len("trunk."):]: v for k, v in params.items()
                if k.startswith("trunk.")
            }
            mbs = jax.tree_util.tree_map(
                lambda a: a.reshape(n_micro, a.shape[0] // n_micro,
                                    *a.shape[1:]), x)
            aux_mbs = jax.tree_util.tree_map(
                lambda a: a.reshape(n_micro, a.shape[0] // n_micro,
                                    *a.shape[1:]), aux)
            if schedule.upper() in ("1F1B", "VPP"):
                sp = self._plan_v.pack(trunk_params)
                loss, dfirst, dstage, dlast = pipeline_1f1b_step(
                    first_fn, stage_fn, last_fn,
                    first_params, sp, last_params, mbs, aux_mbs,
                    mesh=mesh, vpp=vpp)
                grads = {}
                for n in set(dfirst) | set(dlast):
                    g = None
                    if n in dfirst:
                        g = dfirst[n]
                    if n in dlast:  # tied params: sum both uses' grads
                        g = dlast[n] if g is None else g + dlast[n]
                    grads[n] = g
                for k, v in self._plan_v.unpack_grads(dstage).items():
                    grads[f"trunk.{k}"] = v
            else:  # F-then-B: autodiff through the GPipe forward
                def loss_of(p):
                    fpp = {n: p[n] for n in self._pre_names}
                    lpp = {n: p[n] for n in self._post_names}
                    tpp = {k[len("trunk."):]: v for k, v in p.items()
                           if k.startswith("trunk.")}
                    h0 = jax.vmap(lambda xm: first_fn(fpp, xm))(mbs)
                    # stage slice leaves arrive [layers_per_stage(+pad),
                    # ...] — exactly what stage_fn's masked scan consumes
                    ys = pipeline_apply(
                        stage_fn, self._plan_pp.pack(tpp),
                        h0, mesh=mesh, n_micro=n_micro)
                    losses = jax.vmap(
                        lambda y, a: last_fn(lpp, y, a))(ys, aux_mbs)
                    return losses.mean()

                loss, grads = jax.value_and_grad(loss_of)(params)
            new_params, new_state = self.optimizer.update(
                grads, opt_state, params)
            return new_params, new_state, loss

        return step_fn

    def run(self, x, aux):
        from .sharding import mesh_context

        if self.abstract:
            raise RuntimeError(
                "PipelineTrainStep(abstract=True) holds no real "
                "parameters; use lower() for AOT compilation")
        with mesh_context(self.mesh):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, x, aux)
        return loss
