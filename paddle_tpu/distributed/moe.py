"""Mixture-of-Experts with expert parallelism.

Parity: python/paddle/incubate/distributed/models/moe/ — ``MoELayer``
with GShard/Switch/Naive gates, capacity-factor dispatch, aux load-balance
loss — plus the C++ ``global_scatter``/``global_gather`` all-to-all
collective ops (paddle/fluid/operators/collective/global_scatter_op.*).

TPU-native inversion: the reference routes tokens with explicit ragged
all-to-alls. Here dispatch/combine are *static-shape einsums* against
one-hot capacity tensors (the GShard formulation, which is what maps onto
the MXU) and the expert dim of the batched expert weights is sharded over
a mesh axis — GSPMD turns the dispatch einsum into exactly the all-to-all
the reference hand-codes, overlapped by XLA.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import initializer as I
from ..core.module import Layer
from ..kernels import _backend, grouped_experts
from ..nn import functional as F
from .sharding import shard_activation


def _top2_gating(logits, capacity: int, rng_key=None):
    """GShard top-2 gating. logits: [tokens, experts] fp32.

    Returns combine [t, e, c], dispatch mask [t, e, c] (bool), aux loss,
    and the dropped-token fraction (routed assignments that exceeded
    expert capacity — the quantity the reference logs to detect
    too-small capacity_factor).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate1_idx = jnp.argmax(probs, axis=-1)  # [t]
    mask1 = jax.nn.one_hot(gate1_idx, e, dtype=probs.dtype)
    # aux load-balance loss (GShard eq.4): e * mean(density * density_proxy)
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e

    probs_wo1 = probs * (1.0 - mask1)
    gate2_idx = jnp.argmax(probs_wo1, axis=-1)
    mask2 = jax.nn.one_hot(gate2_idx, e, dtype=probs.dtype)

    # positions within each expert (cumsum over tokens)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1  # [t, e]
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2 +
            jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(probs * keep1, axis=-1)  # [t]
    g2 = jnp.sum(probs * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    routed = jnp.sum(mask1) + jnp.sum(
        mask2 * (probs_wo1.max(-1) > 0)[:, None])
    kept = jnp.sum(keep1) + jnp.sum(keep2)
    drop_fraction = 1.0 - kept / jnp.maximum(routed, 1.0)

    p1 = jnp.sum(pos1 * keep1, axis=-1).astype(jnp.int32)  # [t]
    p2 = jnp.sum(pos2 * keep2, axis=-1).astype(jnp.int32)
    cap1 = jax.nn.one_hot(p1, capacity, dtype=probs.dtype)  # [t, c]
    cap2 = jax.nn.one_hot(p2, capacity, dtype=probs.dtype)
    combine = (
        g1[:, None, None] * keep1[:, :, None] * cap1[:, None, :]
        + g2[:, None, None] * keep2[:, :, None] * cap2[:, None, :]
    )  # [t, e, c]
    dispatch = combine > 0.0
    return combine, dispatch, aux, drop_fraction


def _switch_gating(logits, capacity: int):
    """Switch-transformer top-1 gating."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)
    density = jnp.mean(mask, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e
    pos = jnp.cumsum(mask, axis=0) * mask - mask
    keep = mask * (pos < capacity)
    drop_fraction = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(mask), 1.0)
    g = jnp.sum(probs * keep, axis=-1)
    p = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)
    cap = jax.nn.one_hot(p, capacity, dtype=probs.dtype)
    combine = g[:, None, None] * keep[:, :, None] * cap[:, None, :]
    return combine, combine > 0.0, aux, drop_fraction


def relu2(x):
    """Squared ReLU, the two-matrix experts' activation."""
    return jnp.square(jax.nn.relu(x))


def _activation(name: str):
    return relu2 if name == "relu2" else getattr(F, name)


class ExpertFFN(Layer):
    """Batched expert FFN: weights [E, in, hidden], [E, hidden, in] with
    the expert dim sharded over ``expert_axis``. ``bias=False`` leaves
    out ``b1``/``b2``; ``gated=True`` adds the third matrix ``w3`` of a
    gated expert, ``act(x w3) * (x w1)`` before ``w2``; ``activation``
    names a function of ``nn.functional`` or ``relu2``."""

    def __init__(self, num_experts, d_model, d_hidden, expert_axis="ep",
                 activation="gelu", init_std=0.02, bias=True, gated=False):
        super().__init__()
        init = I.Normal(0.0, init_std)
        self.w1 = self.create_parameter(
            (num_experts, d_model, d_hidden), default_initializer=init,
            spec=(expert_axis, None, "tp"),
        )
        self.w2 = self.create_parameter(
            (num_experts, d_hidden, d_model), default_initializer=init,
            spec=(expert_axis, "tp", None),
        )
        self.w3 = self.create_parameter(
            (num_experts, d_model, d_hidden), default_initializer=init,
            spec=(expert_axis, None, "tp"),
        ) if gated else None
        self.b1 = self.create_parameter(
            (num_experts, d_hidden), is_bias=True, spec=(expert_axis, "tp")
        ) if bias else None
        self.b2 = self.create_parameter(
            (num_experts, d_model), is_bias=True, spec=(expert_axis, None)
        ) if bias else None
        self.act = _activation(activation)

    def forward(self, x):
        # x: [E, cap_total, d_model]
        h = jnp.einsum("ecm,emh->ech", x, self.w1.value)
        if self.b1 is not None:
            h = h + self.b1.value[:, None]
        if self.w3 is not None:
            h = self.act(jnp.einsum("ecm,emh->ech", x, self.w3.value)) * h
        else:
            h = self.act(h)
        y = jnp.einsum("ech,ehm->ecm", h, self.w2.value)
        return y if self.b2 is None else y + self.b2.value[:, None]


class MoELayer(Layer):
    """Parity: incubate MoELayer(gate={...}, experts=[...]).

    forward(x: [batch, seq, d_model]) -> (y, aux_loss). Stores the last
    aux loss in ``self.last_aux_loss`` for trainers that prefer the
    paddle-style side-channel.
    """

    def __init__(
        self,
        d_model: int,
        num_experts: int,
        d_hidden: Optional[int] = None,
        gate: str = "gshard",
        top_k: int = 2,
        capacity_factor: Optional[float] = None,
        expert_axis: str = "ep",
        aux_loss_weight: float = 1e-2,
    ):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.gate_type = gate
        self.top_k = 1 if gate == "switch" else top_k
        if capacity_factor is None:
            # layer default rides PT_FLAGS_moe_capacity_factor (1.25)
            from .. import flags

            capacity_factor = float(flags.flag("moe_capacity_factor"))
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        self.gate_weight = self.create_parameter(
            (d_model, num_experts),
            default_initializer=I.Normal(0.0, 0.02),
        )
        self.expert_axis = expert_axis
        self.experts = ExpertFFN(
            num_experts, d_model, d_hidden or 4 * d_model, expert_axis
        )
        self.last_aux_loss = 0.0
        self.last_drop_fraction = 0.0  # scalar jnp: routed-but-dropped share

    def capacity(self, tokens: int) -> int:
        cap = int(self.capacity_factor * tokens * self.top_k / self.num_experts)
        return max(cap, 4)

    def forward(self, x):
        b, s, m = x.shape
        tokens = b * s
        xf = x.reshape(tokens, m)
        logits = (xf.astype(jnp.float32) @
                  self.gate_weight.value.astype(jnp.float32))
        cap = self.capacity(tokens)
        if self.gate_type == "switch":
            combine, dispatch, aux, dropped = _switch_gating(logits, cap)
        else:
            combine, dispatch, aux, dropped = _top2_gating(logits, cap)
        combine = combine.astype(x.dtype)
        # dispatch: [t, e, c] x [t, m] -> [e, c, m]; GSPMD inserts the
        # token→expert all-to-all here (expert dim sharded)
        expert_in = jnp.einsum(
            "tec,tm->ecm", dispatch.astype(x.dtype), xf
        )
        expert_in = shard_activation(expert_in, self.expert_axis, None, None)
        expert_out = self.experts(expert_in)
        expert_out = shard_activation(expert_out, self.expert_axis, None, None)
        y = jnp.einsum("tec,ecm->tm", combine, expert_out)
        self.last_aux_loss = aux * self.aux_loss_weight
        self.last_drop_fraction = dropped
        return y.reshape(b, s, m), self.last_aux_loss


def _dropless_topk_gating(logits, top_k: int):
    """Top-k gating with NO capacity clamp: every routed token is
    processed. Returns (expert_idx [t, k], gates [t, k], aux)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # load-balance aux (GShard form on the top-1 assignment)
    mask1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=probs.dtype)
    aux = jnp.sum(jnp.mean(mask1, 0) * jnp.mean(probs, 0)) * e
    return expert_idx, gates, aux


def dropless_moe_apply(x, expert_idx, gates, w1, b1, w2, b2, act):
    """MegaBlocks-style dropless dispatch, TPU-native form: sort the
    (token, expert) assignments by expert and run ONE grouped matmul per
    projection via ``jax.lax.ragged_dot`` — XLA's grouped-GEMM primitive
    tiles the ragged group dim onto the MXU without materializing
    one-hot dispatch tensors or dropping overflow tokens.

    x: [t, m]; expert_idx/gates: [t, k]; w1: [E, m, h]; w2: [E, h, m].
    Parity: the reference's dropless/"no-token-dropping" MoE modes
    (incubate moe capacity_factor=None paths).
    """
    t, k = expert_idx.shape
    E = w1.shape[0]
    flat_e = expert_idx.reshape(-1)             # [t*k]
    order = jnp.argsort(flat_e)                 # stable
    inv = jnp.argsort(order)
    xs = jnp.repeat(x, k, axis=0)[order]        # [t*k, m] sorted by expert
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    h = jax.lax.ragged_dot(xs, w1, group_sizes)
    h = h + jnp.repeat(b1, group_sizes, axis=0,
                       total_repeat_length=t * k)
    h = act(h)
    y = jax.lax.ragged_dot(h, w2, group_sizes)
    y = y + jnp.repeat(b2, group_sizes, axis=0,
                       total_repeat_length=t * k)
    y = y[inv].reshape(t, k, -1)                # unsort, [t, k, m]
    return jnp.sum(y * gates[..., None].astype(y.dtype), axis=1)


def dropless_moe_ep_apply(xf, gate_weight, w1, b1, w2, b2, act, top_k,
                          mesh, ep_axis="ep"):
    """Distributed dropless dispatch over the ``ep`` mesh axis.

    Parity: the reference's ``global_scatter`` → per-expert FFN →
    ``global_gather`` pipeline (paddle/fluid/operators/collective/
    global_scatter_op.*, incubate moe) — tokens travel to the shard
    owning their expert, are processed in ONE contiguous grouped matmul,
    and travel back.

    TPU-native form (static shapes, one SPMD program):
      1. route + stable-sort local (token, k) assignments by expert id;
      2. counts → the per-destination segment sizes; a dense
         ``lax.all_to_all`` exchanges STATIC per-source slots of
         N = t_local·top_k rows — every routed token always has a seat,
         so the exchange is dropless *by construction* (the reference's
         ragged NCCL alltoallv becomes a fixed-shape ICI collective;
         ``lax.ragged_all_to_all`` sends only the filled prefixes and is
         the drop-in TPU bandwidth upgrade, but XLA:CPU has no kernel
         for it, and CI runs on the CPU mesh);
      3. received rows re-sort into per-local-expert contiguous groups →
         ``lax.ragged_dot`` (padding rows ride a zero-weight dummy
         expert);
      4. reverse all_to_all returns outputs to the source's sorted
         positions; unsort; combine with gates.

    xf: [t, m] with the token dim sharded over ``ep_axis`` (t % ep == 0);
    w1/b1/w2/b2: [E, ...] sharded over ``ep_axis`` on the expert dim.
    Mesh axes other than ``ep_axis`` stay under GSPMD (shard_map
    ``axis_names``), so EP composes with dp/fsdp/tp.
    Returns (y [t, m], aux scalar) with aux computed from GLOBAL routing
    statistics (pmean over ep).
    """
    from jax import lax

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[ep_axis]
    E = w1.shape[0]
    if E % ep:
        raise ValueError(
            f"ep degree {ep} must divide num_experts {E}")
    e_loc = E // ep

    def body(x_loc, gw, w1_loc, b1_loc, w2_loc, b2_loc):
        n = x_loc.shape[0] * top_k
        logits = x_loc.astype(jnp.float32) @ gw.astype(jnp.float32)
        expert_idx, gates, _ = _dropless_topk_gating(logits, top_k)
        # aux from global stats: pmean of per-shard densities == global
        # means (equal token counts per shard)
        probs = jax.nn.softmax(logits, axis=-1)
        mask1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=probs.dtype)
        density = lax.pmean(jnp.mean(mask1, 0), ep_axis)
        proxy = lax.pmean(jnp.mean(probs, 0), ep_axis)
        aux = jnp.sum(density * proxy) * E

        flat_e = expert_idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        xs = jnp.repeat(x_loc, top_k, axis=0)[order]

        counts = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        send_sizes = counts.reshape(ep, e_loc).sum(1).astype(jnp.int32)
        input_offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(send_sizes)[:-1]])

        # ragged exchange: destination segments pack into static
        # per-source slots (the public collective owns this machinery)
        from .collective import alltoall_single_in

        recv_buf, _ = alltoall_single_in(
            xs, send_sizes, axis=ep_axis, slot_rows=n)       # [ep, n, m]
        cmat = lax.all_to_all(                               # [ep, e_loc]
            counts.reshape(ep, e_loc), ep_axis, 0, 0)

        b_rows = ep * n
        buf = recv_buf.reshape(b_rows, -1)
        vals = jnp.concatenate(
            [jnp.arange(e_loc), jnp.array([e_loc])]).astype(jnp.int32)

        def block_ids(crow):
            cnt = jnp.concatenate(
                [crow, (n - crow.sum())[None]]).astype(jnp.int32)
            return jnp.repeat(vals, cnt, total_repeat_length=n)

        ids = jax.vmap(block_ids)(cmat).reshape(b_rows)
        order2 = jnp.argsort(ids, stable=True)
        inv2 = jnp.argsort(order2, stable=True)
        xs2 = buf[order2]
        per_e = cmat.sum(0)
        gsz = jnp.concatenate(
            [per_e, (b_rows - per_e.sum())[None]]).astype(jnp.int32)

        w1e = jnp.concatenate(
            [w1_loc, jnp.zeros((1,) + w1_loc.shape[1:], w1_loc.dtype)])
        b1e = jnp.concatenate(
            [b1_loc, jnp.zeros((1,) + b1_loc.shape[1:], b1_loc.dtype)])
        w2e = jnp.concatenate(
            [w2_loc, jnp.zeros((1,) + w2_loc.shape[1:], w2_loc.dtype)])
        b2e = jnp.concatenate(
            [b2_loc, jnp.zeros((1,) + b2_loc.shape[1:], b2_loc.dtype)])

        h = lax.ragged_dot(xs2, w1e, gsz)
        h = h + jnp.repeat(b1e, gsz, axis=0, total_repeat_length=b_rows)
        h = act(h)
        y2 = lax.ragged_dot(h, w2e, gsz)
        y2 = y2 + jnp.repeat(b2e, gsz, axis=0, total_repeat_length=b_rows)
        # padding rows picked up dummy-expert bias: zero them
        y2 = jnp.where((ids[order2] < e_loc)[:, None], y2, 0.0)

        y_ret = lax.all_to_all(
            y2[inv2].reshape(ep, n, -1), ep_axis, 0, 0)
        # row r of the sorted order returned from dest j = e//e_loc at
        # slot r - input_offsets[j]
        j_r = (sorted_e // e_loc).astype(jnp.int32)
        p_r = jnp.arange(n) - input_offsets[j_r]
        y_sorted = y_ret[j_r, p_r]
        inv = jnp.argsort(order, stable=True)
        y = y_sorted[inv].reshape(-1, top_k, y_sorted.shape[-1])
        return (jnp.sum(y * gates[..., None].astype(y.dtype), axis=1),
                aux)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(ep_axis), P(), P(ep_axis), P(ep_axis), P(ep_axis),
                  P(ep_axis)),
        out_specs=(P(ep_axis), P()),
        axis_names=frozenset({ep_axis}),
        check_vma=False,
    )
    return f(xf, gate_weight, w1, b1, w2, b2)


class DroplessMoELayer(MoELayer):
    """MoELayer with exact (no-drop) routing via grouped matmuls.

    Single shard (or ep degree 1): MegaBlocks-style sort + one
    ``ragged_dot`` per projection, no [t, e, c] dispatch tensors.
    With an active mesh whose ``ep`` degree > 1: sort-based all-to-all
    dispatch over the ep axis (``dropless_moe_ep_apply``) — dropless
    and expert-parallel compose, replacing the round-3 replicated-only
    constraint. last_drop_fraction is always 0 by construction.
    """

    def forward(self, x):
        from .sharding import current_mesh

        b, s, m = x.shape
        xf = x.reshape(b * s, m)
        mesh = current_mesh()
        ep = (mesh.shape.get(self.expert_axis, 1)
              if mesh is not None and self.expert_axis else 1)
        if ep > 1:
            if (b * s) % ep:
                from ..errors import InvalidArgumentError

                raise InvalidArgumentError(
                    f"dropless EP: token count {b * s} must be "
                    f"divisible by ep degree {ep}")
            y, aux = dropless_moe_ep_apply(
                xf, self.gate_weight.value,
                self.experts.w1.value, self.experts.b1.value,
                self.experts.w2.value, self.experts.b2.value,
                self.experts.act, self.top_k, mesh, self.expert_axis)
        else:
            logits = (xf.astype(jnp.float32) @
                      self.gate_weight.value.astype(jnp.float32))
            expert_idx, gates, aux = _dropless_topk_gating(
                logits, self.top_k)
            y = dropless_moe_apply(
                xf, expert_idx, gates,
                self.experts.w1.value, self.experts.b1.value,
                self.experts.w2.value, self.experts.b2.value,
                self.experts.act)
        self.last_aux_loss = aux * self.aux_loss_weight
        self.last_drop_fraction = jnp.zeros(())
        return y.reshape(b, s, m), self.last_aux_loss


# ---------------------------------------------------------------------
# One chip's share of an expert-parallel layer: told which experts it
# holds, it routes over all of them and computes its own experts' part.
# ---------------------------------------------------------------------
def sigmoid_topk_routing(scores_in, correction_bias, top_k: int,
                         scale: float = 1.0, eps: float = 1e-20):
    """Sigmoid-scored top-k routing (DeepSeek-V3 / Nemotron-H / LFM2
    form). ``scores_in`` [t, E] float32 router outputs. The choice is
    the top-k of ``sigmoid + correction_bias``; the weights are the
    chosen sigmoids WITHOUT the bias, renormalised over the k chosen
    (``/ (sum + eps)``: the published codes differ in ``eps``), times
    ``scale``. Returns (expert_idx [t, k] int32, gates [t, k]
    float32)."""
    s = jax.nn.sigmoid(scores_in.astype(jnp.float32))
    bias = jax.lax.stop_gradient(correction_bias.astype(jnp.float32))
    # top_k hands back the chosen values: taking the bias off them again
    # spares a gather over [t, E] and its scatter in the backward pass
    chosen, idx = jax.lax.top_k(s + bias, top_k)
    g = chosen - bias[idx]
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), g * scale


def _held_rows(expert_idx, first: int, n_held: int):
    """The (token, choice) assignments that fall on the experts
    ``first .. first + n_held - 1``: ``order`` lists all t*k flat
    assignments with the held ones first, grouped by expert and inside
    an expert by token; ``counts`` [n_held] is how many each held expert
    got."""
    local = expert_idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held), axis=0,
                     dtype=jnp.int32)
    return jnp.argsort(key, stable=True).astype(jnp.int32), counts


def _expert_rows(xs, gate, w_e: dict, act):
    """One block of rows ``xs`` [rows, m] through ONE expert's matrices,
    weighed by ``gate`` (rows past the expert's last come in as zeros
    with a zero weight). Two matrices: ``act(xs w1) w2``; with a third,
    the gated form ``(act(xs w3) * (xs w1)) w2``, as ``ExpertFFN``."""
    h = xs @ w_e["w1"]
    h = act(xs @ w_e["w3"]) * h if "w3" in w_e else act(h)
    return (h @ w_e["w2"]).astype(jnp.float32) * gate[:, None]


def _block(order, start, end, j, top_k: int, rows: int, t: int):
    """Block ``j`` of the expert whose sorted rows are
    ``order[start:end]``: each row's token and flat assignment. Inside
    an expert both rise and never repeat; rows past the expert's last
    get indices past the arrays' ends, so every gather fills them with
    zeros; the row accumulators (``_row_acc``) keep ``rows`` spare rows
    for them. (Telling XLA that the indices are sorted and unique
    doubles the time of the scatters on a v5e: PERF.md, PR 31.)"""
    lo = start + j * rows
    idx = jax.lax.dynamic_slice(order, (lo,), (rows,))
    ahead = jnp.arange(rows)
    valid = lo + ahead < end
    return (jnp.where(valid, idx // top_k, t + ahead),
            jnp.where(valid, idx, t * top_k + ahead))


def _take(a, i):
    return a.at[i].get(mode="fill", fill_value=0)


def _add(a, i, v):
    return a.at[i].add(v.astype(a.dtype), mode="drop")


# Rows are added into a float32 accumulator laid out [row, m / 128, 128]:
# a row is then whole (8, 128) tiles of its own, where a row of a
# [t, m] array is one sublane of m / 128 tiles that it shares with
# seven other rows. On a v5e XLA's scatter-add of 256 rows of 2688
# takes 33 us in this layout and 95 us in the flat one (PERF.md, PR 31).
def _row_acc(t: int, m: int, spare: int):
    lanes = 128 if m % 128 == 0 else m
    return jnp.zeros((t + spare, m // lanes, lanes), jnp.float32)


def _add_rows(acc, tok, v):
    """``tok`` lies inside ``acc``: a block's idle rows point at the
    spare rows past ``t``, each at its own."""
    return acc.at[tok].add(v.reshape(-1, *acc.shape[1:]),
                           mode="promise_in_bounds")


def _rows_of(acc, t: int):
    return acc[:t].reshape(t, -1)


def _n_blocks(start, end, rows: int, floor: int):
    """Trips of an expert's walk: the blocks its rows fill and never
    fewer than ``floor`` (a block past the expert's last row is all
    zeros with zero weights: it adds nothing, and costs what a full one
    costs)."""
    n = (end - start + rows - 1) // rows
    return jnp.maximum(n, floor) if floor else n


def _spans(counts):
    ends = jnp.cumsum(counts)
    return ends - counts, ends


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _held_experts(act, top_k, rows, floor, x, gates, order, counts, w):
    """sum over the held rows of gate * expert(x[token]), [t, m] float32.
    A scan over the held experts; inside it each expert walks its own
    rows in blocks of ``rows``, as many as it got: the trip count is
    read on the device, so the backward pass is written out below."""
    def per_expert(out, args):
        w_e, start, end = args

        def body(j, out):
            tok, flat = _block(order, start, end, j, top_k, rows,
                               x.shape[0])
            return _add_rows(out, tok, _expert_rows(
                _take(x, tok), _take(gates, flat), w_e, act))

        return jax.lax.fori_loop(
            0, _n_blocks(start, end, rows, floor), body, out), None

    out, _ = jax.lax.scan(per_expert, _row_acc(*x.shape, rows),
                          (w, *_spans(counts)))
    return _rows_of(out, x.shape[0])


def _held_experts_fwd(act, top_k, rows, floor, x, gates, order, counts, w):
    out = _held_experts(act, top_k, rows, floor, x, gates, order, counts, w)
    return out, (x, gates, order, counts, w)


def _held_experts_bwd(act, top_k, rows, floor, res, dout):
    x, gates, order, counts, w = res
    f32 = jnp.float32

    def per_expert(acc, args):
        w_e, start, end = args

        def body(j, inner):
            dx, dgates, dw_e = inner
            tok, flat = _block(order, start, end, j, top_k, rows,
                               x.shape[0])
            _, vjp = jax.vjp(
                lambda xs, g, w_e: _expert_rows(xs, g, w_e, act),
                _take(x, tok), _take(gates, flat), w_e)
            dxs, dg, dw_b = vjp(_take(dout, tok))
            return (_add_rows(dx, tok, dxs), _add(dgates, flat, dg),
                    jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(f32), dw_e, dw_b))

        dx, dgates, dw_e = jax.lax.fori_loop(
            0, _n_blocks(start, end, rows, floor), body,
            (*acc, jax.tree_util.tree_map(
                lambda v: jnp.zeros(v.shape, f32), w_e)))
        return (dx, dgates), jax.tree_util.tree_map(
            lambda a, v: a.astype(v.dtype), dw_e, w_e)

    (dx, dgates), dw = jax.lax.scan(
        per_expert, (_row_acc(*x.shape, rows), jnp.zeros(gates.shape, f32)),
        (w, *_spans(counts)))
    return _rows_of(dx, x.shape[0]).astype(x.dtype), dgates, None, None, dw


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


# ---------------------------------------------------------------------
# The same sum through grouped kernels (``kernels/grouped_experts.py``):
# the held rows are gathered ONCE into a buffer sorted by expert and laid
# out in tiles, a tile is one expert's, and what the walk does block by
# block inside two nested loops is five kernel calls over the live tiles.
# ---------------------------------------------------------------------
def _tiles_walked(counts, rows: int, floor: int):
    """Tiles of ``rows`` that each expert's stretch takes: what its rows
    fill and never fewer than ``floor``."""
    return jnp.maximum((counts + rows - 1) // rows, floor)


# rows that one trip of the gather and combine loops moves, about: the
# loops run over the live tiles only, in chunks of whole tiles. On a v5e
# at the LFM2 cell's shape a layer's forward and backward take 6.55 ms
# in chunks of 1024 or 512 rows, 6.86 in chunks of 256, 7.33 in 2048 and
# over 8.3 in 4096 and more (PERF.md, PR 39)
_CHUNK_ROWS = 1024


def _chunk_tiles(tile: int) -> int:
    return max(_CHUNK_ROWS // tile, 1)


def _sorted_layout(order, counts, top_k: int, t: int, tile: int, floor: int):
    """Where each held row sits in the sorted buffer. Expert ``e`` takes
    ``max(ceil(c_e / tile), floor, 1)`` tiles from a tile boundary on;
    the static bound on the tiles is every assignment on the held
    experts, rounded up to whole chunks. Returns

      flat  [rows]  each buffer row's flat assignment, ``t * top_k`` and
                    more (past ``gates``' end) for a row that holds none
      tok   [rows]  its token, ``t`` and more for a row that holds
                    none: a gather reads zeros for it and a scatter-add
                    drops it
      tile_expert [tiles], n_live [1]   the kernels' scalars."""
    n, n_held = t * top_k, counts.shape[0]
    per = _chunk_tiles(tile)
    tiles = math.ceil(n / tile) + n_held * max(floor, 1)
    tiles = math.ceil(tiles / per) * per
    mine = _tiles_walked(counts, tile, max(floor, 1))
    tile_ends = jnp.cumsum(mine)
    n_live = tile_ends[-1:]
    i = jnp.arange(tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(i[:, None] >= tile_ends, axis=1, dtype=jnp.int32),
        n_held - 1)
    starts, ends = _spans(counts)
    lo = starts[tile_expert] + (i - (tile_ends - mine)[tile_expert]) * tile
    # a tile's rows are one slice of ``order`` (padded, so that the last
    # slice is whole), cut at the expert's end
    padded = jnp.pad(order, (0, tile))
    flat = jax.vmap(lambda lo: jax.lax.dynamic_slice(padded, (lo,), (tile,))
                    )(jnp.clip(lo, 0, n))
    ahead = jnp.arange(tile, dtype=jnp.int32)
    # (a tile past the live ones starts past its expert's end: none valid)
    valid = (lo[:, None] + ahead < ends[tile_expert][:, None]).reshape(-1)
    flat = flat.reshape(-1)
    return (jnp.where(valid, flat, n), jnp.where(valid, flat // top_k, t),
            tile_expert, n_live.astype(jnp.int32))


def _live_chunks(n_live, tile: int):
    """(rows a trip, trips) of a loop over the live tiles."""
    per = _chunk_tiles(tile)
    return per * tile, (n_live[0] + per - 1) // per


def _gather_live(sources: tuple, idx: tuple, chunk: int, n_chunks):
    """``source[idx]`` for each pair, over the first ``n_chunks`` chunks
    of the indices; the rows past them are never written, and never
    read. An index past a source's end reads zeros."""
    def body(c, outs):
        lo = c * chunk
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                out, _take(a, jax.lax.dynamic_slice_in_dim(i, lo, chunk)),
                lo, 0)
            for out, a, i in zip(outs, sources, idx))

    return jax.lax.fori_loop(0, n_chunks, body, tuple(
        jax.lax.empty((i.shape[0], *a.shape[1:]), a.dtype)
        for a, i in zip(sources, idx)))


def _add_live_rows(acc, tok, v):
    """``_add_rows`` for rows of which some hold no assignment: those
    point past ``acc``'s end and are dropped, and a v5e does skip them
    (10,240 rows of which 2,048 hold none: 0.82 ms against 1.04 with
    each at a spare row of its own; PERF.md, PR 39)."""
    return acc.at[tok].add(v.reshape(-1, *acc.shape[1:]), mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _grouped_experts(top_k, tile, floor, x, gates, order, counts, w):
    """``_held_experts`` for gated silu experts, through the kernels."""
    return _grouped_experts_fwd(top_k, tile, floor, x, gates, order,
                                counts, w)[0]


def _grouped_experts_fwd(top_k, tile, floor, x, gates, order, counts, w):
    t = x.shape[0]
    flat, tok, te, n_live = _sorted_layout(order, counts, top_k, t, tile,
                                           floor)
    chunk, n_chunks = _live_chunks(n_live, tile)
    xs, gs = _gather_live((x, gates[:, None]), (tok, flat), chunk, n_chunks)
    a, b, h = grouped_experts.gate_up(xs, w["w1"], w["w3"], te, n_live,
                                      tile=tile)
    ys = grouped_experts.down(h, w["w2"], gs, te, n_live, tile=tile)

    def combine(c, out):
        lo = c * chunk
        return _add_live_rows(
            out, jax.lax.dynamic_slice_in_dim(tok, lo, chunk),
            jax.lax.dynamic_slice_in_dim(ys, lo, chunk))

    out = jax.lax.fori_loop(0, n_chunks, combine, _row_acc(*x.shape, 0))
    return _rows_of(out, t), (xs, a, b, gs, flat, tok, te, n_live, w)


def _grouped_experts_bwd(top_k, tile, floor, res, dout):
    xs, a, b, gs, flat, tok, te, n_live, w = res
    t, n_held = dout.shape[0], w["w1"].shape[0]
    chunk, n_chunks = _live_chunks(n_live, tile)
    dys, = _gather_live((dout.astype(xs.dtype),), (tok,), chunk, n_chunks)
    da, db, hg, dg = grouped_experts.d_hidden(
        dys, w["w2"], a, b, gs, te, n_live, tile=tile)
    dxs = grouped_experts.d_rows(da, db, w["w1"], w["w3"], te, n_live,
                                 tile=tile)
    dw1, dw3 = grouped_experts.d_weights(
        xs, (db, da), te, n_live, tile=tile, n_experts=n_held,
        dtype=w["w1"].dtype)
    dw2, = grouped_experts.d_weights(
        hg, (dys,), te, n_live, tile=tile, n_experts=n_held,
        dtype=w["w2"].dtype)

    def scatter(c, acc):
        dx, dgates = acc
        lo = c * chunk
        return (_add_live_rows(
                    dx, jax.lax.dynamic_slice_in_dim(tok, lo, chunk),
                    jax.lax.dynamic_slice_in_dim(dxs, lo, chunk)),
                _add(dgates, jax.lax.dynamic_slice_in_dim(flat, lo, chunk),
                     jax.lax.dynamic_slice_in_dim(dg, lo, chunk)))

    dx, dgates = jax.lax.fori_loop(
        0, n_chunks, scatter,
        (_row_acc(t, xs.shape[1], 0), jnp.zeros((t * top_k,), jnp.float32)))
    return (_rows_of(dx, t).astype(xs.dtype), dgates, None, None,
            {"w1": dw1, "w2": dw2, "w3": dw3})


_grouped_experts.defvjp(_grouped_experts_fwd, _grouped_experts_bwd)


def _use_grouped(x, w: dict, act, block_rows: int) -> bool:
    """Whether the held rows go through the grouped kernels: gated silu
    experts whose widths are whole lanes, on the chip (or anywhere under
    ``PADDLE_TPU_FORCE_PALLAS``, interpreted). Everything else takes the
    walk, which is also the kernels' reference."""
    return _backend.use_kernel(
        "w3" in w and act in (F.silu, jax.nn.silu)
        and grouped_experts.aligned(x.shape[1], w["w1"].shape[2],
                                    block_rows))


def held_experts_apply(x, expert_idx, gates, w: dict, act, first: int,
                       block_rows: int = 256, min_blocks: int = 0):
    """The held experts' part of a dropless top-k layer.

    x [t, m]; expert_idx, gates [t, k] over ALL the router's experts;
    ``w`` the held experts' stacked matrices (``w1`` [n_held, m, h],
    ``w2`` [n_held, h, m], and for gated experts ``w3`` [n_held, m, h]:
    ``_expert_rows`` has both forms), which are the
    experts ``first .. first + n_held - 1``. Returns
    (y [t, m] float32 = sum over the chosen AND held experts of
    gate * expert(x), counts [n_held]).

    Rows of absent experts cost nothing: the assignments are sorted
    with the held ones first, by expert, and each held expert (a scan
    over the stacked matrices) walks its own rows in blocks
    of ``block_rows`` (gather, two or three plain products,
    scatter-add), as many blocks as it got. Nothing is dropped,
    whatever the routing: a full expert takes more blocks. At most
    ``block_rows - 1`` rows of zeros an expert are multiplied in
    vain; with ``min_blocks`` every held expert walks at least that
    many blocks, so that the layer's time does not follow small
    differences of load (``HeldExpertsMoE.even_share_slack``).

    Gated silu experts whose widths are whole lanes go, on the chip,
    through the grouped kernels instead (``_use_grouped``): the same
    rows in the same blocks, there tiles of one sorted buffer, and an
    expert without a row takes one tile of zeros."""
    t, k = expert_idx.shape
    order, counts = _held_rows(expert_idx, first, w["w1"].shape[0])
    if _use_grouped(x, w, act, block_rows):
        return _grouped_experts(
            k, block_rows, min_blocks, x,
            gates.reshape(-1).astype(jnp.float32), order, counts, w), counts
    # room for an expert's last block to read a whole slice
    order = jnp.pad(order, (0, block_rows))
    y = _held_experts(act, k, block_rows, min_blocks, x,
                      gates.reshape(-1).astype(jnp.float32), order,
                      counts, w)
    return y, counts


def held_rows_walked(x, w: dict, act, counts, block_rows: int,
                     min_blocks: int):
    """The rows ``held_experts_apply`` multiplies for these counts, the
    zeros that fill an expert's last block and the floor's included:
    blocks times ``block_rows`` on the walk, live tiles times the tile
    through the kernels. ``counts`` over it is the fill."""
    least = max(min_blocks, 1) if _use_grouped(x, w, act, block_rows) \
        else min_blocks
    return jnp.sum(_tiles_walked(counts, block_rows, least)) * block_rows


def sum_routing_counts(counts: list) -> dict:
    """A model's ``step_counters()`` from its sparse layers'
    ``last_counts``: rows summed over the layers, ``moe_rows_max`` the
    fullest held expert of the worst layer, ``moe_rows_walked`` where
    the layers leave it (gated ones do); ``{}`` without a layer."""
    if not counts:
        return {}
    out = {
        "moe_rows_routed": sum(c["rows_routed"] for c in counts),
        "moe_rows_held": sum(c["rows_held"] for c in counts),
        "moe_rows_max": jnp.max(jnp.stack(
            [c["rows_max"] for c in counts]))}
    if all("rows_walked" in c for c in counts):
        out["moe_rows_walked"] = sum(c["rows_walked"] for c in counts)
    return out


class HeldExpertsMoE(Layer):
    """One chip's share of an expert-parallel sparse layer.

    The router is whole (``num_experts`` outputs, top-k over all of
    them); of the routed experts this layer holds ``held = (first,
    count)`` and computes their part of the result for the rows routed
    to them, and nothing for the rest; a shared expert, where
    ``shared_hidden`` is given, sees every token. The experts are
    bias-free, ``act(x w1) w2`` or, with ``gated``, the three-matrix
    ``(act(x w3) * (x w1)) w2``; ``norm_eps`` is what the router's
    renormalisation adds to the sum of the chosen scores. On one chip
    there is no exchange: what the absent experts would add is added by
    the chips that hold them.

    The walk is dropless and its trip count is whole blocks, so an even
    share that just fills its blocks (1024 rows in blocks of 256) makes
    each expert take four or five by the draw of the routers, and the
    step's time follows the seed. ``even_share_slack`` (None: off) is a
    capacity factor used as a floor, not a ceiling: every held expert
    walks at least the blocks that ``slack`` times its even share
    fills, and more when it got more. On a v5e at 8192 tokens, top-4 of
    32 and a slack of 1.25 the step is 2% slower than the mean of the
    bare walk and the same from seed to seed (PERF.md, PR 38).

    forward(x [b, s, m]) -> y [b, s, m]; the step's routing counts are
    left in ``last_counts`` ({"rows_routed", "rows_held", "rows_max"},
    device scalars of the trace that called forward). A gated layer
    also leaves "rows_walked", the rows its experts multiplied
    (``held_rows_walked``: "rows_held" over it is the fill); the
    two-matrix layer traces exactly what it did before the kernels
    came, counters included."""

    def __init__(self, d_model: int, num_experts: int, d_hidden: int,
                 top_k: int, held, activation: str = "relu2",
                 shared_hidden: Optional[int] = None,
                 routed_scale: float = 1.0, init_std: float = 0.02,
                 expert_axis: str = "ep", gated: bool = False,
                 norm_eps: float = 1e-20,
                 even_share_slack: Optional[float] = None):
        super().__init__()
        self.num_experts, self.top_k = num_experts, top_k
        self.norm_eps = norm_eps
        self.first, n_held = held
        if self.first < 0 or self.first + n_held > num_experts:
            raise ValueError(f"held experts {held} outside 0..{num_experts}")
        self.routed_scale = routed_scale
        init = I.Normal(0.0, init_std)
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), default_initializer=init)
        # chosen by sigmoid + this, weighed without it; a buffer that
        # load balancing moves, never a parameter
        self.register_buffer("e_score_correction_bias",
                             jnp.zeros((num_experts,), jnp.float32))
        self.experts = ExpertFFN(n_held, d_model, d_hidden, expert_axis,
                                 activation, init_std, bias=False,
                                 gated=gated)
        self.shared_experts = ExpertFFN(
            1, d_model, shared_hidden, None, activation, init_std,
            bias=False, gated=gated) if shared_hidden else None
        self.block_rows = 256  # rows of one product of a held expert
        self.even_share_slack = even_share_slack
        self.last_counts = None

    def min_blocks(self, tokens: int) -> int:
        """The floor of every held expert's walk for a call of
        ``tokens``: the blocks that ``even_share_slack`` times an even
        share of the routed rows fills; 0 without a slack."""
        if self.even_share_slack is None:
            return 0
        even = tokens * self.top_k / self.num_experts
        return math.ceil(self.even_share_slack * even / self.block_rows)

    def route(self, xf):
        scores = jnp.matmul(
            xf.astype(jnp.float32),
            self.gate_weight.value.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        return sigmoid_topk_routing(
            scores, self._buffers["e_score_correction_bias"], self.top_k,
            self.routed_scale, self.norm_eps)

    def forward(self, x):
        b, s, m = x.shape
        xf = x.reshape(b * s, m)
        with jax.named_scope("moe_router"):
            expert_idx, gates = self.route(xf)
        with jax.named_scope("moe_experts"):
            e = self.experts
            w = {"w1": e.w1.value, "w2": e.w2.value}
            if e.w3 is not None:
                w["w3"] = e.w3.value
            y, counts = held_experts_apply(
                xf, expert_idx, gates, w, e.act, self.first,
                self.block_rows, self.min_blocks(b * s))
            y = y.astype(x.dtype)
        self.last_counts = {
            "rows_routed": jnp.asarray(b * s * self.top_k, jnp.int32),
            "rows_held": jnp.sum(counts), "rows_max": jnp.max(counts)}
        if e.w3 is not None:
            self.last_counts["rows_walked"] = held_rows_walked(
                xf, w, e.act, counts, self.block_rows,
                self.min_blocks(b * s))
        if self.shared_experts is not None:
            with jax.named_scope("moe_shared"):
                y = y + self.shared_experts(xf[None])[0]
        return y.reshape(b, s, m)
