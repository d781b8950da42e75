"""Collective communication API.

Parity: python/paddle/distributed/communication/ (all_reduce, all_gather,
reduce_scatter, alltoall, broadcast, send/recv, barrier) over
ProcessGroupNCCL (paddle/fluid/distributed/collective/).

TPU-native: there is no userspace NCCL to wrap. Tensor-traffic
collectives are XLA HLO ops emitted *inside* compiled programs — either
implicitly by GSPMD or explicitly via ``jax.lax.p*`` under ``shard_map``.
This module provides:
  1. in-jit functions (psum/all_gather/...) usable inside shard_map'ed
     code, matching paddle.distributed call signatures; and
  2. eager wrappers that shard_map a single collective over the active
     mesh — the moral equivalent of a one-op NCCL launch, used by tests
     and host-side logic (and by checkpoint barriers).
Host-level coordination (the reference's TCPStore) is
``jax.distributed``'s builtin store; see env.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import record_collective as _record
from .topology import get_hybrid_communicate_group


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


# ---------------------------------------------------------------------------
# in-jit collectives (call inside shard_map with a named axis).
# Each records (op, axis, payload bytes, call site) at TRACE time via
# observability.comm — one entry per collective baked into a compiled
# program, so a program's communication volume is queryable.
# ---------------------------------------------------------------------------
def all_reduce_in(x, op: str = ReduceOp.SUM, axis: str = "dp"):
    _record("all_reduce", axis, x)
    if op == ReduceOp.SUM:
        return jax.lax.psum(x, axis)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axis)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(x, axis)
    if op == ReduceOp.PROD:
        return jnp.exp(jax.lax.psum(jnp.log(x), axis))
    raise ValueError(op)


def all_gather_in(x, axis: str = "dp", tiled_dim: int = 0):
    _record("all_gather", axis, x)
    return jax.lax.all_gather(x, axis, axis=tiled_dim, tiled=True)


def reduce_scatter_in(x, axis: str = "dp", scatter_dim: int = 0):
    _record("reduce_scatter", axis, x)
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                                tiled=True)


def all_to_all_in(x, axis: str = "sep", split_dim: int = 0, concat_dim: int = 0):
    _record("all_to_all", axis, x)
    return jax.lax.all_to_all(x, axis, split_axis=split_dim,
                              concat_axis=concat_dim, tiled=True)


def ppermute_in(x, axis: str, perm):
    _record("ppermute", axis, x)
    return jax.lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


# ---------------------------------------------------------------------------
# eager wrappers over the active mesh
# ---------------------------------------------------------------------------
def _active_mesh() -> Mesh:
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise RuntimeError(
            "no active mesh: call distributed.init_parallel_env() / "
            "fleet_init first"
        )
    return hcg.mesh


def _group_axis(group) -> str:
    if group is None:
        return "dp"
    if isinstance(group, str):
        return group
    return group.axis


def all_reduce(tensor, op=ReduceOp.SUM, group=None, mesh: Optional[Mesh] = None):
    """Eager allreduce over one mesh axis. The input is interpreted as
    *already sharded* along that axis (dim 0 carries the per-rank data in
    the reference's SPMD model)."""
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    other = tuple(a for a in mesh.axis_names if a != axis)
    spec = P(axis)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False,
    )
    def f(x):
        return all_reduce_in(x, op, axis)

    return f(tensor)


def all_gather(tensor_or_list, tensor=None, group=None, mesh=None):
    """paddle signature: all_gather(out_list, tensor). Returns the list of
    per-rank pieces; also supports functional use all_gather(tensor)."""
    if isinstance(tensor_or_list, list):
        out_list, x = tensor_or_list, tensor
    else:
        out_list, x = None, tensor_or_list
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    n = mesh.shape[axis]

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(xs):
        return all_gather_in(xs, axis, 0)

    stacked = f(x)
    if out_list is not None:
        per = stacked.shape[0] // n
        chunks = [stacked[i * per:(i + 1) * per] for i in range(n)]
        out_list.extend(chunks)
        return out_list
    return stacked


def reduce_scatter(tensor, group=None, op=ReduceOp.SUM, mesh=None):
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(x):
        return reduce_scatter_in(x, axis, 0)

    return f(tensor)


def alltoall(in_tensor_list, out_tensor_list=None, group=None, mesh=None):
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    x = (
        jnp.concatenate(in_tensor_list, axis=0)
        if isinstance(in_tensor_list, (list, tuple))
        else in_tensor_list
    )

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(x):
        return all_to_all_in(x, axis, 0, 0)

    out = f(x)
    if out_tensor_list is not None:
        n = mesh.shape[axis]
        per = out.shape[0] // n
        out_tensor_list.extend(
            out[i * per:(i + 1) * per] for i in range(n)
        )
        return out_tensor_list
    return out


def broadcast(tensor, src: int = 0, group=None, mesh=None):
    """Replicate src rank's shard to all ranks along the axis."""
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    n = mesh.shape[axis]

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(x):
        full = all_gather_in(x, axis, 0)
        per = full.shape[0] // n
        piece = jax.lax.dynamic_slice_in_dim(full, src * per, per, 0)
        return piece

    return f(tensor)


def barrier(group=None):
    """Host barrier: a trivial device allreduce forces synchronization."""
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return
    x = jnp.ones((hcg.mesh.devices.size,), jnp.int32)
    all_reduce(x, mesh=hcg.mesh, group="dp") if "dp" in hcg.mesh.axis_names \
        else None


# ---------------------------------------------------------------------------
# object collectives (parity: paddle.distributed.all_gather_object /
# broadcast_object_list — pickled python objects over the coordination
# service rather than NCCL byte tensors)
# ---------------------------------------------------------------------------
def _object_via_host(obj, tag: str):
    """Share pickled objects through jax's multihost broadcast (the
    TPU-world TCPStore): every process contributes, all receive the
    list ordered by process index."""
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    # fixed-size frame: length-prefix + padded body, gathered as one
    # host-value broadcast per process
    max_len = int(multihost_utils.process_allgather(
        jnp.asarray([payload.size]))[..., 0].max())
    frame = np.zeros((max_len + 8,), np.uint8)
    frame[:8] = np.frombuffer(
        np.asarray([payload.size], np.int64).tobytes(), np.uint8)
    frame[8:8 + payload.size] = payload
    gathered = np.asarray(
        multihost_utils.process_allgather(jnp.asarray(frame)))
    out = []
    for row in gathered.reshape(jax.process_count(), -1):
        n = int(np.frombuffer(row[:8].tobytes(), np.int64)[0])
        out.append(pickle.loads(row[8:8 + n].tobytes()))
    return out


def all_gather_object(object_list, obj, group=None):
    """Parity: paddle.distributed.all_gather_object — appends every
    rank's ``obj`` (any picklable) into ``object_list``."""
    object_list.extend(_object_via_host(obj, "all_gather_object"))
    return object_list


def broadcast_object_list(object_list, src: int = 0, group=None):
    """Parity: paddle.distributed.broadcast_object_list — replaces the
    list contents with rank ``src``'s."""
    gathered = _object_via_host(list(object_list), "broadcast_object")
    if not 0 <= src < len(gathered):
        raise ValueError(
            f"broadcast_object_list: src {src} out of range for "
            f"{len(gathered)} process(es)")
    object_list[:] = gathered[src]
    return object_list


# ---------------------------------------------------------------------------
# groups (parity: paddle.distributed.new_group / Group). A TPU "group"
# is a mesh axis: arbitrary rank sets have no NCCL communicator to
# build — they must correspond to one axis's subgroups of the active
# mesh (the topology the reference's HCG builds its groups from too).
# ---------------------------------------------------------------------------
class Group:
    """A communicator handle bound to one mesh axis."""

    _registry: dict = {}
    _next_id = [1]

    def __init__(self, axis: str, ranks=None):
        self.axis = axis
        self.ranks = ranks
        self.id = Group._next_id[0]
        Group._next_id[0] += 1
        Group._registry[self.id] = self

    @property
    def nranks(self):
        return _active_mesh().shape[self.axis]

    def __repr__(self):
        return f"Group(axis={self.axis!r}, id={self.id})"


def _axis_subgroups(mesh: Mesh, axis: str):
    """Device-id rank sets forming each subgroup of ``axis``."""
    import numpy as np

    ax = mesh.axis_names.index(axis)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    moved = np.moveaxis(ids, ax, -1).reshape(-1, ids.shape[ax])
    return [tuple(int(r) for r in row) for row in moved]


def new_group(ranks=None, backend=None, timeout=None, axis=None):
    """Create a Group. Pass ``axis=`` to bind a mesh axis directly, or
    ``ranks`` matching one of an axis's subgroups (the only rank sets a
    mesh topology can serve — anything else raises loudly)."""
    if axis is not None:
        return Group(axis, ranks)
    mesh = _active_mesh()
    if ranks is None:
        return Group(mesh.axis_names[0])
    want = tuple(int(r) for r in ranks)
    for ax in mesh.axis_names:
        if want in _axis_subgroups(mesh, ax):
            return Group(ax, want)
    raise ValueError(
        f"new_group(ranks={ranks}): rank set matches no mesh-axis "
        f"subgroup of {dict(mesh.shape)} — TPU groups are mesh axes")


def get_group(gid: int):
    return Group._registry.get(gid)


def destroy_process_group(group=None):
    if group is None:
        Group._registry.clear()
    else:
        Group._registry.pop(getattr(group, "id", None), None)


def is_initialized():
    return get_hybrid_communicate_group() is not None


# ---------------------------------------------------------------------------
# more eager collectives
# ---------------------------------------------------------------------------
def reduce(tensor, dst: int = 0, op=ReduceOp.SUM, group=None, mesh=None):
    """Reduce to rank ``dst``: every rank gets its own shard back except
    dst, which gets the reduction (SPMD lockstep form)."""
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(x):
        red = all_reduce_in(x, op, axis)
        return jnp.where(jax.lax.axis_index(axis) == dst, red, x)

    return f(tensor)


def scatter(tensor, tensor_list=None, src: int = 0, group=None, mesh=None):
    """Rank r receives piece r of src's list (paddle signature:
    scatter(out, tensor_list, src))."""
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    n = mesh.shape[axis]
    x = (jnp.stack(tensor_list) if tensor_list is not None
         else tensor.reshape(n, -1, *tensor.shape[1:]))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(), out_specs=P(axis),
        check_vma=False,
    )
    def f(full):
        i = jax.lax.axis_index(axis)
        return jax.lax.dynamic_index_in_dim(full, i, 0, keepdims=False)

    return f(x)


def gather(tensor, gather_list=None, dst: int = 0, group=None, mesh=None):
    """All ranks contribute their shard; the stacked result is returned
    (every rank materializes it — an SPMD program cannot hold rank-
    dependent shapes; paddle's dst-only contract is a subset)."""
    stacked = all_gather(tensor, group=group, mesh=mesh)
    n = (mesh or _active_mesh()).shape[_group_axis(group)]
    # the global result replicates the gathered block once per rank —
    # slice ONE block, then split it into the per-rank pieces
    gathered = stacked[: stacked.shape[0] // n]
    per = gathered.shape[0] // n
    chunks = [gathered[i * per:(i + 1) * per] for i in range(n)]
    if gather_list is not None:
        gather_list.extend(chunks)
        return gather_list
    return chunks


def alltoall_single_in(x, send_sizes, axis: str = "ep",
                       slot_rows: Optional[int] = None):
    """Ragged all-to-all, in-jit form (call under ``shard_map``).

    Parity: the variable-split ``alltoall_single`` / NCCL alltoallv
    (upstream python/paddle/distributed/communication/all_to_all.py).
    TPU-native: XLA collectives are static-shaped, so each destination's
    ragged segment is packed into a fixed slot of ``slot_rows`` rows and
    exchanged with ONE dense ``lax.all_to_all`` over the ICI ring
    (``lax.ragged_all_to_all`` would send only filled prefixes, but
    XLA:CPU has no kernel for it and CI runs on the CPU mesh).

    x: [n, ...] local rows sorted so rows destined for rank d form the
    d-th contiguous segment; ``send_sizes``: int32 [nranks] segment
    lengths (sum <= n, traced values allowed). Returns
    ``(recv, recv_sizes)`` where ``recv`` is [nranks, slot_rows, ...]
    (source-major; row s holds rank s's segment for this rank, zero
    padded) and ``recv_sizes`` is int32 [nranks].
    """
    n = x.shape[0]
    slot_rows = slot_rows or n
    send_sizes = send_sizes.astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(send_sizes)[:-1]])
    slot = jnp.arange(slot_rows, dtype=jnp.int32)
    src_idx = offsets[:, None] + slot[None, :]
    valid = slot[None, :] < send_sizes[:, None]
    valid = valid.reshape(valid.shape + (1,) * (x.ndim - 1))
    send_buf = jnp.where(
        valid, x[jnp.clip(src_idx, 0, max(n - 1, 0))],
        jnp.zeros((), x.dtype))
    _record("alltoall_single", axis, send_buf)
    recv = jax.lax.all_to_all(send_buf, axis, 0, 0)
    recv_sizes = jax.lax.all_to_all(send_sizes, axis, 0, 0, tiled=True)
    return recv, recv_sizes


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, mesh=None):
    """All-to-all on dim 0 (paddle ``alltoall_single``), uniform or
    ragged splits.

    Uniform (no split sizes): ``in_tensor`` is the global array; rank
    r's chunk j goes to rank j; returns the transposed global array.

    Ragged: ``in_split_sizes`` is either one row of ``nranks`` ints
    (every rank sends the same split pattern) or an ``[nranks][nranks]``
    matrix whose row r is rank r's split list (the single-controller
    SPMD form of the reference's per-process argument). Each row must
    sum to the per-rank local length. Per-rank outputs generally have
    different lengths, so the ragged form returns a LIST of per-rank
    arrays (rank r's = the reference's ``out_tensor`` on process r);
    ``out_split_sizes`` is validated against the transpose if given.
    """
    if in_split_sizes is None and out_split_sizes is None:
        return alltoall(in_tensor, group=group, mesh=mesh)
    import numpy as np

    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    n = mesh.shape[axis]
    if in_split_sizes is None:
        # only out_split_sizes given: infer sends from the transpose
        outs = np.asarray(out_split_sizes, dtype=np.int32)
        if outs.ndim == 1:
            outs = np.tile(outs, (n, 1))
        in_split_sizes, out_split_sizes = outs.T, None
    splits = np.asarray(in_split_sizes, dtype=np.int32)
    if splits.ndim == 1:
        splits = np.tile(splits, (n, 1))
    if splits.shape != (n, n):
        raise ValueError(
            f"alltoall_single: in_split_sizes must be [{n}] or "
            f"[{n}][{n}], got shape {tuple(splits.shape)}")
    n_loc = in_tensor.shape[0] // n
    row_sums = splits.sum(axis=1)
    if not (row_sums == n_loc).all():
        raise ValueError(
            f"alltoall_single: each rank's in_split_sizes must sum to "
            f"its local length {n_loc}, got {row_sums.tolist()}")
    if out_split_sizes is not None:
        outs = np.asarray(out_split_sizes, dtype=np.int32)
        if outs.ndim == 1:
            outs = np.tile(outs, (n, 1))
        if not (outs == splits.T).all():
            raise ValueError(
                "alltoall_single: out_split_sizes must be the transpose "
                "of in_split_sizes")
    slot_rows = max(int(splits.max()), 1)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False,
    )
    def f(x_loc, sizes_loc):
        recv, recv_sizes = alltoall_single_in(
            x_loc, sizes_loc[0], axis=axis, slot_rows=slot_rows)
        return recv[None], recv_sizes[None]

    recv, recv_sizes = f(in_tensor, jnp.asarray(splits))
    recv = jax.device_get(recv)            # [n, n, slot_rows, ...]
    out = [
        jnp.concatenate(
            [recv[r, s, : int(splits[s, r])] for s in range(n)], axis=0)
        for r in range(n)
    ]
    if out_tensor is not None and isinstance(out_tensor, list):
        out_tensor.extend(out)
    return out


# ---------------------------------------------------------------------------
# p2p (parity: send/recv/isend/irecv, P2POp + batch_isend_irecv).
# Lockstep SPMD: a rank pair is one ppermute edge; every rank runs the
# same program, non-addressed ranks keep their input.
# ---------------------------------------------------------------------------
class _Task:
    def __init__(self, value=None):
        self.value = value

    def wait(self):
        if self.value is not None:
            jax.block_until_ready(self.value)
        return self.value


def _p2p(tensor, pairs, group=None, mesh=None):
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    def f(x):
        moved = jax.lax.ppermute(x, axis, pairs)
        dsts = jnp.asarray([d for _, d in pairs])
        i = jax.lax.axis_index(axis)
        hit = jnp.any(dsts == i)
        return jnp.where(hit, moved, x)

    return f(tensor)


def send(tensor, dst: int = 0, group=None, mesh=None):
    """Paired send: rank src's shard replaces rank dst's (the matching
    ``recv`` reads the returned array). Returns the post-exchange
    array."""
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    src = (dst - 1) % mesh.shape[axis]
    return _p2p(tensor, [(src, dst)], group, mesh)


def recv(tensor, src: int = 0, group=None, mesh=None):
    mesh = mesh or _active_mesh()
    axis = _group_axis(group)
    dst = (src + 1) % mesh.shape[axis]
    return _p2p(tensor, [(src, dst)], group, mesh)


def isend(tensor, dst: int = 0, group=None, mesh=None):
    return _Task(send(tensor, dst, group, mesh))


def irecv(tensor, src: int = 0, group=None, mesh=None):
    return _Task(recv(tensor, src, group, mesh))


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(tensor)
    return tensor


class P2POp:
    """Parity: paddle.distributed.P2POp — a deferred send/recv edge for
    batch_isend_irecv."""

    def __init__(self, op, tensor, peer, group=None):
        name = getattr(op, "__name__", str(op))
        if name not in ("send", "isend", "recv", "irecv"):
            raise ValueError(f"P2POp: unknown op {op}")
        self.is_send = "send" in name
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Execute every edge and return one task per op. Call-site parity
    for the reference's grouped-NCCL launcher: in lockstep SPMD each op
    is a canonical ring edge (see ``send``/``recv``); real pipelined
    transfer fusion lives in the compiled schedules
    (``distributed/pipeline.py``'s in-jit ppermute), not here."""
    tasks = []
    for o in p2p_op_list:
        val = (send(o.tensor, o.peer, o.group) if o.is_send
               else recv(o.tensor, o.peer, o.group))
        tasks.append(_Task(val))
    return tasks
