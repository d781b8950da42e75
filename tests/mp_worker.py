"""Worker entry for the multi-process (real ``jax.distributed``) tests.

Run as: ``python mp_worker.py <mode> <out_dir>`` with the PADDLE_*
rendezvous env set by the test (or by the launch CLI). Each mode prints
``MP_OK <mode>`` on success; assertions crash the worker otherwise.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def _global_array(mesh, spec, host_local):
    from jax.experimental import multihost_utils

    return multihost_utils.host_local_array_to_global_array(
        host_local, mesh, spec)


def mode_collective(out_dir):
    """Eager collectives + object collectives across 2 real processes."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.distributed.sharding import mesh_context

    rank = jax.process_index()
    world = jax.process_count()
    assert world == 2, world
    mesh = dist.build_mesh(dp=len(jax.devices()))

    with mesh_context(mesh):
        # every process contributes its rank+1; allreduce(SUM) must give
        # the same total on every shard
        local = np.full((jax.local_device_count(), 4), float(rank + 1),
                        np.float32)
        x = _global_array(mesh, P("dp"), local)
        y = coll.all_reduce(x, mesh=mesh)
        got = np.asarray(
            [np.asarray(s.data) for s in y.addressable_shards])
        expect = sum(
            (r + 1) * jax.local_device_count() for r in range(world))
        np.testing.assert_allclose(got, float(expect))

        # object collectives ride the coordination service
        objs = []
        coll.all_gather_object(objs, {"rank": rank, "tag": "mp"})
        assert [o["rank"] for o in objs] == list(range(world)), objs

        lst = [{"v": rank}]
        coll.broadcast_object_list(lst, src=1)
        assert lst[0]["v"] == 1, lst
    print(f"MP_OK collective rank={rank}", flush=True)


def mode_ckpt_roundtrip(out_dir):
    """save_state_dict across 2 processes (real barriers, one writer per
    chunk) then reshard-on-load; every rank verifies content."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import checkpoint as ckpt

    rank = jax.process_index()
    mesh = dist.build_mesh(dp=len(jax.devices()))
    full = np.arange(4 * 6, dtype=np.float32).reshape(4, 6)
    n_local = 4 // len(jax.devices()) * jax.local_device_count()
    local = full[rank * n_local:(rank + 1) * n_local]
    x = _global_array(mesh, P("dp"), local)
    state = {"w": x, "b": _global_array(
        mesh, P(), np.float32([1, 2, 3]))}

    path = os.path.join(out_dir, "ckpt")
    ckpt.save_state_dict(state, path)
    assert ckpt.is_committed(path)

    # reshard-on-load: everyone loads the FULL tensor replicated
    loaded = ckpt.load_state_dict(
        path, shardings={"w": NamedSharding(mesh, P()),
                         "b": NamedSharding(mesh, P())})
    np.testing.assert_allclose(np.asarray(loaded["w"]), full)
    np.testing.assert_allclose(np.asarray(loaded["b"]), [1, 2, 3])
    print(f"MP_OK ckpt_roundtrip rank={rank}", flush=True)


def mode_ckpt_kill_rank(out_dir):
    """Async save with rank 1 dying mid-save (after the tmpdir barrier,
    before its metadata lands): rank 0's metadata quorum must TIME OUT,
    refuse to commit, and leave the previous checkpoint intact."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import checkpoint as ckpt

    rank = jax.process_index()
    mesh = dist.build_mesh(dp=len(jax.devices()))
    local = np.full((jax.local_device_count(), 2), 7.0, np.float32)
    state = {"w": _global_array(mesh, P("dp"), local)}
    path = os.path.join(out_dir, "ckpt_async")

    # a good committed checkpoint first (both ranks alive)
    ckpt.save_state_dict(state, path)
    assert ckpt.is_committed(path)

    if rank == 1:
        # die mid-save: after the snapshot+barrier, before any shard or
        # metadata file is written
        real_write = ckpt._write_snapshot

        def _die(*a, **k):
            os._exit(0)

        ckpt._write_snapshot = _die
        saver = ckpt.AsyncCheckpointer(commit_timeout=6.0)
        saver.save(state, path)
        saver.wait_until_finished()  # unreachable: _die exits the proc
        raise AssertionError("rank 1 should have died in _write_snapshot")

    saver = ckpt.AsyncCheckpointer(commit_timeout=6.0)
    saver.save(state, path)
    try:
        saver.wait_until_finished()
        raise AssertionError("commit quorum should have timed out")
    except TimeoutError as e:
        assert "1/2" in str(e) or "metadata" in str(e), e
    # the torn tmp dir must NOT have been committed; the previous
    # checkpoint survives
    assert ckpt.is_committed(path)
    assert not os.path.exists(
        os.path.join(path, "..", "ckpt_async.tmp", ckpt.COMMITTED_MARKER))
    print(f"MP_OK ckpt_kill_rank rank={rank}", flush=True)
    # rank 1 is already dead: skip atexit distributed shutdown, which
    # would wait on the lost peer
    os._exit(0)


def mode_launch_hello(out_dir):
    """Body for the launch-CLI rendezvous test: prove the PADDLE_* env
    the launcher injected forms a real 2-process jax world."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.distributed.sharding import mesh_context

    rank = jax.process_index()
    world = jax.process_count()
    assert world == int(os.environ["PADDLE_TRAINERS_NUM"]), world
    mesh = dist.build_mesh(dp=len(jax.devices()))
    with mesh_context(mesh):
        x = _global_array(
            mesh, P("dp"),
            np.full((jax.local_device_count(),), float(rank + 1),
                    np.float32))
        y = coll.all_reduce(x, mesh=mesh)
        total = float(np.asarray(y.addressable_shards[0].data)[0])
    print(f"MP_OK launch_hello rank={rank} world={world} sum={total}",
          flush=True)


MODES = {
    "collective": mode_collective,
    "ckpt_roundtrip": mode_ckpt_roundtrip,
    "ckpt_kill_rank": mode_ckpt_kill_rank,
    "launch_hello": mode_launch_hello,
}


if __name__ == "__main__":
    mode, out_dir = sys.argv[1], sys.argv[2]
    from paddle_tpu.distributed import env as dist_env

    dist_env.init_parallel_env()
    MODES[mode](out_dir)
