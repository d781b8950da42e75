"""Elastic membership manager tests (reference:
python/paddle/distributed/fleet/elastic/ — mocked-etcd style tests;
here the store is a real temp directory)."""

import json
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.elastic import (
    ElasticManager,
    FileStore,
    WorldSpec,
    latest_checkpoint,
    parse_np_range,
)


def test_parse_np_range():
    assert parse_np_range("2:4") == (2, 4)
    assert parse_np_range("3") == (3, 3)


def _mgr(tmp_path, node_id, np=(1, 4), **kw):
    store = FileStore(str(tmp_path), "job1")
    kw.setdefault("heartbeat_interval", 0.05)
    kw.setdefault("fault_timeout", 0.5)
    return ElasticManager(store, np, node_id=node_id, **kw)


def test_membership_and_rerank(tmp_path):
    a = _mgr(tmp_path, "nodeA").register()
    b = _mgr(tmp_path, "nodeB").register()
    try:
        alive, faulted = a.scan()
        assert sorted(alive) == ["nodeA", "nodeB"] and not faulted
        # ranks identical from both observers, ordered by node id
        sa, sb = a.plan(), b.plan()
        assert sa.nnodes == sb.nnodes == 2
        assert sa.node_rank == 0 and sb.node_rank == 1
        assert sa.hosts == sb.hosts
    finally:
        a.deregister()
        b.deregister()


def test_fault_detection_and_eviction(tmp_path):
    a = _mgr(tmp_path, "nodeA").register()
    b = _mgr(tmp_path, "nodeB").register()
    try:
        # kill B's heartbeat (simulated crash) and age its file
        b._stop.set()
        b._hb_thread.join(timeout=2)
        old = time.time() - 10
        os.utime(b.store._path("nodeB"), (old, old))
        alive, faulted = a.scan()
        assert alive == ["nodeA"] and faulted == ["nodeB"]
        assert a.evict_faulted() == ["nodeB"]
        # membership shrinks within np range → re-ranked single world
        spec = a.plan()
        import socket

        assert spec == WorldSpec(nnodes=1, node_rank=0,
                                 hosts=[socket.gethostname()],
                                 node_ids=["nodeA"])
    finally:
        a.deregister()
        b.deregister()


def test_plan_respects_np_range(tmp_path):
    a = _mgr(tmp_path, "nodeA", np=(2, 3)).register()
    try:
        assert a.plan() is None  # below min_np
        b = _mgr(tmp_path, "nodeB", np=(2, 3)).register()
        assert a.plan() is not None
        c = _mgr(tmp_path, "nodeC", np=(2, 3)).register()
        d = _mgr(tmp_path, "nodeD", np=(2, 3)).register()
        assert a.plan() is None  # above max_np
        for m in (b, c, d):
            m.deregister()
    finally:
        a.deregister()


def test_wait_for_world_scale_up(tmp_path):
    a = _mgr(tmp_path, "nodeA", np=(2, 2)).register()
    try:
        import threading

        def join_later():
            time.sleep(0.3)
            _mgr(tmp_path, "nodeB", np=(2, 2)).register()

        t = threading.Thread(target=join_later)
        t.start()
        spec = a.wait_for_world(timeout=5.0, poll=0.05)
        t.join()
        assert spec is not None and spec.nnodes == 2
    finally:
        a.deregister()


def test_latest_checkpoint_skips_incomplete(tmp_path):
    root = tmp_path / "ckpts"
    for step, complete in [(10, True), (20, True), (30, False)]:
        d = root / f"step_{step}"
        d.mkdir(parents=True)
        if complete:
            (d / "metadata.json").write_text(json.dumps({}))
    assert latest_checkpoint(str(root)) == str(root / "step_20")
    assert latest_checkpoint(str(tmp_path / "missing")) is None


def test_launch_elastic_np_membership(tmp_path):
    # end-to-end: launch with --np 1:2 supervises a script that fails
    # once then succeeds after restart (checkpoint-resume pattern)
    script = tmp_path / "worker.py"
    marker = tmp_path / "attempted"
    script.write_text(
        "import os, sys\n"
        f"m = {str(repr(str(marker)))}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').write('x'); sys.exit(1)\n"
        "print('resumed ok', os.environ['PADDLE_TRAINERS_NUM'])\n"
    )
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--elastic", "--max_restarts", "2",
         "--np", "1:1", "--job_id", "t1",
         "--elastic_store", str(tmp_path),
         "--log_dir", str(tmp_path / "log"), str(script)],
        capture_output=True, text=True, timeout=180,
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stdout + r.stderr
    log = (tmp_path / "log" / "workerlog.0").read_bytes().decode()
    assert "resumed ok 1" in log


def test_launch_stop_deregisters_heartbeat(tmp_path):
    # after a successful run the heartbeat file must be gone — a ghost
    # node would corrupt the next launch's world
    script = tmp_path / "ok.py"
    script.write_text("print('fine')\n")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--np", "1:1", "--job_id", "t2",
         "--elastic_store", str(tmp_path), "--elastic_settle", "0.2",
         "--log_dir", str(tmp_path / "log"), str(script)],
        capture_output=True, text=True, timeout=180,
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stdout + r.stderr
    reg = tmp_path / "elastic_t2"
    assert not any(f.startswith("node_") for f in os.listdir(reg))
