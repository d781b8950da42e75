"""Multi-process tests: REAL ``jax.distributed`` worlds (2 processes,
Gloo CPU collectives), the reference's launcher/worker test pattern
(upstream: test/collective/*). Every barrier and the async metadata
quorum in distributed/checkpoint.py silently no-ops at
process_count()==1 — these are the only tests where they actually run.
"""

import os
import socket
import subprocess
import sys

import jax
import pytest

# these tests need Gloo CPU collectives in the CPU backend; on the
# 0.4.x line every cross-process collective raises "Multiprocess
# computations aren't implemented on the CPU backend"
pytestmark = pytest.mark.skipif(
    jax.__version_info__ < (0, 5),
    reason="multi-process CPU (Gloo) collectives need jax >= 0.5; this "
           "jax's CPU backend rejects multiprocess computations")

HERE = os.path.dirname(__file__)
REPO = os.path.join(HERE, "..")
WORKER = os.path.join(HERE, "mp_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _base_env():
    """CPU world plumbing shared by every spawn style: force the cpu
    platform, 2 local devices/process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    return env


def _worker_env(rank, world, port):
    env = _base_env()
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_MASTER": f"127.0.0.1:{port}",
    })
    return env


def _spawn_world(mode, tmp_path, world=2, timeout=240,
                 expect_rc={0: 0, 1: 0}):
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, mode, str(tmp_path)],
            env=_worker_env(r, world, port), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc.get(r, 0), (
            f"rank {r} rc={p.returncode}\n{out[-3000:]}")
    return outs


def test_eager_collectives_two_processes(tmp_path):
    outs = _spawn_world("collective", tmp_path)
    for r, out in enumerate(outs):
        assert f"MP_OK collective rank={r}" in out, out[-2000:]


def test_checkpoint_save_load_two_processes(tmp_path):
    """Sync save: real cross-process barriers, one-writer-per-chunk, and
    reshard-on-load of the other rank's shards."""
    outs = _spawn_world("ckpt_roundtrip", tmp_path)
    for r, out in enumerate(outs):
        assert f"MP_OK ckpt_roundtrip rank={r}" in out, out[-2000:]
    # both ranks' unique chunks landed in ONE committed directory
    ckpt_dir = tmp_path / "ckpt"
    assert (ckpt_dir / "COMMITTED").exists()


def test_async_checkpoint_kill_one_rank_mid_save(tmp_path):
    """Rank 1 dies after the tmpdir barrier but before writing its
    metadata: rank 0's quorum poll must time out without committing and
    the previous checkpoint must stay loadable."""
    outs = _spawn_world("ckpt_kill_rank", tmp_path, timeout=300)
    assert "MP_OK ckpt_kill_rank rank=0" in outs[0], outs[0][-2000:]
    assert (tmp_path / "ckpt_async" / "COMMITTED").exists()
    tmp_dir = tmp_path / "ckpt_async.tmp"
    if tmp_dir.exists():
        assert not (tmp_dir / "COMMITTED").exists()


def test_launch_cli_rendezvous(tmp_path):
    """python -m paddle_tpu.distributed.launch --nproc_per_node 2:
    workers rendezvous via the injected PADDLE_MASTER and run a real
    cross-process allreduce."""
    port = _free_port()
    env = _base_env()
    log_dir = str(tmp_path / "logs")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--master", f"127.0.0.1:{port}",
         "--log_dir", log_dir,
         os.path.abspath(WORKER), "launch_hello", str(tmp_path)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    logs = ""
    for n in range(2):
        with open(os.path.join(log_dir, f"workerlog.{n}")) as f:
            logs += f.read()
    # 4 global devices (2/process) holding rank+1 → allreduce = 1+1+2+2
    assert "MP_OK launch_hello rank=0 world=2 sum=6.0" in logs, logs[-2000:]
    assert "MP_OK launch_hello rank=1 world=2 sum=6.0" in logs, logs[-2000:]
