"""Launch CLI (parity: python -m paddle.distributed.launch —
python/paddle/distributed/launch/: Context arg/env parsing,
CollectiveController building a Job of Pod/Containers, per-rank process
supervision with log capture, master rendezvous).

TPU-native: on TPU pods there is one process per host (not per chip), and
``jax.distributed`` handles rendezvous via the coordinator address. The
controller therefore launches ``nproc_per_node`` worker processes (>1
only for CPU/debug meshes), wires the PADDLE_* env contract the rest of
the framework reads (env.py), captures per-rank logs to
``log/workerlog.N``, supervises exits, and — with ``--elastic`` — re-spawns
failed workers so training resumes from the latest checkpoint
(checkpoint-resume recovery, the reference's elastic semantics with etcd
replaced by the coordinator; SURVEY.md §5 "Failure detection").
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional


class Container:
    def __init__(self, rank: int, cmd: List[str], env: dict, log_dir: str):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_dir = log_dir
        self.proc: Optional[subprocess.Popen] = None
        self.log_file = None

    def start(self):
        os.makedirs(self.log_dir, exist_ok=True)
        path = os.path.join(self.log_dir, f"workerlog.{self.rank}")
        self.log_file = open(path, "ab")
        self.proc = subprocess.Popen(
            self.cmd, env=self.env, stdout=self.log_file,
            stderr=subprocess.STDOUT,
        )
        return self.proc

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self.log_file:
            self.log_file.close()


class CollectiveController:
    def __init__(self, args, extra: List[str]):
        self.args = args
        self.extra = extra
        self.containers: List[Container] = []
        self.manager = None
        if args.np:
            # elastic membership via the shared-store ElasticManager
            # (reference: fleet/elastic with etcd; SURVEY.md §5)
            from ..elastic import ElasticManager, FileStore, parse_np_range

            store = FileStore(args.elastic_store, args.job_id)
            self.manager = ElasticManager(
                store, parse_np_range(args.np),
                fault_timeout=args.elastic_timeout)
            self.manager.register()

    def _world(self, grace: bool = False):
        if self.manager is None:
            return self.args.nnodes, self.args.node_rank
        if grace:
            # restart path: let a dead peer's heartbeat go stale before
            # re-ranking, or the rebuilt world still contains it and the
            # respawn burns max_restarts against a doomed membership
            time.sleep(self.manager.fault_timeout)
        self.manager.evict_faulted()
        spec = self.manager.wait_for_world(
            timeout=self.args.elastic_timeout * 6,
            settle=self.args.elastic_settle)
        if spec is None:
            raise RuntimeError(
                "elastic: no viable membership within timeout "
                f"(need np in [{self.manager.min_np}, "
                f"{self.manager.max_np}])")
        return spec.nnodes, spec.node_rank

    def build(self, grace: bool = False):
        nproc = self.args.nproc_per_node
        master = self.args.master or "127.0.0.1:49175"
        nnodes, node_rank = self._world(grace=grace)
        self.containers = []
        for local_rank in range(nproc):
            rank = node_rank * nproc + local_rank
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(nnodes * nproc),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_MASTER": master,
                "COORDINATOR_ADDRESS": master,
            })
            cmd = [sys.executable] + self.extra
            self.containers.append(
                Container(rank, cmd, env, self.args.log_dir)
            )
        return self

    def run(self) -> int:
        for c in self.containers:
            c.start()
        print(
            f"launched {len(self.containers)} worker(s); logs in "
            f"{self.args.log_dir}/workerlog.N"
        )
        restarts = 0
        try:
            while True:
                statuses = [c.poll() for c in self.containers]
                if all(s == 0 for s in statuses):
                    return 0
                failed = [
                    (i, s) for i, s in enumerate(statuses)
                    if s not in (None, 0)
                ]
                if failed:
                    if (self.args.elastic
                            and restarts < self.args.max_restarts):
                        restarts += 1
                        print(
                            f"worker(s) {[i for i, _ in failed]} failed; "
                            f"elastic restart {restarts}/"
                            f"{self.args.max_restarts}"
                        )
                        for c in self.containers:
                            c.terminate()
                        # re-rank over the surviving membership before
                        # respawning (no-op without --np)
                        try:
                            self.build(grace=self.manager is not None)
                        except RuntimeError as e:
                            print(f"elastic: {e}; tearing down")
                            for c in self.containers:
                                c.terminate()
                            return 1
                        for c in self.containers:
                            c.start()
                    else:
                        print(
                            f"worker(s) failed with {failed}; tearing down"
                        )
                        for c in self.containers:
                            c.terminate()
                        return 1
                time.sleep(self.args.poll_interval)
        except KeyboardInterrupt:
            for c in self.containers:
                c.terminate()
            return 130

    def stop(self):
        for c in self.containers:
            c.terminate()
        if self.manager is not None:
            self.manager.deregister()


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="multi-process / multi-host job launcher",
    )
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER"))
    p.add_argument("--devices", type=str, default=None,
                   help="refused: a GPU-visibility list no TPU reads")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--elastic", action="store_true",
                   help="restart failed workers (checkpoint-resume)")
    p.add_argument("--np", type=str, default=None,
                   help="elastic node range 'min:max' (implies membership "
                        "tracking via --elastic_store)")
    p.add_argument("--job_id", type=str,
                   default=os.environ.get("PADDLE_JOB_ID", "default"))
    p.add_argument("--elastic_store", type=str,
                   default=os.environ.get("PADDLE_ELASTIC_STORE", "/tmp"),
                   help="shared directory for membership (must be a "
                        "filesystem ALL nodes see — NFS/GCS-fuse; the "
                        "/tmp default only works single-node)")
    p.add_argument("--elastic_timeout", type=float, default=5.0)
    p.add_argument("--elastic_settle", type=float, default=1.0,
                   help="membership must be stable this long before a "
                        "world forms (startup race debounce)")
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--poll_interval", type=float, default=1.0)
    p.add_argument("training_script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def host_platform() -> str:
    """The platform this host's workers will get, found WITHOUT
    creating a JAX backend: a launcher that touched JAX would hold the
    chip its worker needs. ``JAX_PLATFORMS`` where the caller set it,
    else ``tpu`` where TPU chips sit on this host's PCI bus."""
    want = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if want:
        return want
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    return "tpu" if chips else "cpu"


def check_one_process_per_tpu_host(args):
    """A chip belongs to one process at a time and one process drives
    every chip of a host, so on a TPU host a second worker would fail
    or hang at backend start-up: refuse it here, in words."""
    if args.devices:
        raise SystemExit(
            "--devices is a GPU-visibility list (CUDA_VISIBLE_DEVICES) "
            "that no TPU reads; one worker process drives every chip of "
            "its host")
    if args.nproc_per_node > 1 and host_platform() == "tpu":
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} on a TPU host: a "
            "chip belongs to one process at a time, so launch ONE worker "
            "per host (it drives all of the host's chips); "
            "nproc_per_node > 1 is for CPU/debug meshes "
            "(JAX_PLATFORMS=cpu)")


def launch(argv=None) -> int:
    args = parse_args(argv)
    check_one_process_per_tpu_host(args)
    if args.np and args.elastic_store == "/tmp" and \
            parse_np_max(args.np) > 1:
        print("warning: --elastic_store=/tmp is node-local; multi-node "
              "membership needs a shared filesystem path", file=sys.stderr)
    extra = [args.training_script] + list(args.script_args)
    controller = CollectiveController(args, extra)
    try:
        # build inside the try: a membership-wait timeout in the first
        # build must still deregister the heartbeat, or the ghost node
        # corrupts the next launch's world
        controller.build()
        return controller.run()
    finally:
        controller.stop()


def parse_np_max(np_arg: str) -> int:
    from ..elastic import parse_np_range

    return parse_np_range(np_arg)[1]
