"""chipbench: one cell of BENCHMARK.json, once, on the chip it is started on.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It finds the cell in ``BENCHMARK.json``, the cell's
configuration, traffic and limits by name under ``chipbench/``, and hands
them to the configuration's kind (``chipbench/kinds/<kind>.py``), which
loads, warms up, measures for ``--seconds`` and decides ``correct``
against the plain reference. This file knows kinds, never cells. With
``--trace 1`` the window is traced and every per-layer metric listed for
the cell is read from the trace or the run's counters by the reader its
file names. The last line of standard output is the result.

It exits non-zero and prints no result where JAX finds no TPU, fewer
chips than the cell asks for, or a ``device_kind`` that
``chipbench/peaks.json`` does not list. ``--rehearse-cpu`` runs the same
control flow at a toy size on the CPU, names ``cpu`` on every line and
never prints a result line. ``--mode control`` puts the reference, one
precision down, in the program's place; ``--fault`` breaks the timed
path underneath: both are for setting and testing the limits, and the
benchmark's own runs use neither.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
CACHE = None  # this process's CacheCounter, once main() has made it
# where the module a configuration names under each key is found
PARTS = {"kind": "kinds", "program": "programs", "weights": "weights",
         "reference": "reference", "flops": "opsbytes"}


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(workload: str):
    """(benchmark, cell, configuration, traffic, limits) for the name
    of a ``workloads`` entry."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("limits", workload + ".json")
    return bench, cell, config, traffic, limits


def cell_metrics(bench: dict, workload: str, group: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return group == "end_to_end" or reports(e2e[m["moves"]])

    return [m for m in bench[group] if reports(m)]


class Ctx:
    """What a kind is handed: the cell's data, the arguments, the
    devices, and ``say`` for lines that name the device."""

    def __init__(self, args, cell, config, traffic, limits, devices):
        self.cell, self.config = cell, config
        self.traffic, self.devices = traffic, devices
        self.limits = dict(limits)
        if args.rehearse_cpu:  # the toy size reads on another scale
            self.limits.update(limits.get("rehearsal", {}))
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse_cpu
        self.mode, self.fault = args.mode, args.fault
        d = devices[0]
        self.tag = f"{d.platform} {d.device_kind} x{cell['chips']}" + (
            " CPU-REHEARSAL" if self.rehearse else "")
        self.t_start = T_START  # set-up runs from the process's start

    def say(self, msg: str):
        print(f"[chipbench {self.tag} +{time.perf_counter() - T_START:.1f}s]"
              f" {msg}", file=sys.stderr, flush=True)

    def part(self, key: str):
        """The module the configuration names under ``key``: its kind,
        the builder of the program's model, the weights from the seed,
        the plain reference, the count of required operations."""
        return importlib.import_module(
            f"chipbench.{PARTS[key]}.{self.config[key]}")

    def generator(self):
        return importlib.import_module(
            "chipbench.generators." + self.traffic["generator"])

    def widths(self) -> dict:
        """The configuration's sizes; the toy ones in a rehearsal."""
        w = dict(self.config["rehearsal"] if self.rehearse
                 else self.config)
        w.setdefault("head_dim",
                     w["hidden_size"] // w["num_attention_heads"])
        return w

    def sizes(self) -> dict:
        """The traffic's parameters, the rehearsal's laid over them."""
        t = dict(self.traffic)
        if self.rehearse:
            t.update(self.traffic.get("rehearsal", {}))
        return t


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout
    (copied from benchmarks/compile_cache.py): where
    JAX_COMPILATION_CACHE_DIR is set JAX reads it itself."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_every_program():
    """Keep the small programs too (JAX's default leaves out what
    compiled in under a second): a run after the first finds every
    program in the cache, and set-up is the same work from run to run."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CacheCounter:
    """This process's persistent-cache hits and misses, from JAX's own
    monitoring events (copied from benchmarks/compile_cache.py)."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def start_trace():
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # no Python tracer: it slows the host and swells the file; the host
    # spans the breakdown needs are TraceAnnotations
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def stop_trace():
    import jax

    jax.profiler.stop_trace()


def read_per_layer(bench, workload, peaks, facts) -> tuple:
    """Every per-layer metric of the cell through its reader; returns
    (metrics, device seconds, breakdown). A reader that finds nothing
    returns None and the metric is left out."""
    from chipbench.readers import xplane

    trace = xplane.load(TRACE_DIR)
    out = {}
    for m in cell_metrics(bench, workload, "per_layer"):
        spec = load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        value = reader.read(trace, spec.get("args", {}), facts, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": trace.busy_s(), "window_s": trace.window_s()}
    return out, device, trace.breakdown(facts.get("no_span", "host (no span)"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on the CPU; never a result")
    ap.add_argument("--mode", choices=("run", "control"), default="run")
    ap.add_argument("--fault", default=None,
                    help="break the timed path (tests and limit-setting)")
    args = ap.parse_args(argv)
    if not NAME.match(args.workload):
        raise SystemExit(f"chipbench: bad workload name {args.workload!r}")
    bench, cell, config, traffic, limits = find_cell(args.workload)

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_FORCE_PALLAS"] = "1"
        os.environ["PT_FLAGS_fused_decode"] = "on"
    # what the configuration asks of the runtime's environment, before
    # JAX loads it; what the machine sets already stands
    for key, value in config.get("runtime_env", {}).items():
        os.environ.setdefault(key, str(value))
    # the runtime attaching to the chip: no code of this repo is in it.
    # It is part of setup_s, as every run pays it, and is printed beside
    # it (PERF.md section 6 has what it is made of)
    t_attach = time.perf_counter()
    import jax

    devices = jax.devices()
    attach_s = time.perf_counter() - t_attach
    want = "cpu" if args.rehearse_cpu else "tpu"
    peaks = load_json("peaks.json")["device_kinds"].get(
        devices[0].device_kind)
    if devices[0].platform != want or len(devices) < cell["chips"] or (
            peaks is None and not args.rehearse_cpu):
        print(f"chipbench: {args.workload} needs {cell['chips']} {want} "
              f"device(s) of a kind in chipbench/peaks.json; JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"{devices[0].device_kind!r}", file=sys.stderr)
        return 1
    if args.rehearse_cpu:  # nominal: exercises the readers, never a result
        peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    devices = devices[:cell["chips"]]
    cache_dir = enable_compile_cache()
    cache_every_program()
    global CACHE
    cache = CACHE = CacheCounter()
    ctx = Ctx(args, cell, config, traffic, limits, devices)
    ctx.say(f"attached to the device in {attach_s:.1f} s (inside setup_s)")
    ctx.say(f"cell {args.workload} seed {args.seed} seconds {args.seconds}"
            f" trace {args.trace} mode {args.mode} fault {args.fault}")

    res = ctx.part("kind").run(ctx)

    ctx.say(f"compile cache {cache_dir}: {cache.hits} hits, "
            f"{cache.misses} misses in this process")
    # the cell's own end-to-end metrics, as BENCHMARK.json lists them
    # (a kind may measure more than a cell reports)
    metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                           "unit": m["unit"]}
               for m in cell_metrics(bench, args.workload, "end_to_end")
               if args.mode == "run"}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and res.get("facts"):
        metrics, dev, breakdown = read_per_layer(
            bench, args.workload, peaks, res["facts"])
        line["metrics"] = metrics
        device.update(dev)
        line["breakdown"] = breakdown
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    line["compile_cache"] = {"hits": cache.hits, "misses": cache.misses}
    line["device_attach_s"] = attach_s
    line["not_compared"] = {n: c for n, c in res["compared"].items()
                            if c["limit"] is None}
    line["compared"] = {n: c for n, c in res["compared"].items()
                        if c["limit"] is not None}
    for name, pair in line["not_compared"].items():
        ctx.say(f"read, not compared, {name}: {pair['value']!r}")
    for name, pair in line["compared"].items():
        ctx.say(f"compared {name}: {pair['value']!r} limit "
                f"{pair['limit']!r}")
    ctx.say(f"correct: {line['correct']}")
    if args.rehearse_cpu:
        ctx.say("rehearsal finished: control flow only, no result; "
                + json.dumps({k: line[k] for k in
                              ("correct", "attempted", "failed")}))
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
