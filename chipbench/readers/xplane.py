"""One reduction from the profiler's trace to numbers.

``jax.profiler`` leaves ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData.from_file`` reads it with nothing but JAX.
On a TPU the device is the plane ``/device:TPU:<n>``. Its line
``XLA Ops`` has one event for each operation that ran (a Pallas kernel
is an event like any other, under the name the kernel was given) and
its line ``XLA Modules`` one for each run of a compiled program. Host
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event there under its own name.
The benchmark puts ``chipbench.window`` around the traced window, so
the window is known in the trace's own clock.

Busy time is the UNION of the operations' intervals (nested and
overlapping events are counted once), never their sum. An operation
belongs to the module run whose interval holds its start.

    python3 -m chipbench.readers.xplane <dir>     prints what a trace holds
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys

WINDOW = "chipbench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def union_ns(intervals) -> int:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, hi = 0, None
    for s, e in sorted(intervals):
        if hi is None or s > hi:
            total += e - s
            hi = e
        elif e > hi:
            total += e - hi
            hi = e
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list:
    """The idle (start, end) stretches of [lo, hi] that no interval
    covers, longest first."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return sorted((g for g in out if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])


class Trace:
    """Device operations, module runs and host spans of one trace, cut
    to the ``chipbench.window`` span. Per device: ``ops`` is a list of
    (start, end, name), ``modules`` likewise."""

    def __init__(self, devices: dict, spans: list, window: tuple):
        self.window = window
        lo, hi = window
        self.devices = {}
        for dev, lines in devices.items():
            ops = [(max(s, lo), min(e, hi), n)
                   for s, e, n in lines.get(OPS_LINE, [])
                   if e > lo and s < hi]
            mods = sorted((s, e, n) for s, e, n in
                          lines.get(MODULES_LINE, []) if e > lo and s < hi)
            self.devices[dev] = {"ops": ops, "modules": mods}
        self.spans = [(s, e, n) for s, e, n in spans
                      if e > lo and s < hi and n != WINDOW]

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(union_ns((s, e) for s, e, _ in d["ops"])
                   for d in self.devices.values()) / len(self.devices) / 1e9

    def module_busy_ns(self, pattern: str, holds: str = None,
                       lacks: str = None) -> tuple:
        """(busy ns, runs) of the module runs whose name matches, over
        all devices: the union of the operations inside those runs.
        Where programs share a name (every serving program is
        ``jit_fn``), ``holds`` keeps the runs in which some operation
        matches it and ``lacks`` those in which none does."""
        rx = re.compile(pattern)
        hold = re.compile(holds) if holds else None
        lack = re.compile(lacks) if lacks else None
        busy = runs = 0
        for d in self.devices.values():
            mods = [m for m in d["modules"] if rx.search(m[2])]
            starts = [m[0] for m in mods]
            inside = [[] for _ in mods]
            for s, e, n in d["ops"]:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mods[i][1]:
                    inside[i].append((s, e, n))
            for ops in inside:
                if hold and not any(hold.search(n) for _, _, n in ops):
                    continue
                if lack and any(lack.search(n) for _, _, n in ops):
                    continue
                runs += 1
                busy += union_ns((s, e) for s, e, _ in ops)
        return busy, runs

    def op_ns(self, pattern: str) -> tuple:
        """(summed ns, events) of the operations whose name matches."""
        rx = re.compile(pattern)
        hits = [e - s for d in self.devices.values()
                for s, e, n in d["ops"] if rx.search(n)]
        return sum(hits), len(hits)

    def breakdown(self, no_span: str) -> dict:
        """The ten operations that took most device time, and the ten
        longest idle gaps of the first device, each named by the host
        span that covers most of it."""
        per_op = collections.Counter()
        for d in self.devices.values():
            for s, e, n in d["ops"]:
                per_op[n] += e - s
        # containers (a while loop holds its body's operations) would
        # count their children twice in a sum: the table says so by name
        top = [[short_name(n), t / 1e9 / max(len(self.devices), 1)]
               for n, t in per_op.most_common(10)]
        idle = []
        if self.devices:
            first = self.devices[sorted(self.devices)[0]]
            lo, hi = self.window
            for s, e in gaps_ns(((a, b) for a, b, _ in first["ops"]),
                                lo, hi)[:10]:
                cover = collections.Counter()
                for a, b, n in self.spans:
                    if b > s and a < e:
                        cover[n] += min(b, e) - max(a, s)
                name = no_span
                if cover and cover.most_common(1)[0][1] * 2 >= e - s:
                    name = cover.most_common(1)[0][0]
                idle.append([name, (e - s) / 1e9])
        return {"device_ops": top, "idle_gaps": idle}


def short_name(hlo: str, width: int = 150) -> str:
    """An operation's event name is its whole HLO text: keep its name,
    result and first operands, without the layouts."""
    return re.sub(r"\{[^{}]*\}", "", hlo)[:width]


def _newest(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> Trace:
    """Read the newest trace under ``trace_dir``. The window is the
    ``chipbench.window`` span; without one, the extent of the device's
    operations."""
    import jax

    data = jax.profiler.ProfileData.from_file(_newest(trace_dir))
    devices, spans, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns), ev.name)
                        for ev in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == WINDOW:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                    elif name.startswith("chipbench.") or \
                            name in SPAN_NAMES:
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      name))
    if window is None:
        every = [t for lines in devices.values()
                 for s, e, _ in lines.get(OPS_LINE, []) for t in (s, e)]
        window = (min(every), max(every)) if every else (0, 1)
    return Trace(devices, spans, window)


# the host spans the benchmark puts around its own calls
SPAN_NAMES = {"make_batch", "TrainStep.run", "client.send",
              "client.receive"}


def dump(trace_dir: str):
    """What a trace holds: planes, lines, the commonest event names."""
    import jax

    data = jax.profiler.ProfileData.from_file(_newest(trace_dir))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter()
            for ev in events:
                names[ev.name] += ev.duration_ns
            print(f"  line {line.name!r}: {len(events)} events")
            for n, t in names.most_common(25):
                print(f"      {t / 1e6:12.3f} ms  {n[:110]}")
            if events and (plane.name.startswith("/device")
                           or line.name == "XLA Ops"):
                ev = events[len(events) // 2]
                print("      stats of one event:",
                      {k: str(v)[:80] for k, v in ev.stats})


def grep(trace_dir: str, pattern: str, limit: int = 40):
    """Count, summed time and whole name of the device events that
    match, by name."""
    import jax

    rx = re.compile(pattern)
    data = jax.profiler.ProfileData.from_file(_newest(trace_dir))
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            seen, total = collections.Counter(), collections.Counter()
            for ev in line.events:
                if rx.search(ev.name):
                    seen[ev.name] += 1
                    total[ev.name] += ev.duration_ns
            for n, t in total.most_common(limit):
                print(f"{line.name}: x{seen[n]} {t / 1e6:.3f} ms  "
                      f"{n[:900]}")


if __name__ == "__main__":
    if len(sys.argv) > 2:
        grep(sys.argv[1], sys.argv[2])
    else:
        dump(sys.argv[1])
