"""The program's own spans and phase scopes, read from the profiler's trace.

``readers/xplane.py`` keeps the benchmark's spans and the operations'
names. This loader reads the same newest ``.xplane.pb`` once per process
for what the program itself wrote there (``paddle_tpu/observability/
spans.py`` has the names):

  * host events whose name starts with ``pt.`` (the program's
    ``TraceAnnotation``s), each with its arguments and its thread;
  * each device operation with its ``op_name``, the path of scopes and
    transforms it was traced under (``jit(step_fn)/transpose(jvp(mlp))/
    dot_general``), which holds the program's ``jax.named_scope``s.

**Where an operation's ``op_name`` is.** On a TPU plane it is the stat
``tf_op`` of the event's METADATA (one record per distinct operation,
beside ``hlo_category``, ``flops`` and ``source``), not of the event,
and ``jax.profiler.ProfileData`` shows an event's own stats only. The
event's name (the whole HLO text) carries no ``metadata={...}``. So the
file is decoded here from the protobuf wire format, the few messages of
``xplane.proto`` that are needed; nothing is imported for it.

Everything is cut to the ``chipbench.window`` span. A program without
spans or scopes (the parent of the PR that brought them) gives empty
lists and every reader on this loader returns ``None`` for it.

    python3 -m chipbench.readers.program_spans <dir>

prints device time by phase and idle time by innermost ``pt.*`` span.
"""

from __future__ import annotations

import bisect
import collections
import os
import re
import struct
import sys

from chipbench.readers import xplane

PREFIX = "pt."
OP_NAME_STAT = "tf_op"


# ---- the wire format: (field number, value) pairs of one message;
# a value is an int (varint), or the bytes of a length-delimited or
# fixed-width field
def _varint(buf, i: int) -> tuple:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif kind == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield num, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict):
    """One XStat -> (name, value): str, int or float."""
    name, value = None, None
    for num, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf, want_stats) -> dict:
    """One XPlane -> its name and, per line, the events as (start ns,
    end ns, name, stats). ``want_stats(plane, line)`` says which of
    {"event", "metadata"} stats to decode for a line, or None to skip
    the line."""
    name, lines, ev_meta, stat_meta = "", [], [], []
    for num, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(v)
        elif num == 5:
            stat_meta.append(v)
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(entry)
        for num, v in _fields(value):
            if num == 2:
                stat_names[key] = bytes(v).decode()
    meta = {}  # id -> (name, raw stats)
    for entry in ev_meta:
        key, value = _map_entry(entry)
        mname, stats = "", []
        for num, v in _fields(value):
            if num == 2:
                mname = bytes(v).decode("utf-8", "replace")
            elif num == 5:
                stats.append(v)
        meta[key] = (mname, stats)
    out = {"name": name, "lines": {}}
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for num, v in _fields(line):
            if num == 2:
                lname = bytes(v).decode()
            elif num == 3:
                t0_ns = _signed(v)
            elif num == 4:
                events.append(v)
        which = want_stats(name, lname)
        if which is None:
            continue
        meta_stats = {}  # decoded once per distinct operation
        rows = []
        for ev in events:
            mid = off_ps = dur_ps = 0
            own = []
            for num, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    off_ps = _signed(v)
                elif num == 3:
                    dur_ps = _signed(v)
                elif num == 4:
                    own.append(v)
            ename, raw = meta.get(mid, ("", []))
            if which == "metadata":
                if mid not in meta_stats:
                    meta_stats[mid] = dict(
                        _stat(s, stat_names) for s in raw)
                stats = meta_stats[mid]
            else:
                stats = dict(_stat(s, stat_names) for s in own)
            start = t0_ns + off_ps // 1000
            rows.append((start, start + dur_ps // 1000, ename, stats))
        out["lines"].setdefault(lname, []).extend(rows)
    return out


class ProgramTrace:
    """``window`` (lo, hi) in ns; ``devices``: per device plane ``ops``
    as (start, end, name, op_name) and ``modules`` as (start, end,
    name); ``spans``: the program's host spans as (start, end, name,
    arguments, thread). All cut to the window."""

    def __init__(self, devices: dict, spans: list, window: tuple):
        lo, hi = self.window = window
        self.devices = {}
        for dev, d in devices.items():
            self.devices[dev] = {
                "ops": sorted((max(s, lo), min(e, hi), n, p)
                              for s, e, n, p in d.get("ops", [])
                              if e > lo and s < hi),
                "modules": sorted((s, e, n) for s, e, n in
                                  d.get("modules", [])
                                  if e > lo and s < hi)}
        self.spans = sorted(
            ((max(s, lo), min(e, hi), n, a, t) for s, e, n, a, t in spans
             if e > lo and s < hi), key=lambda r: (r[0], -r[1]))

        self._own = {}

    def first_device(self):
        return self.devices[sorted(self.devices)[0]] if self.devices \
            else None

    def module_ops(self, module: str) -> tuple:
        """([(operation, its own ns)] over all devices, runs) for the
        runs of the modules whose name matches; worked out once."""
        if module not in self._own:
            rows, runs = [], 0
            for d in self.devices.values():
                ops, n = in_modules(d["ops"], d["modules"], module)
                rows += zip(ops, self_ns(ops))
                runs += n
            self._own[module] = (rows, runs)
        return self._own[module]


def parse(path: str) -> ProgramTrace:
    with open(path, "rb") as f:
        space = memoryview(f.read())

    def want(plane, line):
        if plane.startswith("/device:TPU:"):
            return {xplane.OPS_LINE: "metadata",
                    xplane.MODULES_LINE: "event"}.get(line)
        return "event" if plane.startswith("/host:") else None

    devices, spans, window = {}, [], None
    for num, v in _fields(space):
        if num != 1:
            continue
        plane = _plane(v, want)
        if plane["name"].startswith("/device:TPU:"):
            lines = plane["lines"]
            devices[plane["name"]] = {
                "ops": [(s, e, n, st.get(OP_NAME_STAT) or "")
                        for s, e, n, st in lines.get(xplane.OPS_LINE, [])],
                "modules": [(s, e, n) for s, e, n, _ in
                            lines.get(xplane.MODULES_LINE, [])]}
        elif plane["name"].startswith("/host:"):
            for thread, rows in plane["lines"].items():
                for s, e, n, st in rows:
                    if n == xplane.WINDOW:
                        window = (s, e)
                    elif n.startswith(PREFIX):
                        spans.append((s, e, n, st, thread))
    if window is None:  # as xplane.load: the extent of the operations
        every = [t for d in devices.values() for s, e, _, _ in d["ops"]
                 for t in (s, e)] or [t for s, e, *_ in spans
                                      for t in (s, e)]
        window = (min(every), max(every)) if every else (0, 1)
    return ProgramTrace(devices, spans, window)


_LOADED = {}  # path -> ProgramTrace: a run's readers share one parse


def load(trace_dir: str = None) -> ProgramTrace:
    """The newest trace under ``trace_dir`` (the harness's own trace
    directory where none is given), parsed once per process."""
    if trace_dir is None:
        from chipbench import run as harness

        trace_dir = harness.TRACE_DIR
    path = xplane._newest(trace_dir)
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = parse(path)
    return _LOADED[key]


# ---- reductions, on plain lists so that a test can hand-make them
def scope_rx(scopes) -> re.Pattern:
    """Matches an ``op_name`` that holds one of the scopes as a part of
    its path, bare or inside transforms: ``jit(step_fn)/mlp/dot_general``
    and the backward's ``transpose(jvp(mlp))`` alike, but not a
    parameter's own name (``...layers.0.mlp.up_proj.weight``)."""
    return re.compile(r"(?:^|[/(])(?:%s)(?=[/)]|$)"
                      % "|".join(re.escape(s) for s in scopes))


def in_modules(ops: list, modules: list, pattern: str) -> tuple:
    """(the operations that start inside a run of a module whose name
    matches, the number of such runs)."""
    rx = re.compile(pattern)
    mods = [m for m in modules if rx.search(m[2])]
    starts = [m[0] for m in mods]
    kept = []
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < mods[i][1]:
            kept.append(op)
    return kept, len(mods)


def self_ns(ops: list) -> list:
    """Each operation's own time: its interval less the operations
    nested inside it (a ``while`` holds its body's operations), so that
    the operations' times add up to the union of their intervals and an
    instant is counted once, for the innermost operation."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [0] * len(ops)
    stack, at = [], 0  # the open operations as (index, end); the clock

    def close():
        nonlocal at
        i, end = stack.pop()
        own[i] += end - at
        at = end

    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            own[stack[-1][0]] += s - at
            e = min(e, stack[-1][1])  # cut to the operation that holds it
        at = s
        stack.append((i, e))
    while stack:
        close()
    return own


def innermost(spans: list) -> list:
    """Flatten spans that nest and overlap into (start, end, name)
    stretches that do not: each instant goes to the span opened last
    among those that cover it, which on one thread is the innermost."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    edges = sorted({t for s in spans for t in (s[0], s[1])})
    out, live, nxt = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            live.append(spans[nxt])
            nxt += 1
        live = [s for s in live if s[1] > a]
        if live:
            out.append((a, b, max(live, key=lambda s: (s[0], -s[1]))[2]))
    return out


def idle_by_span(gaps: list, spans: list) -> dict:
    """Idle ns by the innermost span over each instant of the gaps;
    the key ``None`` holds the idle time under no span."""
    flat = innermost(spans)
    starts = [f[0] for f in flat]
    out = collections.Counter()
    for gs, ge in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(flat) and flat[i][0] < ge:
            a, b, n = flat[i]
            both = min(b, ge) - max(a, gs)
            if both > 0:
                out[n] += both
                covered += both
            i += 1
        out[None] += (ge - gs) - covered
    return dict(out)


def device_gaps(trace: ProgramTrace) -> list:
    """The first device's idle stretches inside the window."""
    dev = trace.first_device()
    if dev is None:
        return []
    lo, hi = trace.window
    return xplane.gaps_ns(((s, e) for s, e, _, _ in dev["ops"]), lo, hi)


def scopes_of(op_name: str) -> str:
    """The scopes of a path, without the jits, the transforms around
    them and the primitive at its end: ``jit(step_fn)/transpose(
    jvp(mlp))/dot_general`` gives ``mlp``."""
    kept = []
    for part in op_name.rstrip(":").split("/")[:-1]:
        while True:
            inner = re.fullmatch(r"[A-Za-z_][\w.\-]*\((.*)\)", part)
            if not inner or part.startswith(("jit(", "pjit(")):
                break
            part = inner.group(1)
        if part and not part.startswith(("jit(", "pjit(")):
            kept.append(part)
    return "/".join(kept)


def phase_table(trace: ProgramTrace, module: str = "^jit_step_fn") -> list:
    """[(scopes of the path, ms per run)] over the runs of the module,
    most first; a Pallas kernel and what has no scope under names of
    their own."""
    per = collections.Counter()
    rows, runs = trace.module_ops(module)
    for op, own in rows:
        per[scopes_of(op[3]) or (
            "(no scope: pallas kernel)" if "tpu_custom_call" in op[2]
            else "(no scope)")] += own
    return [(k, v / 1e6 / max(runs, 1)) for k, v in per.most_common()]


def main(trace_dir: str):
    trace = load(trace_dir)
    lo = trace.window[0]
    print(f"window {(trace.window[1] - lo) / 1e9:.3f} s, "
          f"{len(trace.spans)} program spans, devices "
          f"{sorted(trace.devices)}")
    print("device ms per run of ^jit_step_fn, by scope:")
    for name, ms in phase_table(trace):
        print(f"  {ms:10.3f}  {name}")
    gaps, spans = device_gaps(trace), [s[:3] for s in trace.spans]
    idle = idle_by_span(gaps, spans)
    total = sum(idle.values())

    def named(by: dict) -> str:
        return ", ".join(f"{n or '(no pt.* span)'} {ns / 1e6:.3f}"
                         for n, ns in sorted(by.items(),
                                             key=lambda kv: -kv[1]) if ns)

    print(f"device idle {total / 1e6:.3f} ms, by innermost program span:")
    for name, ns in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {ns / 1e6:10.3f} ms {100 * ns / max(total, 1):6.2f}%  "
              f"{name or '(under no pt.* span)'}")
    print("the ten longest idle stretches (ms into the window, ms long, "
          "ms under each span):")
    for s, e in gaps[:10]:
        print(f"  {(s - lo) / 1e6:10.3f} {(e - s) / 1e6:8.3f}  "
              f"{named(idle_by_span([(s, e)], spans))}")


if __name__ == "__main__":
    main(sys.argv[1])
