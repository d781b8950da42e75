"""Device idle time that lies under the program's own host spans.

The idle stretches are the first device's (``xplane.gaps_ns`` over its
operations, inside the traced window); each instant of one goes to the
innermost ``pt.*`` span open on the host at that instant, on the
trace's one clock. Counted is the idle time whose innermost span
matches ``span``: as a share, in percent, of all idle time, or with
``"per": <pattern>`` in milliseconds per span matching that pattern
(``pt.train.step``: a step). Returns nothing where the trace holds no
program span.
"""

import re

from chipbench.readers import program_spans


def idle_ns(gaps: list, spans: list, pattern: str) -> tuple:
    """(idle ns under an innermost span matching, all idle ns)."""
    rx = re.compile(pattern)
    by_span = program_spans.idle_by_span(gaps, spans)
    return (sum(ns for n, ns in by_span.items() if n and rx.search(n)),
            sum(by_span.values()))


def read(trace, args, facts, peaks):
    t = program_spans.load()
    if not t.spans or not t.devices:
        return None
    spans = [s[:3] for s in t.spans]
    under, total = idle_ns(program_spans.device_gaps(t), spans,
                           args["span"])
    if "per" in args:
        per = re.compile(args["per"])
        count = sum(1 for s in spans if per.search(s[2]))
        return under / 1e6 / count if count else None
    return 100.0 * under / total if total else None
