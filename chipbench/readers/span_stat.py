"""One number from the arguments or the durations of the program's own
host spans whose name matches ``span``, over the traced window:

  ``"mean_of": a``            the mean of argument ``a``, each span
                              weighing its duration; with ``"over": b``
                              the mean of ``a / b`` (spans whose ``b``
                              is 0 left out)
  ``"ratio": [a, b]``         sum of ``a`` over sum of ``b``
  ``"self_minus": pattern``   mean duration in ms, less the span's
                              children (spans of its thread inside it)
                              whose name matches ``pattern``

times ``scale`` where given. Returns nothing where no span matches or
the arguments are not there.
"""

import re

from chipbench.readers import program_spans


def stat(spans: list, args: dict):
    """``spans`` as (start, end, name, arguments, thread)."""
    rx = re.compile(args["span"])
    hit = [s for s in spans if rx.search(s[2])]
    if not hit:
        return None
    scale = args.get("scale", 1.0)
    if "mean_of" in args:
        a, b = args["mean_of"], args.get("over")
        rows = [(s[1] - s[0], s[3][a] / s[3][b] if b else s[3][a])
                for s in hit if a in s[3] and (
                    b is None or s[3].get(b))]
        weight = sum(w for w, _ in rows)
        return scale * sum(w * v for w, v in rows) / weight \
            if weight else None
    if "ratio" in args:
        a, b = args["ratio"]
        den = sum(s[3].get(b, 0) for s in hit)
        return scale * sum(s[3].get(a, 0) for s in hit) / den \
            if den else None
    minus = re.compile(args["self_minus"])
    kids = [s for s in spans if minus.search(s[2])]
    own = 0
    for s in hit:
        own += (s[1] - s[0]) - sum(
            k[1] - k[0] for k in kids
            if k[4] == s[4] and k[0] >= s[0] and k[1] <= s[1])
    return scale * own / 1e6 / len(hit)


def read(trace, args, facts, peaks):
    return stat(program_spans.load().spans, args)
