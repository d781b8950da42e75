"""A kernel's share of its roofline, in percent.

The least time the chip could take for the calls the trace holds (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, both
from ``chipbench/opsbytes/<opsbytes>.py`` and the cell's shapes) over
the summed device time of the events whose name matches ``kernel``.
Returns nothing where the trace holds no such event. ``bound`` in the
metric's file says which of the two binds at the cell's shapes.
"""

import importlib


def read(trace, args, facts, peaks):
    ns, calls = trace.op_ns(args["kernel"])
    if not calls or ns <= 0:
        return None
    fn = importlib.import_module("chipbench.opsbytes." + args["opsbytes"])
    ops, byts = fn.ops_bytes({**facts["shapes"], **facts["counters"]},
                             calls)
    least = max(ops / peaks["bf16_flops_per_s"],
                byts / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
