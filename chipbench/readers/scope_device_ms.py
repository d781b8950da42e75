"""Device milliseconds of one phase of a compiled program, per run of
the modules whose name matches ``module``: the own time (an instant is
counted once, for the innermost operation) of the operations whose
``op_name`` holds one of ``scopes`` as a word anywhere in its path
(``jax.named_scope`` in the program; a backward operation carries
``transpose(jvp(mlp))``) or whose name matches a kernel pattern.
``kernels_of`` names metric files whose ``args.kernel`` patterns are
taken as they stand, so a Pallas kernel that no scope may enclose is
counted by the very pattern its roofline metric holds.

``"unclaimed_by": [metric, ...]`` turns it round: the operations of the
module that none of the named metrics' files claims.

A fusion belongs to the scope of the ``op_name`` it carries, the one of
the operation XLA rooted it at. Returns nothing where the trace holds
no operation with a scope in its path (a program without scopes).
"""

import re

from chipbench.readers import program_spans
from chipbench.run import load_json


def claims(args: dict):
    """op -> bool for the operations this metric's arguments claim."""
    rx = program_spans.scope_rx(args["scopes"])
    kernels = [re.compile(k) for k in args.get("kernels", [])] + [
        re.compile(load_json("metrics", m + ".json")["args"]["kernel"])
        for m in args.get("kernels_of", [])]
    return lambda op: bool(rx.search(op[3])) or any(
        k.search(op[2]) for k in kernels)


def device_ns(trace, module: str, claim) -> tuple:
    """(own ns of the claimed operations, runs of the module, whether
    any operation of the module has a scope path at all)."""
    rows, runs = trace.module_ops(module)
    scoped = any(program_spans.scopes_of(op[3]) for op, _ in rows)
    return sum(own for op, own in rows if claim(op)), runs, scoped


def read(trace, args, facts, peaks):
    if "unclaimed_by" in args:
        others = [claims(load_json("metrics", m + ".json")["args"])
                  for m in args["unclaimed_by"]]

        def claim(op):
            return not any(c(op) for c in others)
    else:
        claim = claims(args)
    ns, runs, scoped = device_ns(program_spans.load(), args["module"], claim)
    if not runs or not scoped:
        return None
    return ns / 1e6 / runs
