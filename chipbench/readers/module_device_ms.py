"""Device-busy milliseconds inside the runs of the modules whose name
matches ``module``: per run, or per unit where one run holds several
(``"per_kernel": {"kernel": <pattern>, "per_unit": "layers"}`` counts a
unit for every ``shapes[per_unit]`` events of that kernel: a decode step
calls the decode kernel once a layer)."""


def read(trace, args, facts, peaks):
    busy, runs = trace.module_busy_ns(
        args["module"], args.get("holds"), args.get("lacks"))
    if not runs:
        return None
    units = runs
    if "per_kernel" in args:
        spec = args["per_kernel"]
        _, calls = trace.op_ns(spec["kernel"])
        units = calls / facts["shapes"][spec["per_unit"]]
    if not units:
        return None
    return busy / 1e6 / units
