"""Share, in percent, of the device's busy time that falls inside the
runs of the modules whose name matches ``module``."""


def read(trace, args, facts, peaks):
    busy, runs = trace.module_busy_ns(
        args["module"], args.get("holds"), args.get("lacks"))
    total = trace.busy_s() * 1e9 * max(len(trace.devices), 1)
    if not runs or total <= 0:
        return None
    return 100.0 * busy / total
