"""A count the run made itself: ``scale * counters[counter]``, over
``counters[per]`` where given."""


def read(trace, args, facts, peaks):
    c = facts["counters"]
    value = c.get(args["counter"])
    if value is None:
        return None
    if "per" in args:
        if not c.get(args["per"]):
            return None
        value = value / c[args["per"]]
    return args.get("scale", 1.0) * value
