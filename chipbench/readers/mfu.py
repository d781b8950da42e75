"""The whole step's share of the chip's peak, in percent: the
operations the traced window's tokens REQUIRE (``required_flops``, from
shapes by ``chipbench/opsbytes/dense_gqa_flops.py``, nothing recomputed
counted) over the window's wall time, the chips and the published peak."""


def read(trace, args, facts, peaks):
    c = facts["counters"]
    if not c.get("required_flops") or not c.get("wall_s"):
        return None
    chips = max(len(trace.devices), 1)
    return 100.0 * c["required_flops"] / c["wall_s"] / chips \
        / peaks["bf16_flops_per_s"]
