"""100 * (1 - union of the device's operation intervals / traced window)."""


def read(trace, args, facts, peaks):
    if not trace.devices or trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
