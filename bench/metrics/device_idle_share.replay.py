"""Share of a replay window in which no program ran on the device (the
profiler's trace, averaged over the chips)."""


def read(run):
    if run.kind != "replay" or run.device is None:
        return None
    return 100.0 * (1.0 - run.device.busy_s / run.device.window_s)
