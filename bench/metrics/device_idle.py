"""Share of the traced window in which no operation ran on the device, in %."""


def read(run):
    w = run.trace.window_s
    if w <= 0 or not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / w)
