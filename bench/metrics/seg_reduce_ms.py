"""Device milliseconds per round in the segment-reduce Pallas kernel: the
summed durations of its trace events over the rounds completed."""


def read(run):
    t = run.trace.op_seconds(run.is_kernel)
    if t <= 0 or not run.rounds_traced:
        return None
    return 1e3 * t / run.rounds_traced
