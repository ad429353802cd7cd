"""Device-busy milliseconds per round: the union of device-operation
intervals in the traced window over the rounds completed in it."""


def read(run):
    busy = run.trace.busy_s()
    if busy <= 0 or not run.rounds_traced:
        return None
    return 1e3 * busy / run.rounds_traced
