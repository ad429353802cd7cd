"""Host milliseconds per round spent in ``serve_rounds`` until it returns
(the enqueue), from the host clock around every call of the traced window."""


def read(run):
    if not run.dispatch_s:
        return None
    return 1e3 * sum(run.dispatch_s) / (len(run.dispatch_s) * run.rounds_per_call)
