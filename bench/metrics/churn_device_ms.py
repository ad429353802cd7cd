"""Device milliseconds per round of population churn: the self time of the
operations under the round step's ``churn`` scope (``churn_step`` and the
FL buffers' churn update; ``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).group_s("churn"), run)
