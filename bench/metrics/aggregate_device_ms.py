"""Device milliseconds per round of the FL round's aggregation: the self
time of the operations under ``fl_round``'s ``scatter``, ``eq4`` (with the
segment-reduce kernel), ``verify``, ``eq5`` and ``eval`` scopes, and of
``fl_round``'s own operations outside them (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).group_s("aggregate"), run)
