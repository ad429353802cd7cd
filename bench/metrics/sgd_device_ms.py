"""Device milliseconds per round of the FL round's local training: the self
time of the operations under ``fl_round``'s ``gather`` (participants and
their minibatches) and ``local_sgd`` scopes (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).group_s("sgd"), run)
