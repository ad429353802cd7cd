"""Device milliseconds per round of the round step's edge-association
stages: the self time of the operations under the ``association``,
``migration``, ``faults``, ``price``, ``chain``, ``replay`` and ``dynamics``
scopes (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).group_s("edge_assoc"), run)
