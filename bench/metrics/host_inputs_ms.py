"""Host milliseconds per round in ``serve_rounds``' ``serve.inputs`` span:
slicing one round's inputs out of the call's stacks (``round_keys``,
``_row_t``, ``plan_row``), summed over the traced window and divided by its
rounds (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).host_s.get("serve.inputs"), run)
