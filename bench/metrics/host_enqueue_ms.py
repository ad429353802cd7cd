"""Host milliseconds per round in ``serve_rounds``' ``serve.enqueue`` span: the
call of the jitted round step until it returns (the enqueue), summed over
the traced window and divided by its rounds (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).host_s.get("serve.enqueue"), run)
