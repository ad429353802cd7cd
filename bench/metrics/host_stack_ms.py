"""Host milliseconds per round in ``serve_rounds``' ``serve.stack`` span:
stacking a call's metrics, one ``jnp.stack`` per metric and once a call,
summed over the traced window and divided by its rounds (``stages.py``)."""
import stages


def read(run):
    return stages.per_round_ms(stages.of(run).host_s.get("serve.stack"), run)
