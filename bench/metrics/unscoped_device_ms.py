"""Device milliseconds per round of the operations that carry no stage of
the round step, such as the layout copies and pads XLA inserts: their self
time (``stages.py``). None where the step carries no stage at all."""
import stages


def read(run):
    st = stages.of(run)
    if not st.scoped:
        return None
    return stages.per_round_ms(st.stage_s.get(None, 0.0), run)
