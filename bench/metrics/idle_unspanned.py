"""Share of the device's idle time in the traced window during which the
host is in none of the program's (``serve.*``) or the benchmark's host spans,
in %: idle time no span explains (``stages.py``). None where the program
writes no host span."""
import stages


def read(run):
    st = stages.of(run)
    if not st.host_n or st.idle_s <= 0:
        return None
    return 100.0 * st.idle_by[None] / st.idle_s
