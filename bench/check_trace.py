#!/usr/bin/env python3
"""CPU check of the trace reduction against recorded fixtures.

    JAX_PLATFORMS=cpu python3 bench/check_trace.py

Each ``fixtures/<name>.xplane.pb`` is a few rounds cut from a chip run's
trace by ``make_fixture.py``; ``fixtures/<name>.json`` holds the values a
nanosecond occupancy mask gave for it. This reduces every fixture with
``trace.py`` and the per-layer readers of ``metrics/`` and fails when
``device_idle``, ``step_device_ms``, ``seg_reduce_ms`` or the idle gaps of
the breakdown differ from them.
"""
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REL = 1e-9


def _close(a, b):
    return abs(a - b) <= REL * max(abs(a), abs(b), 1e-30)


def main():
    import run
    import trace as trace_mod
    from metrics import device_idle, seg_reduce_ms, step_device_ms

    paths = sorted(glob.glob(os.path.join(HERE, "fixtures", "*.xplane.pb")))
    if not paths:
        print("no fixtures")
        return 1
    bad = 0
    for path in paths:
        with open(path[:-len(".xplane.pb")] + ".json") as f:
            want = json.load(f)
        red = trace_mod.reduce(path)
        kernels = set(want["kernels"])
        facts = run.RunFacts(trace=red, rounds_traced=want["rounds"],
                             is_kernel=lambda n: n in kernels)
        got = {"device_idle": device_idle.read(facts),
               "step_device_ms": step_device_ms.read(facts),
               "seg_reduce_ms": seg_reduce_ms.read(facts),
               "busy_s": red.busy_s(), "window_s": red.window_s}
        gaps = [g for _, g in red.idle_gaps()]
        for k, v in got.items():
            ok = v is not None and _close(v, want[k])
            bad += not ok
            print(f"{os.path.basename(path)} {k}: {v!r} want {want[k]!r} {'ok' if ok else 'FAIL'}")
        ok = len(gaps) == len(want["idle_gap_s"]) and all(
            _close(a, b) for a, b in zip(gaps, want["idle_gap_s"]))
        bad += not ok
        print(f"{os.path.basename(path)} idle gaps: {gaps} {'ok' if ok else 'FAIL'}")
        print(f"{os.path.basename(path)} breakdown: {red.top_ops(5)} {red.idle_gaps(3)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
