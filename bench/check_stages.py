#!/usr/bin/env python3
"""CPU check of the stage attribution against recorded fixtures.

    JAX_PLATFORMS=cpu python3 bench/check_stages.py

Every ``fixtures/<name>.json`` that holds a ``stage_map`` (a fixture cut by
``make_fixture.py --stages``) holds the values an owner array and masks gave
for its trace. This reduces the fixture with ``trace.py``, attributes it with
``stages.py`` and the fixture's stage map, and fails when a reader of
``metrics/`` that ``stages.py`` serves, or the count of a host span, differs.
"""
import glob
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REL = 1e-9


def _close(a, b):
    return abs(a - b) <= REL * max(abs(a), abs(b), 1e-30)


def check(path) -> int:
    """Failures of one fixture (``<name>.xplane.pb``)."""
    import run
    import stages
    import trace as trace_mod

    with open(path[:-len(".xplane.pb")] + ".json") as f:
        want = json.load(f)
    facts = run.RunFacts(trace=trace_mod.reduce(path), rounds_traced=want["rounds"],
                         trace_path=path, stage_map=want["stage_map"],
                         step_module=want["step_module"])
    name, bad = os.path.basename(path), 0
    for metric, v_want in want["stage_metrics"].items():
        v = importlib.import_module("metrics." + metric).read(facts)
        ok = v is not None and _close(v, v_want)
        bad += not ok
        print(f"{name} {metric}: {v!r} want {v_want!r} {'ok' if ok else 'FAIL'}")
    got = stages.of(facts).host_n
    for span, n in want["host_span_counts"].items():
        ok = got.get(span, 0) == n
        bad += not ok
        print(f"{name} {span} count: {got.get(span, 0)} want {n} {'ok' if ok else 'FAIL'}")
    return bad


def main():
    paths = []
    for p in sorted(glob.glob(os.path.join(HERE, "fixtures", "*.xplane.pb"))):
        with open(p[:-len(".xplane.pb")] + ".json") as f:
            if "stage_map" in json.load(f):
                paths.append(p)
    if not paths:
        print("no stage fixtures")
        return 1
    return 1 if sum(check(p) for p in paths) else 0


if __name__ == "__main__":
    sys.exit(main())
