#!/usr/bin/env python3
"""Cut a stage fixture out of a recorded trace, for ``check_stages.py``.

    python3 bench/make_stage_fixture.py --trace <run's .xplane.pb> \
        --hlo <run's step.hlo.txt> --calls 3 --rounds-per-call 1 \
        --out bench/fixtures/<name>

Makes ``make_fixture.py``'s cut (the first ``--calls`` ``serve_rounds`` calls
of the window: the first device's operations, the benchmark's host spans,
and the values ``check_trace.py`` must get back, as it reads every fixture),
then adds the program's host spans (``serve.*``) and the step's runs on the
device's ``XLA Modules`` line to ``<out>.xplane.pb``, and to ``<out>.json``
the stage of each kept operation (``stages.stage_map`` of ``--hlo``), the
number of each host span, and the value of each per-layer metric that
``stages.py`` serves, worked out here with a nanosecond owner array (each
nanosecond belongs to the innermost operation running in it) and masks.
"""
import argparse
import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import make_fixture  # noqa: E402
import stages  # noqa: E402
import trace as trace_mod  # noqa: E402


def _program_events(path, dev, module, lo, hi):
    """The program's host spans inside [lo, hi], and the runs of the step's
    module on device ``dev`` that overlap it, in whole ns."""
    from jax.profiler import ProfileData

    spans, runs = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                s = int(round(ev.start_ns))
                e = int(round(ev.start_ns + ev.duration_ns))
                if (plane.name == trace_mod.HOST_PLANE and ev.name in stages.HOST_SPANS
                        and s >= lo and e <= hi):
                    spans.append((ev.name, s, e))
                elif (plane.name == dev and line.name == stages.MODULES_LINE
                        and ev.name.split("(")[0] == module and e > lo and s < hi):
                    runs.append((ev.name, max(s, lo), min(e, hi)))
    return spans, runs


def _stage_values(hlo, ops, spans, module_runs, lo, hi, rounds):
    """The stage map of the kept operations and what each of ``stages.py``'s
    readers must give, from an owner array and masks."""
    smap = stages.stage_map(hlo)
    kept = {n: smap.get(n) for n, _, _ in ops}
    busy = np.zeros(hi - lo, bool)
    in_step = np.zeros(hi - lo, bool)
    for _, s, e in module_runs:
        in_step[s - lo:e - lo] = True
    # covered_before[i]: nanoseconds of the step's runs before lo + i
    covered_before = np.concatenate([[0], np.cumsum(in_step, dtype=np.int64)])
    owner = np.full(hi - lo, -1, np.int32)
    # starts ascending, the longer first: an op painted after its parent
    # lies inside it, so every nanosecond ends with the innermost op
    order = sorted(ops, key=lambda x: (x[1], -x[2]))
    stage_of = []
    for i, (n, s, e) in enumerate(order):
        owner[s - lo:e - lo] = i
        busy[s - lo:e - lo] = True
        inside = covered_before[e - lo] - covered_before[s - lo] == e - s
        stage_of.append(kept[n] if inside or not module_runs else None)
    ns_of = np.bincount(owner[owner >= 0], minlength=len(order))
    by_stage = {}
    for st, t in zip(stage_of, ns_of):
        by_stage[st] = by_stage.get(st, 0) + int(t)

    def ms(ns):
        return 1e3 * ns * 1e-9 / rounds

    values = {f"{g}_device_ms": ms(sum(by_stage.get(x, 0) for x in names))
              for g, names in stages.GROUPS.items()
              if any(st in names for st in stage_of)}
    values["unscoped_device_ms"] = ms(by_stage.get(None, 0))
    counts = {}
    for name in stages.HOST_SPANS:
        own = [e - s for n, s, e in spans if n == name]
        counts[name] = len(own)
        values["host_" + name.split(".")[1] + "_ms"] = ms(sum(own))
    covered = np.zeros(hi - lo, bool)
    for _, s, e in spans:
        covered[s - lo:e - lo] = True
    idle = ~busy
    values["idle_unspanned"] = 100.0 * float((idle & ~covered).sum()) / float(idle.sum())
    return {"stage_map": kept, "step_module": stages.module_name(hlo),
            "host_span_counts": counts, "stage_metrics": values}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", required=True)
    ap.add_argument("--hlo", required=True)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--rounds-per-call", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    subprocess.run([sys.executable, make_fixture.__file__, "--trace", args.trace,
                    "--hlo", args.hlo, "--calls", str(args.calls),
                    "--rounds-per-call", str(args.rounds_per_call), "--out", args.out],
                   check=True)
    # the window, device and offset make_fixture.py cut with
    full = trace_mod.reduce(args.trace)
    calls = sorted(s for s in full.spans if s[0] == "serve_rounds")
    lo, hi = int(round(calls[0][1])), int(round(calls[args.calls][1]))
    dev = sorted(full.ops)[0]
    base = lo - 1000
    ops = [(n, int(round(s)), int(round(e))) for n, s, e in full.ops[dev]
           if s >= lo and e <= hi]
    bench_spans = [(n, int(round(s)), int(round(e))) for n, s, e in full.spans
                   if s >= lo and e <= hi]
    with open(args.hlo) as f:
        hlo = f.read()
    spans, runs = _program_events(args.trace, dev, stages.module_name(hlo), lo, hi)

    planes = [(p.name, {ln.name: [(ev.name, int(round(ev.start_ns)), int(round(ev.duration_ns)))
                                  for ev in ln.events] for ln in p.lines})
              for p in ProfileData.from_file(args.out + ".xplane.pb").planes]
    for name, lines in planes:
        if name == trace_mod.HOST_PLANE:
            next(iter(lines.values())).extend((n, s - base, e - s) for n, s, e in spans)
        elif name == dev:
            lines[stages.MODULES_LINE] = [(n, s - base, e - s) for n, s, e in runs]
    with open(args.out + ".xplane.pb", "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(make_fixture._proto(planes)))

    with open(args.out + ".json") as f:
        expected = json.load(f)
    expected.update(_stage_values(hlo, ops, bench_spans + spans, runs, lo, hi,
                                  args.calls * args.rounds_per_call))
    with open(args.out + ".json", "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected["stage_metrics"]))


if __name__ == "__main__":
    main()
