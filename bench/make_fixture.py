#!/usr/bin/env python3
"""Cut a small fixture out of a recorded trace, for ``check_trace.py``.

    python3 bench/make_fixture.py --trace <run's .xplane.pb> \
        --hlo <run's step.hlo.txt> --calls 4 --rounds-per-call 1 \
        --out bench/fixtures/<name>

Keeps, from the first ``--calls`` ``serve_rounds`` calls of the trace's
window, the device operations of the first device and the benchmark's host
spans, with a new ``window`` span around them, and writes
``<out>.xplane.pb`` (through the profiler's own text-proto parser) and
``<out>.json``: the kernel names of the compiled step, the rounds in the
window, and the values ``check_trace.py`` must get back, worked out here by
a different method than ``trace.py`` uses (a nanosecond occupancy mask).
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import flops  # noqa: E402
import trace as trace_mod  # noqa: E402


def _proto(planes):
    """XSpace text proto: planes = [(name, {line: [(event, start_ns, dur_ns)]})]."""
    out, meta_id = [], 0
    for pid, (pname, lines) in enumerate(planes, 1):
        names = {}
        body = []
        for lid, (lname, events) in enumerate(lines.items(), 1):
            evs = []
            for n, s, d in events:
                if n not in names:
                    meta_id += 1
                    names[n] = meta_id
                evs.append(f"    events {{ metadata_id: {names[n]} offset_ps: {int(s) * 1000}"
                           f" duration_ps: {int(d) * 1000} }}")
            body.append(f"  lines {{ id: {lid} name: {json.dumps(lname)} timestamp_ns: 0\n"
                        + "\n".join(evs) + "\n  }")
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}"
                for n, i in names.items()]
        out.append(f"planes {{\n  id: {pid}\n  name: {json.dumps(pname)}\n"
                   + "\n".join(body + meta) + "\n}")
    return "\n".join(out) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", required=True)
    ap.add_argument("--hlo", required=True)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--rounds-per-call", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    full = trace_mod.reduce(args.trace)
    calls = sorted(s for s in full.spans if s[0] == "serve_rounds")
    lo = int(round(calls[0][1]))
    hi = int(round(calls[args.calls][1]))
    dev = sorted(full.ops)[0]
    # whole nanoseconds, so the proto and the mask below hold the same times
    ops = [(n, int(round(s)), int(round(e))) for n, s, e in full.ops[dev]
           if s >= lo and e <= hi]
    spans = [(n, int(round(s)), int(round(e))) for n, s, e in full.spans
             if s >= lo and e <= hi]
    base = lo - 1000     # keep every time positive
    planes = [(trace_mod.HOST_PLANE, {"python3": [(trace_mod.WINDOW_SPAN, lo - base, hi - lo)]
                                      + [(n, s - base, e - s) for n, s, e in spans]}),
              (dev, {trace_mod.OPS_LINE: [(n, s - base, e - s) for n, s, e in ops]})]
    with open(args.out + ".xplane.pb", "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_proto(planes)))

    with open(args.hlo) as f:
        kernels = sorted({c["name"] for c in flops.custom_calls(f.read())})
    mask = np.zeros(int(hi - lo), bool)
    for _, s, e in ops:
        mask[int(s - lo):int(e - lo)] = True
    d = np.diff(np.concatenate([[0], (~mask).astype(np.int8), [0]]))
    gaps = sorted((np.flatnonzero(d == -1) - np.flatnonzero(d == 1)).tolist(),
                  reverse=True)[:10]
    rounds = args.calls * args.rounds_per_call
    kernel_ns = sum(e - s for n, s, e in ops if n in kernels)
    expected = {
        "kernels": kernels,
        "rounds": rounds,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(mask.sum()) * 1e-9,
        "device_idle": 100.0 * (1.0 - mask.sum() / mask.size),
        "step_device_ms": 1e3 * mask.sum() * 1e-9 / rounds,
        "seg_reduce_ms": 1e3 * kernel_ns * 1e-9 / rounds,
        "idle_gap_s": [g * 1e-9 for g in gaps],
    }
    with open(args.out + ".json", "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected)[:500])
    print(f"{args.out}.xplane.pb: {os.path.getsize(args.out + '.xplane.pb')} bytes")


if __name__ == "__main__":
    main()
