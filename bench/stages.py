"""Stage attribution of a traced window: device time by the round step's
named scopes, and the host time of ``serve_rounds``' own spans.

The program wraps each stage of its round step in a ``jax.named_scope``
(``core/serve.py:_round_step``, ``fl/stream.py:fl_round``). A scope lands in
the ``op_name`` metadata of every HLO instruction it traced, which the
compiled step's text keeps (``step.hlo.txt`` of a traced run), and the
device trace names each event by its instruction. So:

* ``stage_map(hlo_text)`` - instruction name -> the finest stage in its
  ``op_name`` path (None: no stage, e.g. a layout copy XLA inserted);
* ``of(run)`` - a run's ``Stages``, from ``TRACE_DIR`` where ``run.py``
  leaves the trace and ``step.hlo.txt``: each stage's device self time in
  the window (``trace.Reduced.top_ops``' rule: a ``while`` event's body time
  is the body's), the program's host spans (``serve.*``) inside the window,
  and the device's idle time by the innermost host span it falls in.

The names are the program's, copied here so the benchmark does not import
the program; a program without them (an older checkout) gives an empty
map and no host spans, and every reader then returns None.
"""
import bisect
import os
import re
from typing import NamedTuple

import trace as trace_mod

# stages of _round_step, then the stages of fl_round inside its `fl_round`
STEP_STAGES = ("association", "migration", "faults", "price", "chain", "fl_round",
               "churn", "dynamics", "replay")
FL_STAGES = ("gather", "local_sgd", "scatter", "eq4", "verify", "eq5", "eval")
STAGES = STEP_STAGES + FL_STAGES

# the per-layer metrics' groups of stages
GROUPS = {
    "edge_assoc": ("association", "migration", "faults", "price", "chain", "replay",
                   "dynamics"),
    "sgd": ("gather", "local_sgd"),
    # with fl_round's own ops outside its sub-stages (its metrics)
    "aggregate": ("scatter", "eq4", "verify", "eq5", "eval", "fl_round"),
    "churn": ("churn",),
}

# serve_rounds' host spans (core/serve.py SPAN_*)
HOST_SPANS = ("serve.inputs", "serve.enqueue", "serve.stack")
# innermost first: an idle stretch is put down to the first that covers it
IDLE_ORDER = HOST_SPANS + ("materialize", "traffic_block", "serve_rounds")

# where bench/run.py leaves a traced run's trace and compiled step
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")
MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def finest_stage(op_name: str):
    """The last stage named in an ``op_name`` path (its last component is
    the primitive, never a scope); of several merged paths, the first."""
    found = None
    for part in op_name.split(";")[0].split("/")[:-1]:
        if part in STAGES:
            found = part
    return found


def stage_map(hlo_text: str) -> dict:
    """Instruction name -> finest stage (or None) for every instruction of
    the compiled step's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = finest_stage(op.group(1)) if op else None
    return out


class Stages(NamedTuple):
    scoped: bool     # some instruction of the step carries a stage
    stage_s: dict    # stage (None: no stage) -> device self seconds, per device
    host_s: dict     # host span -> summed seconds inside the window
    host_n: dict     # host span -> count inside the window
    idle_s: float    # device-idle seconds in the window (first device)
    idle_by: dict    # IDLE_ORDER name or None (no span) -> idle seconds

    def group_s(self, group):
        """Device seconds of a group's stages; None when the step has none."""
        names = GROUPS[group]
        if not any(s in names for s in self.stage_s):
            return None
        return sum(t for s, t in self.stage_s.items() if s in names)


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _cut(iv, cover):
    """(parts of ``iv`` inside ``cover``, parts outside); both merged."""
    inside, outside = [], []
    j = 0
    for s, e in iv:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            a, b = max(cover[k][0], cur), min(cover[k][1], e)
            if a > cur:
                outside.append([cur, a])
            if b > a:
                inside.append([a, b])
            cur = max(cur, b)
            k += 1
        if e > cur:
            outside.append([cur, e])
    return inside, outside


def _length(iv):
    return sum(e - s for s, e in iv)


def module_name(hlo_text: str):
    """The compiled step's HLO module name (``HloModule <name>, ...``)."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    return m.group(1) if m else None


def read_trace(path: str, window, module) -> tuple:
    """From a trace, clipped to the window: the program's host spans
    (``HOST_SPANS``) as [(name, start_ns, end_ns)], and per device plane the
    intervals in which the step's module ran (its events on the device's
    ``XLA Modules`` line, named ``<module>(<program id>)``)."""
    from jax.profiler import ProfileData

    lo, hi = window
    spans, runs = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_mod.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        if e > lo and s < hi:
                            spans.append((ev.name, max(s, lo), min(e, hi)))
        elif plane.name.startswith(trace_mod.DEVICE_PREFIX) and module:
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    if ev.name == module or ev.name.startswith(module + "("):
                        runs.setdefault(plane.name, []).append(
                            [ev.start_ns, ev.start_ns + ev.duration_ns])
    return spans, {d: _merge(iv) for d, iv in runs.items()}


def _split(iv, runs):
    """(the ops of ``iv`` inside one of ``runs`` (merged, sorted), the
    rest); with no runs on record every op counts as inside."""
    if not runs:
        return list(iv), []
    starts = [a for a, _ in runs]
    inside, outside = [], []
    for op in iv:
        j = bisect.bisect_right(starts, op[1]) - 1
        (inside if j >= 0 and op[2] <= runs[j][1] else outside).append(op)
    return inside, outside


def attribute(red, smap: dict, spans: list, runs=None) -> Stages:
    """``Stages`` of a reduced trace (``trace.Reduced``), a stage map, the
    program's host spans and the step's runs per device (``read_trace``).
    An op outside the step's runs carries no stage, whatever its name: an
    instruction name is unique within one program only. Where a device has
    no runs on record, every op is taken by its name."""
    n_dev = max(len(red.ops), 1)
    stage_s = {}
    for dev, iv in red.ops.items():
        step, other = _split(iv, (runs or {}).get(dev))
        # self times within each part: one program's ops never nest in
        # another's, so a part's own nesting is the whole of it
        for part, stage in ((step, smap.get), (other, lambda n: None)):
            for n, t in trace_mod._self_times(part):
                st = stage(n)
                stage_s[st] = stage_s.get(st, 0.0) + t * 1e-9 / n_dev
    host_s, host_n = {}, {}
    for n, s, e in spans:
        host_s[n] = host_s.get(n, 0.0) + (e - s) * 1e-9
        host_n[n] = host_n.get(n, 0) + 1
    idle, idle_by = [], {}
    if red.ops:
        busy = _merge((s, e) for _, s, e in next(iter(red.ops.values())))
        _, idle = _cut([list(red.window)], busy)
        every = list(red.spans) + list(spans)
        rest = idle
        for name in IDLE_ORDER:
            inside, rest = _cut(rest, _merge((s, e) for n, s, e in every if n == name))
            idle_by[name] = _length(inside) * 1e-9
        idle_by[None] = _length(rest) * 1e-9
    return Stages(scoped=any(v is not None for v in smap.values()), stage_s=stage_s,
                  host_s=host_s, host_n=host_n, idle_s=_length(idle) * 1e-9,
                  idle_by=idle_by)


def of(run) -> Stages:
    """The run's ``Stages`` (read once, then kept on the run). A traced run
    leaves its trace and ``step.hlo.txt`` in ``TRACE_DIR``; a check of a
    fixture gives ``trace_path``, ``stage_map`` and ``step_module``
    instead."""
    st = getattr(run, "_stages", None)
    if st is None:
        path = getattr(run, "trace_path", None) or trace_mod.find(TRACE_DIR)
        smap = getattr(run, "stage_map", None)
        module = getattr(run, "step_module", None)
        if smap is None:
            with open(os.path.join(TRACE_DIR, "step.hlo.txt")) as f:
                hlo = f.read()
            smap, module = stage_map(hlo), module_name(hlo)
        spans, runs = read_trace(path, run.trace.window, module)
        st = attribute(run.trace, smap, spans, runs)
        run._stages = st
    return st


def per_round_ms(seconds, run):
    if seconds is None or not run.rounds_traced:
        return None
    return 1e3 * seconds / run.rounds_traced


def main(argv=None):
    """Print a traced run's stage table, host spans and idle attribution.

        python3 bench/stages.py <trace dir of a --trace 1 run> [rounds per call]
    """
    import json
    import sys

    argv = sys.argv[1:] if argv is None else argv
    tdir, rpc = argv[0], int(argv[1]) if len(argv) > 1 else 1
    with open(os.path.join(tdir, "step.hlo.txt")) as f:
        hlo = f.read()
    path = trace_mod.find(tdir)
    red = trace_mod.reduce(path)
    smap = stage_map(hlo)
    spans, runs = read_trace(path, red.window, module_name(hlo))
    st = attribute(red, smap, spans, runs)
    rounds = rpc * sum(1 for n, _, _ in red.spans if n == "serve_rounds")

    def ms(s):
        return None if s is None else 1e3 * s / rounds

    unscoped = {}
    for dev, iv in red.ops.items():
        step, other = _split(iv, runs.get(dev))
        for part, tag in ((step, ""), (other, " (other program)")):
            for n, t in trace_mod._self_times(part):
                if tag or smap.get(n) is None:
                    key = red.labels.get(n, n) + tag
                    unscoped[key] = unscoped.get(key, 0.0) + t * 1e-9 / len(red.ops)
    out = {
        "rounds": rounds, "step_runs_found": bool(runs),
        "busy_ms": ms(red.busy_s()),
        "stage_ms": {str(k): ms(v) for k, v in sorted(st.stage_s.items(), key=lambda x: -x[1])},
        "group_ms": {g: ms(st.group_s(g)) for g in GROUPS},
        "host_ms": {k: ms(v) for k, v in st.host_s.items()},
        "host_n": st.host_n,
        "idle_ms": ms(st.idle_s),
        "idle_by_ms": {str(k): ms(v) for k, v in st.idle_by.items()},
        "top_unscoped_ms": sorted(([k, ms(v)] for k, v in unscoped.items()),
                                  key=lambda x: -x[1])[:15],
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
