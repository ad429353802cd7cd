"""Reduction of a profiler trace to the numbers the per-layer readers use.

``reduce(path)`` reads the ``.xplane.pb`` the JAX profiler wrote, with
``jax.profiler.ProfileData`` alone, and returns a ``Reduced``:

* ``window``   - the benchmark's own ``window`` host span (start, end, ns);
* ``ops``      - every device operation inside the window, per device:
                 (HLO instruction name, start_ns, end_ns), from the device
                 planes' ``XLA Ops`` lines, whose events are named by their
                 HLO line (``%fusion.3 = f32[8]{0} fusion(...)``);
* ``spans``    - the benchmark's host spans inside the window:
                 (name, start_ns, end_ns).

and derived quantities: ``busy_s`` (the union of a device's op intervals,
averaged over the devices), ``window_s``, the top device operations by
summed time, and the longest idle gaps named by the host span they fall in.
Nothing here imports the program.
"""
import glob
import os
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
SPANS = ("serve_rounds", "materialize", "traffic_block")


class Reduced(NamedTuple):
    window: tuple
    ops: dict          # device plane name -> [(instruction, start_ns, end_ns)]
    spans: list        # [(name, start_ns, end_ns)]
    labels: dict       # instruction -> "instruction result-shape"

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(_union(iv) for iv in self.ops.values()) / len(self.ops) * 1e-9

    def op_seconds(self, match) -> float:
        """Summed device seconds of the operations ``match(name)`` selects,
        averaged over devices."""
        if not self.ops:
            return 0.0
        tot = sum(e - s for iv in self.ops.values() for n, s, e in iv if match(n))
        return tot / len(self.ops) * 1e-9

    def top_ops(self, k=10):
        """The ``k`` operations with the most summed self time (a ``while``
        loop's event encloses its body's events; the body's time is the
        body's)."""
        acc = {}
        for iv in self.ops.values():
            for n, t in _self_times(iv):
                acc[n] = acc.get(n, 0.0) + t * 1e-9 / len(self.ops)
        top = sorted(acc.items(), key=lambda x: -x[1])[:k]
        return [[self.labels.get(n, n), t] for n, t in top]

    def idle_gaps(self, k=10):
        """The ``k`` longest idle gaps of the first device, each named by the
        host span that covers most of it (``host_other`` when none does)."""
        if not self.ops:
            return []
        iv = sorted((s, e) for _, s, e in next(iter(self.ops.values())))
        gaps, cur = [], self.window[0]
        for s, e in iv:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if self.window[1] > cur:
            gaps.append((cur, self.window[1]))
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            best, cover = "host_other", 0
            for n, s, e in self.spans:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = n, c
            out.append([best, (b - a) * 1e-9])
        return out


def _union(iv) -> float:
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((s, e) for _, s, e in iv):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def _self_times(iv):
    """(name, duration less the durations of the events directly inside
    it) for every event."""
    out, stack = [], []
    for n, s, e in sorted(iv, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()[::3]))
        if stack:
            stack[-1][3] -= e - s
        stack.append([n, s, e, e - s])
    out.extend(tuple(x[::3]) for x in stack)
    return out


def _instruction(event_name: str):
    """An ``XLA Ops`` event is named by its HLO line, ``%name = shape op(...)``:
    the instruction name, and a label of the name and the result shape."""
    head, _, rest = event_name.partition(" = ")
    name = head.strip().lstrip("%")
    shape = ("tuple" if rest.startswith("(") else
             rest.split("{", 1)[0].split(" ", 1)[0])
    return name, (f"{name} {shape}" if shape else name)


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, window = [], None
    devices, labels = {}, {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    name, label = _instruction(ev.name)
                    labels[name] = label
                    ops.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' host span")
    lo, hi = window
    ops = {d: [(n, max(s, lo), min(e, hi)) for n, s, e in iv if e > lo and s < hi]
           for d, iv in devices.items()}
    spans = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    return Reduced(window=window, ops=ops, spans=spans, labels=labels)
