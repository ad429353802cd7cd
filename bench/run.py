#!/usr/bin/env python3
"""Run one benchmark cell once on a TPU and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``). A run:

1. puts JAX's persistent compilation cache at ``<checkout>/.jax_cache``
   before JAX starts, and turns it on through the program's own
   ``repro.launch.runtime.setup_compile_cache``;
2. refuses any platform but a TPU, and fewer chips than the cell asks for
   (non-zero exit, no result line);
3. makes the initial data from ``--seed`` (``world.py``), builds the
   service (``service.py``) and drives its first rounds through the
   window's own call, blocking after each; these rounds warm up every
   program the window runs and are the rounds the reference checks;
4. measures for ``--seconds``: a closed loop, pipelined two calls deep,
   that dispatches call b+1 before it brings call b's metrics to the host;
5. reads the peak device memory, frees the program's state, follows the
   checked rounds with the plain reference (``reference.py``) and judges
   each compared number against its limit (``limits/<cell>.json``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and they are its per-layer metrics,
each read by ``metrics/<name>.py`` from the reduced trace (``trace.py``).
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
# fixed, inside the checkout; JAX reads it when it is imported
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402


def process_start() -> float:
    """``time.perf_counter()`` reading of this process's start (from
    ``/proc``; the interpreter's own start-up counts as set-up)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = process_start()


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "limits", name + ".json")) as f:
        limits = json.load(f)
    metrics = {"end_to_end": [m for m in bench["end_to_end"]
                              if name in m.get("workloads", [name])],
               "per_layer": [m for m in bench["per_layer"]
                             if name in m.get("workloads", [name])]}
    return cell, cfg, traffic, limits, metrics


class Counters:
    """Compile requests and persistent-cache hits, from JAX's monitoring
    events (``/jax/core/compile/backend_compile_duration`` fires for every
    backend compile request, hit or miss)."""

    def __init__(self):
        import jax
        self.compiles = self.hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self):
        return (self.compiles, self.hits, self.traces)


def _finite_failures(host, names):
    import numpy as np
    bad = None
    for k in names:
        if k in host:
            f = ~np.isfinite(np.asarray(host[k], np.float64))
            f = f.reshape(f.shape[0], -1).any(axis=1)
            bad = f if bad is None else (bad | f)
    return 0 if bad is None else int(bad.sum())


class RunFacts:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def checked_rounds(svc, cfg, traffic, seed, gen):
    """Build the stream from ``seed`` and drive its first calls through the
    window's own call, blocking after each. Returns the live state and the
    program's answers for the check."""
    import jax
    import numpy as np

    import compare
    import world as world_mod

    world = world_mod.make_world(cfg, traffic, seed)
    fl = "params" in world
    start = (jax.tree_util.tree_map(lambda v: np.asarray(v, np.float64), world["params"])
             if fl else None)
    state = svc.fresh_state(world)
    del world
    policy = cfg["association"] != "average"
    answers, globals_, assoc = [], [], []
    for _ in range(compare.check_calls(traffic["rounds_per_call"])):
        keys, plan = gen.next()
        state, m = svc.call(state, keys, plan)
        answers.append(svc.materialize(m))
        globals_.append(svc.global_params(state) if fl else None)
        if policy:   # one round a call (service.py)
            assoc.append(svc.association(state))
    jax.block_until_ready(state)
    answers = {k: np.concatenate([a[k] for a in answers]) for k in answers[0]}
    return state, {"answers": answers, "first": globals_[0], "last": globals_[-1],
                   "start": start,
                   "decisions": compare.decisions(answers, assoc if policy else None)}


# at most this many open destinations are tried against the other side
MAX_OPEN_DESTINATIONS = 16


def reference_answers(cfg, traffic, seed, margins, *, follow=None, control=False,
                      against=None):
    """The plain reference over the checked calls, from the same seed,
    following ``follow``'s near-line decisions (``reference.run``). With
    migration on and ``against`` (the other side's answers), each mover
    whose destination lies within ``margins["migration"]`` of its runner-up
    is tried at the runner-up, earliest first, and kept there where that
    brings the answers nearer to ``against``'s (``compare.distance``): the
    other side's destinations are not among its answers."""
    import numpy as np

    import compare
    import reference
    import traffic as traffic_mod
    import world as world_mod

    rpc = traffic["rounds_per_call"]
    n = compare.check_calls(rpc) * rpc
    world = world_mod.make_world(cfg, traffic, seed)
    rounds = traffic_mod.rounds(cfg, traffic, seed, 0, n)

    def go(f):
        answers, globals_ = reference.run(cfg, traffic, world, rounds, n, margins, follow=f,
                                          control=control)
        return {"answers": answers, "first": globals_[rpc - 1], "last": globals_[-1]}

    if against is None or not cfg["migration"]:
        return go(follow)
    alt = np.zeros((n, cfg["capacity"]), bool)
    ref, tried = go(dict(follow or {}, mig_alt=alt)), set()
    while len(tried) < MAX_OPEN_DESTINATIONS:
        near = [tuple(i) for i in np.argwhere(ref["answers"]["mig_gap"]
                                              < margins["migration"])]
        near = [i for i in near if i not in tried]
        if not near:
            break
        tried.add(near[0])
        alt2 = alt.copy()
        alt2[near[0]] = True
        ref2 = go(dict(follow or {}, mig_alt=alt2))
        if compare.distance(against, ref2["answers"]) < compare.distance(against,
                                                                         ref["answers"]):
            alt, ref = alt2, ref2
    return ref


def control_answers(cfg, traffic, seed, margins):
    """The control (the reference in bfloat16 at default matmul precision)
    and the reference that follows its near-line decisions."""
    import compare

    ctl = reference_answers(cfg, traffic, seed, margins, control=True)
    assoc = ctl["answers"]["assoc"] if cfg["association"] != "average" else None
    ref = reference_answers(cfg, traffic, seed, margins,
                            follow=compare.decisions(ctl["answers"], assoc),
                            against=ctl["answers"])
    return ctl, ref


def run_cell(cell, cfg, traffic, limits, metric_specs, *, seed, seconds, trace,
             service_factory=None):
    """One run of ``cell``; returns the result dict (and logs on stderr).
    ``service_factory(cfg, traffic)`` replaces the service under test (the
    check of the checks plants faults through it)."""
    import jax
    import numpy as np

    import compare
    import traffic as traffic_mod
    from service import Service

    counters = Counters()
    rpc, depth = traffic["rounds_per_call"], traffic["pipeline_depth"]
    span = jax.profiler.TraceAnnotation if trace else (lambda name: contextlib.nullcontext())

    svc = (service_factory or Service)(cfg, traffic)
    gen = traffic_mod.Traffic(cfg, traffic, seed, on_block=lambda: span("traffic_block"))
    state, prog = checked_rounds(svc, cfg, traffic, seed, gen)
    setup_s = time.perf_counter() - T_PROCESS
    c0 = counters.snap()
    log(f"[setup] {setup_s:.3f} s; compile requests {c0[0]}, persistent-cache hits "
        f"{c0[1]}, traces {c0[2]}; compile cache {CACHE_DIR}")

    names_check = ("round_time", "fl_loss", "fl_accuracy")
    lat, disp = [], []
    attempted = failed = done = 0
    pending = []

    def drain(n):
        nonlocal done, failed
        for _ in range(n):
            tc0, m0 = pending.pop(0)
            with span("materialize"):
                host = svc.materialize(m0)
            th = time.perf_counter()
            lat.append(th - tc0)
            done += rpc if th <= end else 0
            failed += _finite_failures(host, names_check)

    traced = None   # (rounds, dispatch seconds) of the traced window
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the benchmark's own spans only
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        win = jax.profiler.TraceAnnotation("window")
        win.__enter__()

    def stop_tracing():
        # the trace ends with the window's calls done, so every round it
        # dispatched is in the trace
        drain(len(pending))
        jax.block_until_ready(state)
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return attempted, list(disp)

    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        keys, plan = gen.next()
        tc = time.perf_counter()
        with span("serve_rounds"):
            state, m = svc.call(state, keys, plan)
        disp.append(time.perf_counter() - tc)
        attempted += rpc
        pending.append((tc, m))
        drain(max(0, len(pending) - depth + 1))
    t_close = time.perf_counter()
    if trace:
        traced = stop_tracing()
    drain(len(pending))
    jax.block_until_ready(state)
    window_s = max(t_close, end) - t0
    c1 = counters.snap()
    log(f"[window] {window_s:.3f} s, {attempted} rounds dispatched, {done} completed in "
        f"the window; compile requests in the window {c1[0] - c0[0]}, traces "
        f"{c1[2] - c0[2]}")

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}

    result_metrics = {}
    breakdown = None
    if not trace:
        vals = {
            "setup_s": setup_s,
            "rounds_per_s": done / seconds,
            "metrics_latency_p95_ms": 1e3 * float(np.percentile(lat, 95)) if lat else None,
            "peak_hbm_gib": peak / 2 ** 30 if peak is not None else None,
        }
        for spec in metric_specs["end_to_end"]:
            v = vals.get(spec["name"])
            if v is not None:
                result_metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        import flops
        import peaks
        import trace as trace_mod
        hlo = svc.compiled_text(state, gen)
        with open(os.path.join(TRACE_DIR, "step.hlo.txt"), "w") as f:
            f.write(hlo)
        reduced = trace_mod.reduce(trace_mod.find(TRACE_DIR))
        calls = flops.custom_calls(hlo)
        names = {c["name"] for c in calls}
        facts = RunFacts(trace=reduced, cfg=cfg, traffic=traffic,
                         rounds_traced=traced[0], rounds_per_call=rpc,
                         dispatch_s=traced[1], kernel_calls=calls,
                         is_kernel=lambda n: n in names,
                         peaks=peaks.peaks(dev.device_kind))
        for spec in metric_specs["per_layer"]:
            mod = importlib.import_module("metrics." + spec["name"])
            v = mod.read(facts)
            if v is not None:
                result_metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.top_ops(), "idle_gaps": reduced.idle_gaps()}
        log(f"[trace] {len(calls)} tpu_custom_call in the step, "
            f"{sum(len(v) for v in reduced.ops.values())} device ops in the window")

    # the check: free the program's state, follow the checked rounds
    del state
    gen.queue = []
    ref = reference_answers(cfg, traffic, seed, limits["margins"], follow=prog["decisions"],
                            against=prog["answers"])
    nums = compare.numbers(prog, ref, prog["start"])
    correct, rows = compare.judge(nums, limits)
    correct = correct and failed == 0

    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": float(v), "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k} {float(v)!r} limit {lim!r}")
    return result


def start_jax(chips) -> int:
    """Put the program on the path, turn the compile cache on and look for
    ``chips`` TPU chips; 0, or the exit code of what is missing."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.runtime import setup_compile_cache
    except ImportError as e:
        log(f"ERROR: the program is not in this checkout ({e})")
        return 2
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a cell's programs are few, and the eviction scan fails on
    # an entry whose access-time file is not written yet
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"ERROR: this benchmark runs on a TPU only; JAX found {devs[0].platform}")
        return 3
    if len(devs) < chips:
        log(f"ERROR: the cell needs {chips} chips; JAX found {len(devs)}")
        return 3
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, cfg, traffic, limits, metric_specs = load_cell(args.workload)
    err = start_jax(cell["chips"])
    if err:
        return err
    result = run_cell(cell, cfg, traffic, limits, metric_specs, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
