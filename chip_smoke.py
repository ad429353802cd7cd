#!/usr/bin/env python3
"""Bring-up smoke run of the DTWN service and the MADDPG trainer on a TPU.

    python3 chip_smoke.py              # one chip: the checks, phases A-C
    python3 chip_smoke.py --chips 4    # four chips: sharded parity + fit

One chip (the default), in one process:

* checks  — the segment-reduce kernel (every per-BS sum and the Eq. 4
  aggregation) against a float64 NumPy reference on small inputs: ragged
  twin and lane tiles, dropped ids, its gradient and its vmap, and Eq. 4
  against the per-BS weighted mean.
* phase A — the always-on service (``repro.launch.serve_dtwn``'s own
  build/warm-up/run path over ``serve.make_serve_init``,
  ``make_round_step`` and ``serve_rounds``) at the paper's Section V
  setting: capacity 100 twins, 5 BSs, 10 FL participants per round, the
  paper CNN at full width on CIFAR-10-sim, with faults, migration, the
  PBFT chain and the factorized MARL policy.
* phase B — the same path at capacity 10^4 with the tiny model and 1%
  join and leave churn.
* phase C — ``examples/marl_allocation.py``'s MADDPG scan trainer at 5000
  twins and 5 BSs.

Four chips (``--chips 4``), in one process driving all of them, and no
other phase: the served CNN path twin-sharded over the four chips against
the same run on ``jax.devices()[0]`` at a ragged capacity (participants,
population and accept decisions bit for bit, floats allclose), then the
capacity-1000 CNN service, whose per-twin state (~17.3 GB) fits only
split over the four.

What it prints before the last line — compile seconds, rounds/s, peak
device bytes, the segment-reduce backend each call site resolved to, the
``tpu_custom_call`` count of each compiled step — is a smoke check, not a
benchmark. The last line of stdout is ``{"ok": true, "device": {...}}``
only when every phase passed on a TPU. Without a TPU (``JAX_PLATFORMS=cpu``
included), with ``REPRO_PALLAS_INTERPRET`` set, or outside a checkout of
the repository it exits non-zero and prints no result. It adds no flags to
``LIBTPU_INIT_ARGS``. The compile cache is set up by
``repro.launch.runtime.setup_compile_cache``.
"""
import argparse
import inspect
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# capacity of the sharded parity run: not a multiple of 4, so the last
# shard holds padding rows
PARITY_CAPACITY = 202
FIT_CAPACITY = 1000
# 4-vs-1 float parity limit, relative to each quantity's own scale
PARITY_RTOL = 1e-5
CNN_PARAMS = 2_156_490
ROUNDS = 5   # served rounds per phase after the warm-up


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------


class BackendLog:
    """Records, while a program is traced, which backend each
    ``segment_reduce`` call site resolved to: a context manager wrapping
    ``repro.kernels.segment_reduce.resolve_backend``."""

    def __init__(self):
        self.sites = {}

    def __enter__(self):
        import importlib

        self._mod = importlib.import_module("repro.kernels.segment_reduce")
        self._orig = self._mod.resolve_backend
        here = os.path.abspath(self._mod.__file__)

        def resolve(n, num_segments, **kw):
            backend = self._orig(n, num_segments, **kw)
            site = "?"
            for fr in inspect.stack(0)[1:]:
                if os.path.abspath(fr.filename) != here:
                    site = (f"{os.path.relpath(fr.filename, ROOT)}:"
                            f"{fr.function}")
                    break
            self.sites.setdefault(site, set()).add(
                (backend, n, num_segments))
            return backend

        self._mod.resolve_backend = resolve
        return self

    def __exit__(self, *exc):
        self._mod.resolve_backend = self._orig
        return False

    def report(self) -> list:
        return [f"{site} -> "
                + ", ".join(sorted({b for b, _, _ in seen}))
                + f" (N,M) {sorted({(n, m) for _, n, m in seen})}"
                for site, seen in sorted(self.sites.items())]

    def backends(self) -> set:
        return {b for seen in self.sites.values() for b, _, _ in seen}


def custom_calls(compiled) -> int:
    """Pallas kernels (``tpu_custom_call``) in a compiled program."""
    return compiled.as_text().count("tpu_custom_call")


def peak_bytes(device=None):
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


# ---------------------------------------------------------------------------
# checks: the kernel against a float64 reference
# ---------------------------------------------------------------------------


def _oracle(values, assoc, m):
    import numpy as np

    v = np.asarray(values, np.float64).reshape(len(assoc), -1)
    out = np.zeros((m, v.shape[1]))
    a = np.asarray(assoc)
    for j in range(m):
        out[j] = v[a == j].sum(axis=0)
    return out


def phase_checks() -> dict:
    """The segment-reduce dispatch on this device against NumPy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hierarchy
    from repro.kernels.segment_reduce import resolve_backend, segment_reduce

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    out = {"backend": resolve_backend(1000, 7)}
    for i, (n, k, m) in enumerate([(1000, 1, 7), (2500, 3, 5),
                                   (64, 40_000, 5), (300, 129, 10)]):
        a = jax.random.randint(ks[2 * i], (n,), -1, m + 1)   # some dropped
        v = jax.random.normal(ks[2 * i + 1], (n, k))
        got = np.asarray(jax.jit(segment_reduce, static_argnums=2)(v, a, m))
        np.testing.assert_allclose(got, _oracle(v, a, m), rtol=1e-5,
                                   atol=1e-4, err_msg=f"(N,K,M)={(n, k, m)}")

    n, m = 777, 6
    a = jax.random.randint(ks[0], (n,), -1, m + 1)
    v = jax.random.normal(ks[1], (n, 4))
    w = jax.random.normal(ks[2], (m, 4))
    g = jax.jit(jax.grad(lambda v: jnp.sum(segment_reduce(v, a, m) * w)))(v)
    an = np.asarray(a)
    ref = np.where(((an >= 0) & (an < m))[:, None],
                   np.asarray(w)[np.clip(an, 0, m - 1)], 0.0)
    np.testing.assert_allclose(np.asarray(g), ref, rtol=1e-6, atol=1e-6)

    vb = jax.random.normal(ks[3], (3, n, 4))
    ab = jax.random.randint(ks[4], (3, n), 0, m)
    got = np.asarray(jax.jit(jax.vmap(
        lambda v, a: segment_reduce(v, a, m)))(vb, ab))
    for b in range(3):
        np.testing.assert_allclose(got[b], _oracle(vb[b], ab[b], m),
                                   rtol=1e-5, atol=1e-4)

    # Eq. 4 over a stacked model tree vs the per-BS data-weighted mean
    tree = {"w": jax.random.normal(ks[5], (n, 16, 8)),
            "b": jax.random.normal(ks[6], (n, 8))}
    d = jax.random.uniform(ks[7], (n,), minval=1.0, maxval=5.0)
    per_bs, bs_w = jax.jit(hierarchy.bs_aggregate_stacked,
                           static_argnums=3)(tree, d, a, m)
    dn = np.asarray(d, np.float64)
    for name, x in tree.items():
        xn = np.asarray(x, np.float64)
        for j in range(m):
            sel = an == j
            ref = (np.tensordot(dn[sel], xn[sel], axes=1) / dn[sel].sum()
                   if sel.any() else np.zeros(xn.shape[1:]))
            np.testing.assert_allclose(np.asarray(per_bs[name][j]), ref,
                                       rtol=1e-5, atol=1e-5)
    compiled = jax.jit(segment_reduce, static_argnums=2).lower(
        jax.ShapeDtypeStruct((100, 2**21), jnp.float32),
        jax.ShapeDtypeStruct((100,), jnp.int32), 5).compile()
    out["wide_custom_calls"] = custom_calls(compiled)
    return out


# ---------------------------------------------------------------------------
# phases A/B: the served path, through the CLI's own build
# ---------------------------------------------------------------------------


def service_args(*, capacity: int, model: str, rounds: int, seed: int,
                 churn: float = 0.0):
    from repro.launch import serve_dtwn

    argv = ["--capacity", str(capacity), "--n-bs", "5", "--rounds",
            str(rounds), "--fl", "--fl-model", model,
            "--fl-participants", "10", "--faults", "--migration",
            "--consensus", "--policy", "factorized", "--seed", str(seed)]
    if churn:
        argv += ["--join", str(churn), "--leave", str(churn)]
    return serve_dtwn.parse_args(argv)


def phase_service(args, *, ts=None) -> dict:
    """Build, warm up and serve ``args`` through the CLI's path; returns
    the metrics, timings, resolved backends and the step's kernel count."""
    import jax

    from repro.core.serve import round_keys
    from repro.fl.stream import get_model, plan_row
    from repro.launch import serve_dtwn

    with BackendLog() as backends:
        svc = serve_dtwn.build(args, ts=ts)
        warm_s = serve_dtwn.warm_up(svc)
    state, metrics, run_s = serve_dtwn.run(svc)
    t0 = time.perf_counter()
    compiled = svc.step.lower(_abstract(state), round_keys(svc.keys, 0),
                              svc.row, plan_row(svc.plan, 0)).compile()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(get_model(args.fl_model).init_params,
                       jax.random.PRNGKey(0))))
    return {"args": args, "svc": svc, "state": state, "metrics": metrics,
            "warm_s": warm_s, "run_s": run_s,
            "hlo_s": time.perf_counter() - t0,
            "custom_calls": custom_calls(compiled), "backends": backends,
            "n_params": n_params}


def check_service(res: dict, args) -> None:
    import numpy as np

    from repro.launch import serve_dtwn

    m = res["metrics"]
    err = serve_dtwn.check_metrics(m, fl=True)
    if err is not None:
        raise AssertionError(err)
    if not float(np.mean(m["fl_accept_frac"])) > 0.0:
        raise AssertionError("fl_accept_frac is 0: no BS aggregate accepted")
    if not args.join and not (m["fl_n_participants"]
                              == args.fl_participants).all():
        raise AssertionError(f"participants {m['fl_n_participants']}")
    pop = m["n_active"]
    steps = np.diff(pop.astype(np.int64))
    if not (steps == (m["n_joined"] - m["n_left"])[1:]).all():
        raise AssertionError("population accounting broke under churn")


def report_service(name: str, args, res: dict) -> None:
    m = res["metrics"]
    log(f"[{name}] capacity={args.capacity} model={args.fl_model} "
        f"({res['n_params']:,} params) bs={args.n_bs} "
        f"participants={args.fl_participants} churn={args.join}")
    log(f"[{name}] warm-up (compile + 1 round) {res['warm_s']:.2f}s; "
        f"compiled-step fetch {res['hlo_s']:.2f}s; "
        f"tpu_custom_call in compiled step: {res['custom_calls']}")
    log(f"[{name}] smoke rounds/s (not a benchmark): "
        f"{args.rounds / res['run_s']:.3f} ({args.rounds} rounds in "
        f"{res['run_s']:.3f}s)")
    log(f"[{name}] fl_loss {list(map(float, m['fl_loss']))}")
    log(f"[{name}] fl_accuracy {list(map(float, m['fl_accuracy']))}")
    log(f"[{name}] fl_accept_frac {list(map(float, m['fl_accept_frac']))} "
        f"round_time {list(map(float, m['round_time']))}")
    log(f"[{name}] n_active {m['n_active'].tolist()} joined "
        f"{m['n_joined'].tolist()} left {m['n_left'].tolist()}")
    for line in res["backends"].report():
        log(f"[{name}] segment_reduce {line}")


# ---------------------------------------------------------------------------
# phase C: the MADDPG scan trainer
# ---------------------------------------------------------------------------


def phase_trainer(*, twins: int = 5000, bs: int = 5, steps: int = 48) -> dict:
    import importlib.util

    import jax
    import numpy as np

    from repro.core.marl import train

    spec = importlib.util.spec_from_file_location(
        "marl_allocation", os.path.join(ROOT, "examples",
                                        "marl_allocation.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    cfg, dcfg, tcfg = example.configs(example.parse_args(
        ["--policy", "factorized", "--twins", str(twins), "--bs", str(bs),
         "--steps", str(steps)]))
    key = jax.random.PRNGKey(0)
    with BackendLog() as backends:
        t0 = time.perf_counter()
        compiled = train.lower(cfg, dcfg, tcfg, key).compile()
        compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, trace = compiled(key)
    trace = {k: np.asarray(v) for k, v in trace.items()}
    run_s = time.perf_counter() - t0
    for k in ("system_time", "reward", "critic_loss", "actor_loss"):
        if trace[k].shape != (steps,) or not np.isfinite(trace[k]).all():
            raise AssertionError(f"trainer {k}: {trace[k]}")
    n_upd = steps - tcfg.warmup
    if not (trace["critic_loss"][tcfg.warmup:] != 0.0).any():
        raise AssertionError("no MADDPG update ran after warm-up")
    return {"compile_s": compile_s, "run_s": run_s, "trace": trace,
            "custom_calls": custom_calls(compiled), "backends": backends,
            "n_updates": n_upd}


# ---------------------------------------------------------------------------
# four chips: sharded parity and fit
# ---------------------------------------------------------------------------


def _rel_diff(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = float(np.max(np.abs(a))) if a.size else 0.0
    diff = float(np.max(np.abs(a - b))) if a.size else 0.0
    return diff / scale if scale > 0.0 else diff


def phase_parity(*, capacity: int = PARITY_CAPACITY, rounds: int = 3,
                 n_chips: int = 4, seed: int = 0) -> dict:
    """The served CNN path twin-sharded over ``n_chips`` devices vs the
    same run on ``jax.devices()[0]``."""
    import jax
    import numpy as np

    from repro.core.sharding import TwinSharding

    args = service_args(capacity=capacity, model="cnn", rounds=rounds,
                        seed=seed)
    one = phase_service(args, ts=None)
    sh = phase_service(args, ts=TwinSharding.make(n_chips))
    check_service(one, args)
    check_service(sh, args)
    m1, m4 = one["metrics"], sh["metrics"]
    # floats: the sharded path adds per-shard partials in another order.
    # Each is held to PARITY_RTOL of its own scale (max |diff| / max |x|
    # on device 0); every reading is logged before any check is judged.
    p1 = jax.tree_util.tree_map(np.asarray, one["state"].fl.params)
    p4 = jax.tree_util.tree_map(np.asarray, sh["state"].fl.params)
    pairs = {k: (m1[k], m4[k]) for k in (
        "fl_bs_weight", "round_time", "honest_stake_share", "fl_loss",
        "fl_accuracy")}
    pairs.update({f"global_model.{k}": (p1[k], p4[k]) for k in p1})
    rel = {k: _rel_diff(a, b) for k, (a, b) in pairs.items()}
    log(f"[parity] max |diff| / max |x| per float: {rel}")
    # counts and accept decisions bit for bit
    for k in ("fl_n_participants", "n_active", "n_joined", "n_left",
              "fl_accept_frac", "accept_frac"):
        np.testing.assert_array_equal(m1[k], m4[k], err_msg=k)
    over = {k: r for k, r in rel.items() if not r <= PARITY_RTOL}
    if over:
        raise AssertionError(f"sharded floats beyond {PARITY_RTOL} "
                             f"relative: {over}")
    return {"one": one, "sharded": sh, "rel_diff": rel}


def phase_fit(*, capacity: int = FIT_CAPACITY, rounds: int = 3,
              n_chips: int = 4, seed: int = 0) -> dict:
    """The capacity-``capacity`` CNN service sharded over ``n_chips``: its
    twin buffers must be split across every device."""
    import jax

    from repro.core.sharding import TwinSharding

    args = service_args(capacity=capacity, model="cnn", rounds=rounds,
                        seed=seed)
    ts = TwinSharding.make(n_chips)
    res = phase_service(args, ts=ts)
    check_service(res, args)
    buf = res["state"].fl.twin_params["fc1_w"]
    shards = buf.addressable_shards
    rows = sorted(s.data.shape[0] for s in shards)
    devs = {s.device for s in shards}
    if len(devs) != n_chips or rows != [ts.local_n(capacity)] * n_chips:
        raise AssertionError(f"fc1 twin buffer not split over {n_chips} "
                             f"devices: rows per shard {rows}")
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (res["state"].fl.twin_params, res["state"].fl.twin_mom)))
    res["state_bytes"] = state_bytes
    res["rows_per_shard"] = rows
    res["peaks"] = {str(d): peak_bytes(d) for d in jax.devices()[:n_chips]}
    return res


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _fail(msg: str, code: int = 2) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: checks + phases A-C; 4: the four-chip sharded "
                         "parity and fit phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(SRC, "repro", "core", "serve.py")):
        return _fail(f"no repository next to this script ({SRC}/repro is "
                     f"missing); run it from a checkout")
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        return _fail("REPRO_PALLAS_INTERPRET is set: the smoke run must "
                     "execute the compiled kernels, not the interpreter")
    sys.path.insert(0, SRC)

    import jax

    from repro.launch.runtime import device_info, setup_compile_cache

    cache = setup_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        return _fail(f"no TPU: JAX platform is {dev['platform']!r} "
                     f"({dev['kind']}); this script never falls back to it")
    if dev["count"] < args.chips:
        return _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
                     f"found {dev['count']}")
    log(f"device {dev['platform']} {dev['kind']} x{dev['count']}; "
        f"jax {jax.__version__}; compile cache {cache}; "
        f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")

    failed = []

    def phase(name, fn):
        log(f"=== {name}")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
            return
        log(f"[{name}] ok in {time.perf_counter() - t0:.1f}s; peak bytes "
            f"in use so far {peak_bytes()}")

    def require_kernel(name, res):
        if res["custom_calls"] < 1 or "pallas" not in res["backends"].\
                backends():
            raise AssertionError(f"{name}: compiled step holds no "
                                 f"segment-reduce kernel")

    def checks():
        res = phase_checks()
        log(f"[checks] backend={res['backend']}; (N=100, K=2^21, M=5) "
            f"compiled with {res['wide_custom_calls']} tpu_custom_call")
        if res["backend"] != "pallas" or res["wide_custom_calls"] < 1:
            raise AssertionError("the TPU dispatch is not the Pallas kernel")

    def service(name, capacity, model, churn=0.0):
        def fn():
            sargs = service_args(capacity=capacity, model=model,
                                 rounds=ROUNDS, seed=args.seed,
                                 churn=churn)
            res = phase_service(sargs)
            report_service(name, res["args"], res)
            check_service(res, sargs)
            require_kernel(name, res)
            if model == "cnn" and res["n_params"] != CNN_PARAMS:
                raise AssertionError(f"CNN has {res['n_params']} params")
        return fn

    def trainer():
        res = phase_trainer()
        tr = res["trace"]
        log(f"[C] compile {res['compile_s']:.2f}s; run {res['run_s']:.3f}s "
            f"for {len(tr['system_time'])} steps ({res['n_updates']} "
            f"updates); tpu_custom_call in compiled trainer: "
            f"{res['custom_calls']}")
        log(f"[C] critic_loss last {float(tr['critic_loss'][-1])}, actor_loss "
            f"last {float(tr['actor_loss'][-1])}, system_time "
            f"{float(tr['system_time'][0])} -> "
            f"{float(tr['system_time'][-1])}")
        for line in res["backends"].report():
            log(f"[C] segment_reduce {line}")
        require_kernel("C", res)

    def four_chips():
        par = phase_parity(rounds=ROUNDS, seed=args.seed)
        for tag in ("one", "sharded"):
            r = par[tag]
            log(f"[parity:{tag}] warm-up {r['warm_s']:.2f}s; smoke rounds/s "
                f"{ROUNDS / r['run_s']:.3f}; tpu_custom_call "
                f"{r['custom_calls']}; fl_loss "
                f"{list(map(float, r['metrics']['fl_loss']))}")
            require_kernel(f"parity:{tag}", r)
        log(f"[parity] capacity {PARITY_CAPACITY} over 4 chips == device 0: "
            f"participants/n_active/accepts bitwise; floats within "
            f"{PARITY_RTOL} relative: {par['rel_diff']}")
        del par
        fit = phase_fit(rounds=ROUNDS, seed=args.seed)
        report_service("fit", fit["args"], fit)
        log(f"[fit] twin params + momentum {fit['state_bytes']} bytes; "
            f"fc1 rows per shard {fit['rows_per_shard']}")
        for d, b in fit["peaks"].items():
            log(f"[fit] {d} peak_bytes_in_use {b}")
        require_kernel("fit", fit)

    if args.chips == 4:
        phase("four chips: sharded parity + fit", four_chips)
    else:
        phase("checks", checks)
        phase("A: service, paper CNN, capacity 100", service("A", 100, "cnn"))
        phase("B: service, tiny, capacity 10^4, 1% churn",
              service("B", 10_000, "tiny", churn=0.01))
        phase("C: MADDPG trainer, 5000 twins", trainer)

    if failed:
        return _fail(f"failed phases: {failed}", code=1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
