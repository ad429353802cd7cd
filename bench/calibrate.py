#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--faults 3]

In one process, for every seed: the program's checked rounds (through the
window's own call, as a run drives them) against the plain reference, and
the control (the reference in bfloat16 at default matmul precision) against
the reference. For the first ``--faults`` seeds, also each planted fault of
``faults.py`` against the reference. Prints one JSON line per reading:
``{"seed", "kind", "numbers", "near"}`` with ``kind`` one of ``program``,
``control`` or a fault's name, and ``near`` the decisions within the
margins that the reference followed (``mig_open``: the movers whose
destination lay within the migration margin; ``mig_gap_min``: the
narrowest destination margin of a mover); the control's line adds ``gaps``, its
largest gaps in what the decisions read (a margin has to lie above them).
Needs a TPU, as a run does; the limits in ``limits/<cell>.json`` are set
from these readings (``PERF.md`` gives them).
"""
import argparse
import json
import sys

import run


def decision_gaps(ctl, ref):
    """The control's largest gaps, against the reference that follows it,
    in what the two decisions read: the actors' scores and the BSs' holdout
    losses. A decision margin has to lie above these."""
    import numpy as np
    c, r = ctl["answers"], ref["answers"]
    return {name: float(np.max(np.abs(np.asarray(c[k], np.float64)
                                      - np.asarray(r[k], np.float64))))
            for name, k in (("bs_loss", "fl_bs_loss"), ("scores", "scores")) if k in r}


def emit(seed, kind, nums, ref, margins, gaps=None):
    import numpy as np
    a = ref["answers"]
    line = {"seed": seed, "kind": kind, "numbers": {k: float(v) for k, v in nums.items()},
            # decisions within the margins, which the reference followed
            "near": {k: int(np.sum(a[k])) for k in ("assoc_near", "verify_near") if k in a}}
    if "mig_gap" in a:
        line["near"]["mig_open"] = int(np.sum(a["mig_gap"] < margins["migration"]))
        line["mig_gap_min"] = float(np.min(a["mig_gap"]))
    if gaps is not None:
        line["gaps"] = gaps
    print(json.dumps(line), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    cell, cfg, traffic, limits, _ = run.load_cell(args.workload)
    err = run.start_jax(cell["chips"])
    if err:
        return err

    import compare
    import traffic as traffic_mod
    from faults import faulty, kinds
    from service import Service

    seeds = [int(s) for s in args.seeds.split(",")]
    services = {"program": Service(cfg, traffic)}
    margins = limits["margins"]
    for i, seed in enumerate(seeds):
        for kind in ["program"] + (list(kinds(cfg)) if i < args.faults else []):
            if kind not in services:
                services[kind] = faulty(kind)(cfg, traffic)
            svc = services[kind]
            state, prog = run.checked_rounds(svc, cfg, traffic, seed,
                                             traffic_mod.Traffic(cfg, traffic, seed))
            del state
            ref = run.reference_answers(cfg, traffic, seed, margins,
                                        follow=prog["decisions"], against=prog["answers"])
            emit(seed, kind, compare.numbers(prog, ref, prog["start"]), ref, margins)
        ctl, ref = run.control_answers(cfg, traffic, seed, margins)
        emit(seed, "control", compare.numbers(ctl, ref, prog["start"]), ref, margins,
             gaps=decision_gaps(ctl, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
