"""The comparison that decides ``correct``: the program's first rounds
against the plain reference, number by number, each beside its limit.

Numbers (all from the stream's first calls, driven through the window's own
call in set-up: as many as hold ``CHECK_ROUNDS`` rounds, and at least two,
so that the state a call hands the next is checked too):

* ``round_time``   - widest relative gap of the Eq. 17 round time;
* ``fl_loss``      - widest relative gap of the global model's holdout loss;
* ``fl_bs_weight`` - widest gap of the Eq. 4 per-BS weights, relative to the
                     round's largest weight;
* ``change_r1``    - the global model's change over the first checked call
                     (round 1 at one round per call), and
* ``change_r3``    - its change over all checked calls (rounds 1-3): for
                     each leaf the gap between the program's and the
                     reference's norm of the change, over the larger of the
                     reference leaf's norm and the median leaf's; the worst
                     leaf. Leaves whose reference change is under a
                     thousandth of the median leaf's are left out (none of
                     these models has one);
* ``mismatches``   - rounds x fields where an exact answer differs
                     (population, joins, leaves, participants, the chain's
                     and the verify gate's accepted shares, the shares of
                     twins that migrated and straggled and of BSs in
                     outage), plus the twins whose association the program
                     decided otherwise than the reference where the
                     reference's margin was wide.

A configuration without FL has no ``fl_*`` or ``change_*`` number: its
cells are judged on ``round_time`` and ``mismatches``.

A cell's limits file (``limits/<cell>.json``) holds each number's limit under
``numbers`` and the decision margins (``reference.py``) under ``margins``.
"""
import numpy as np

CHECK_ROUNDS = 3
EXACT = ("n_active", "n_joined", "n_left", "fl_n_participants", "accept_frac",
         "fl_accept_frac", "migration_rate", "straggler_frac", "outage_frac")
ORDER = ("round_time", "fl_loss", "fl_bs_weight", "change_r1", "change_r3",
         "mismatches")


def check_calls(rounds_per_call):
    """Calls the check drives: those that hold ``CHECK_ROUNDS`` rounds, and
    at least two."""
    return max(2, -(-CHECK_ROUNDS // rounds_per_call))


def _rel(p, r):
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def decisions(answers, assoc=None):
    """The decisions a side took, in the form ``reference.run`` follows:
    the number of BSs its verify gate accepted in each round (with FL) and,
    where given, the (rounds, N) association its policy chose."""
    out = {}
    if "fl_bs_weight" in answers:
        sub = np.sum(np.asarray(answers["fl_bs_weight"]) > 0, axis=1)
        acc = np.rint(np.asarray(answers["fl_accept_frac"], np.float64) * sub)
        out["accepts"] = acc.astype(np.int32)
    if assoc is not None:
        out["assoc"] = np.asarray(assoc, np.int32)
    return out


def change_gap(prog_new, ref_new, start):
    """Worst-leaf gap of the change norms (see module docstring)."""
    names = sorted(start)
    d_p = {k: np.linalg.norm(np.asarray(prog_new[k], np.float64) - start[k]) for k in names}
    d_r = {k: np.linalg.norm(np.asarray(ref_new[k], np.float64) - start[k]) for k in names}
    med = float(np.median(list(d_r.values())))
    gaps = [abs(d_p[k] - d_r[k]) / max(d_r[k], med) for k in names
            if d_r[k] >= 1e-3 * med and med > 0]
    return max(gaps) if gaps else 0.0


def exact_mismatches(pa, ra):
    """Rounds x fields where an exact answer differs."""
    return sum(int(np.sum(np.asarray(pa[k]) != np.asarray(ra[k]))) for k in EXACT if k in ra)


def distance(pa, ra):
    """How far the reference's answers ``ra`` lie from ``pa``: exact
    mismatches first, then the summed relative gaps of the round time."""
    rt_p = np.asarray(pa["round_time"], np.float64)
    rt_r = np.asarray(ra["round_time"], np.float64)
    return exact_mismatches(pa, ra), float(np.sum(np.abs(rt_p - rt_r)
                                                  / np.maximum(np.abs(rt_r), 1e-30)))


def numbers(prog, ref, start):
    """``prog``/``ref``: {"answers": {name: (rounds, ...)}, "first": global
    model after the first checked call, "last": after the last}; ``ref``
    followed ``prog``'s near-line decisions. ``start``: the initial global
    model (float64 host arrays; None without FL). Returns {number: value}."""
    pa, ra = prog["answers"], ref["answers"]
    out = {"round_time": _rel(pa["round_time"], ra["round_time"]),
           "mismatches": exact_mismatches(pa, ra) + int(np.sum(ra["assoc_disagree"]))}
    if start is not None:
        w_p = np.asarray(pa["fl_bs_weight"], np.float64)
        w_r = np.asarray(ra["fl_bs_weight"], np.float64)
        out.update({
            "fl_loss": _rel(pa["fl_loss"], ra["fl_loss"]),
            "fl_bs_weight": float(np.max(np.abs(w_p - w_r).max(axis=1)
                                         / np.maximum(np.abs(w_r).max(axis=1), 1e-30))),
            "change_r1": change_gap(prog["first"], ref["first"], start),
            "change_r3": change_gap(prog["last"], ref["last"], start),
        })
    return out


def judge(nums, limits):
    """(correct, [(name, value, limit)]) in a fixed order."""
    if set(nums) != set(limits["numbers"]):
        raise ValueError(f"numbers {sorted(nums)} against limits {sorted(limits['numbers'])}")
    rows = [(k, nums[k], limits["numbers"][k]) for k in ORDER if k in nums]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
