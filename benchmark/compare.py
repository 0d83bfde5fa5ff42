"""The comparison that decides `correct`: the program's readings against the
plain reference's, each number beside a limit of its own.

Training numbers are gaps of norms by the worst leaf: |program - reference|
over the larger of the reference's norm of that leaf and of the median leaf,
so that a leaf whose gradient is all but zero does not decide the run.
"""
import sys

import numpy as np


def leaf_gaps(prog, ref, keep=None):
    """{leaf: |program - reference| over the larger of the reference's norm
    of that leaf and of the median leaf}."""
    names = [n for n in ref if keep is None or n in keep]
    floor = float(np.median([ref[n] for n in names]))
    gaps = {}
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
        gaps[n] = float(gap) if np.isfinite(gap) else float("inf")
    return gaps, floor


def worst_leaf_gap(prog, ref, keep=None):
    """-> (worst gap, its leaf, the median leaf's gap, the worst leaf's
    reference norm over the median leaf's)."""
    gaps, floor = leaf_gaps(prog, ref, keep)
    at = max(gaps, key=lambda n: (gaps[n], n))
    return (gaps[at], at, float(np.median(list(gaps.values()))),
            ref[at] / max(floor, 1e-30))


def moved_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is at least `share` of the median
    leaf's: the others (a key's bias under softmax) move under Adam by
    round-off alone and are left out of the parameters' change."""
    floor = share * float(np.median(list(ref_grad_norms.values())))
    return {n for n, g in ref_grad_norms.items() if g >= floor}


def train_numbers(prog, ref):
    """prog / ref: {"losses": [3], "grad_norms": {leaf: x},
    "change_norms": {leaf: x}} -> ({name: value}, where the worst were).

    `loss_gap` is the worst of the three steps and `first_loss_gap` the
    first step's alone; `*_norm_gap` is by the worst leaf and
    `*_norm_median_gap` by the median leaf, which one small noisy leaf
    cannot move; `grad_whole_norm_gap` is of the norm over all leaves."""
    rel = [abs(a - b) / max(abs(b), 1e-30)
           for a, b in zip(prog["losses"], ref["losses"])]
    if not all(np.isfinite(prog["losses"])):
        rel = [float("inf")] * len(rel)
    g, g_at, g_med, g_size = worst_leaf_gap(prog["grad_norms"],
                                            ref["grad_norms"])
    keep = moved_leaves(ref["grad_norms"])
    c, c_at, c_med, c_size = worst_leaf_gap(prog["change_norms"],
                                            ref["change_norms"], keep)
    total = lambda norms: float(np.sqrt(sum(  # noqa: E731
        v * v for v in norms.values())))
    whole = abs(total(prog["grad_norms"]) - total(ref["grad_norms"])) / max(
        total(ref["grad_norms"]), 1e-30)
    return ({"loss_gap": max(rel), "first_loss_gap": rel[0],
             "grad_norm_gap": g, "grad_norm_median_gap": g_med,
             "grad_whole_norm_gap": whole if np.isfinite(whole)
             else float("inf"),
             "change_norm_gap": c, "change_norm_median_gap": c_med},
            {"grad_norm_gap": [g_at, g_size],
             "change_norm_gap": [c_at, c_size]})


def judge(numbers, limits, not_compared=()):
    """[(name, value, limit, ok)], all_ok.  Every number needs a limit, or
    its name in the cell's `not_compared` with the reason in PERF.md; a
    number that is not finite fails."""
    rows, ok = [], True
    for name, value in numbers.items():
        if name in not_compared:
            continue
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": good})
        ok = ok and good
    return rows, ok


def print_checks(rows, stream=None):
    """The last lines on standard error: each number beside its limit."""
    stream = stream or sys.stderr
    for r in rows:
        print(f"check {r['name']}: value {r['value']:.6g} limit "
              f"{r['limit']:.6g} {'ok' if r['ok'] else 'FAIL'}", file=stream)
    stream.flush()


def checks_json(rows):
    return {r["name"]: {"value": r["value"], "limit": r["limit"]}
            for r in rows}
