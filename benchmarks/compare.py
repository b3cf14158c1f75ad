"""The comparisons that decide `correct`: program readings against the
plain reference's, each number with a limit of its own from the cell's
workload file."""
from __future__ import annotations

import math
import statistics

NO_NUMBER = 1e300    # stands for inf, nan or "no limit" in the result line


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Returns (gap, leaf path)."""
    names = [n for n in ref if keep is None or n in keep]
    med = statistics.median(ref[n] for n in names)
    worst, at = 0.0, ""
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, at = gap, n
    return worst, at


def moving_leaves(ref_grad_norms: dict, floor: float = 1e-3) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least
    `floor` of the median leaf's gradient norm."""
    med = statistics.median(ref_grad_norms.values())
    return {n for n, v in ref_grad_norms.items() if v >= floor * med}


def train_numbers(prog: dict, ref: dict) -> dict:
    """{name: value} of a train cell's compared numbers. `prog` and `ref`
    both hold "loss" (three floats), "grad" ({leaf: norm} of the first
    gradient as the optimizer gets it) and "change" ({leaf: norm} of the
    parameters' change after three steps)."""
    out = {f"loss{i + 1}": rel_gap(prog["loss"][i], ref["loss"][i])
           for i in range(3)}
    out["grad_norm"], _ = worst_leaf_gap(prog["grad"], ref["grad"])
    out["change_norm"], _ = worst_leaf_gap(
        prog["change"], ref["change"], keep=moving_leaves(ref["grad"]))
    return out


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every number. One without a
    limit is reported, not judged. A reading that is not finite fails, and
    is written as NO_NUMBER, so that the result line stays plain JSON."""
    out = {}
    for name, value in numbers.items():
        limit = limits.get(name)
        if not math.isfinite(value):
            value = NO_NUMBER
        out[name] = {"value": value,
                     "limit": NO_NUMBER if limit is None else limit,
                     "ok": limit is None or value <= limit}
    return out
