"""The comparison that decides ``correct``: numbers read from the timed
path against the plain reference, each held to the limit in
``limits/<workload>.json``.

Training: for a leaf's norm (the first gradient as the optimizer applies
it, or the change after the first steps) the gap is |program - reference|
over the larger of the reference's norm of that leaf and of the median
leaf; the numbers are the worst leaf's gap of each.  Leaves whose
reference gradient is under a thousandth of the median leaf's move under
Adam by round-off alone and are left out of the change.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best, over a sample of the served tokens.
"""
from __future__ import annotations

import math

import numpy as np

ROUNDOFF_LEAF = 1e-3


def counted_leaves(ref_grad_norms) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= ROUNDOFF_LEAF * np.median(g)


def _leaf_gaps(prog, ref, keep=None):
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def _worst(gaps, names, n=3):
    order = np.argsort(-gaps)[:n]
    return [[names[i], float(gaps[i])] for i in order]


def train_numbers(prog: dict, ref: dict, names: list) -> tuple:
    """(numbers, notes): the three numbers, and for the look at them the
    worst leaves of each and the median leaf's gaps."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grads = _leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    keep = counted_leaves(ref["grad_norms"])
    changes = _leaf_gaps(prog["change_norms"], ref["change_norms"], keep)
    grad_gap, change_gap = float(grads.max()), float(changes.max())
    kept = [n for n, k in zip(names, keep) if k]
    left_out = [n for n, k in zip(names, keep) if not k]
    notes = {"grad_gap_leaf": names[int(grads.argmax())],
             "change_gap_leaf": kept[int(changes.argmax())],
             "change_left_out": left_out,
             "grad_worst": _worst(grads, names),
             "change_worst": _worst(changes, kept),
             "grad_gap_median": float(np.median(grads)),
             "change_gap_median": float(np.median(changes)),
             "step_loss_gaps": (np.abs(lp - lr) / np.abs(lr)).tolist()}
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}, notes


def not_compared(limits: dict) -> set:
    """Names of the numbers a limit file does not leave out: an entry
    with ``"compared": false`` names a number with no upper reading,
    which is read and printed but decides nothing."""
    return {n for n, v in limits.items() if not v.get("compared", True)}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): each compared number beside its limit.  A
    number with no limit fails."""
    checks, ok = {}, True
    skip = not_compared(limits)
    for name, value in numbers.items():
        if name in skip:
            continue
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or not value <= limit:
            ok = False
    return ok, checks
