"""The numbers that decide ``correct`` for training cells.

Readings of one side (program, or a stand-in) over the first steps:
``losses`` (one per step), ``grad`` ({leaf: norm} of the first step's
clipped gradient as the optimizer got it) and ``change`` ({leaf: norm} of
the parameters' change after the checked steps). A stacked leaf counts
once per layer (``layers/attn/wq#4``).

Gap of a leaf: |norm_program - norm_reference| / max(norm_reference of the
leaf, median leaf norm of the reference). Leaves whose reference gradient
is under 1e-3 of the median leaf's in every checked step move by round-off
under Adam alone and are left out of the change; leaves the reference
never touched must not move at all (an exact comparison)."""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from harness.bench import Check

TINY_GRAD = 1e-3


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None):
    names = [k for k in ref if keep is None or k in keep]
    if not names:
        return float("nan"), None
    med = float(np.median([ref[k] for k in ref]))
    worst, where = 0.0, None
    for k in names:
        p = prog.get(k, float("nan"))
        g = abs(p - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, where = g, k
    return worst, where


def compare(prog: dict, ref: dict, limits: dict) -> List[Check]:
    lp, lr = prog["losses"], ref["losses"]
    loss = max(abs(a - b) / abs(b) if math.isfinite(a) else float("inf")
               for a, b in zip(lp, lr))
    grad, _ = leaf_gap(prog["grad"], ref["grad"])
    meds = [float(np.median([v for v in g.values()])) for g in
            ref["step_grads"]]
    moved = {k for k in ref["change"]
             if any(g.get(k, 0.0) >= TINY_GRAD * m
                    for g, m in zip(ref["step_grads"], meds))}
    touched = set().union(*[set(g) for g in ref["step_grads"]])
    change, _ = leaf_gap(prog["change"], ref["change"], keep=moved)
    untouched = max([prog["change"].get(k, float("inf"))
                     for k in ref["change"] if k not in touched] or [0.0])
    return [Check("loss_rel_gap", loss, limits.get("loss_rel_gap", 0.0)),
            Check("first_grad_leaf_gap", grad,
                  limits.get("first_grad_leaf_gap", 0.0)),
            Check("change_leaf_gap", change,
                  limits.get("change_leaf_gap", 0.0)),
            Check("untouched_leaf_change", untouched, 0.0)]


def excluded(ref: dict) -> List[str]:
    meds = [float(np.median(list(g.values()))) for g in ref["step_grads"]]
    touched = set().union(*[set(g) for g in ref["step_grads"]])
    return sorted(k for k in touched
                  if all(g.get(k, 0.0) < TINY_GRAD * m
                         for g, m in zip(ref["step_grads"], meds)))
