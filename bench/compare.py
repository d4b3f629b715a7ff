"""The comparison that decides ``correct``.

The program's first steps and the reference's, from the same weights on
the same batches, give three numbers, each held to a limit of the cell
(``workloads/<cell>.json``):

  loss_gap         worst relative gap of a step's loss
  grad_norm_gap    worst leaf: |norm of the program's first gradient -
                   the reference's|, over the larger of the reference's
                   norm of that leaf and of the median leaf
  change_norm_gap  the same for each leaf's change after the steps

A leaf is one layer of one parameter.  Leaves whose reference gradient
is under a thousandth of the median leaf's move by round-off alone, so
they are left out of the change (none has been so far).
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_norm_gap", "change_norm_gap")
NEGLIGIBLE_GRAD = 1e-3


def _gap(p, r, scale):
    g = abs(p - r) / scale
    return g if math.isfinite(g) else math.inf


def _worst_leaf(prog, ref, keep):
    med = statistics.median(r for r, k in zip(ref, keep) if k)
    return max(_gap(p, r, max(r, med))
               for p, r, k in zip(prog, ref, keep) if k)


def numbers(prog: dict, ref: dict) -> dict:
    """Each number for a run; a non-finite reading counts as inf."""
    loss = max(_gap(p, r, abs(r))
               for p, r in zip(prog["losses"], ref["losses"]))
    everyone = [True] * len(ref["grad_norms"])
    floor = NEGLIGIBLE_GRAD * statistics.median(ref["grad_norms"])
    moved = [g >= floor for g in ref["grad_norms"]]
    return {"loss_gap": loss,
            "grad_norm_gap": _worst_leaf(prog["grad_norms"],
                                         ref["grad_norms"], everyone),
            "change_norm_gap": _worst_leaf(prog["change_norms"],
                                           ref["change_norms"], moved)}


def judge(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= limits[k] for k in NUMBERS)
