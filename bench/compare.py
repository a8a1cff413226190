"""The comparison that decides ``correct`` for a training cell.

Each number against the plain reference:

- ``loss_gap``: the largest relative gap between the program's loss and the
  reference's over the checked steps;
- ``first_grad_gap``: by the worst leaf, the gap between the norms of the
  first gradient as the optimizer received it, relative to the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- ``change_gap``: the same for the norm of each leaf's change after the
  checked steps;
- ``first_grad_diff``: the first gradient as received, value by value, on
  a sample of round 1's kept blocks drawn from the seed: the l2 norm of
  the difference over the reference's. A norm sees the rounding of a lower
  precision only at second order, and a mean loss averages it over every
  token; this number sees it at first order.

A cell compares the numbers that its traffic file gives a limit; the
others are read for the record.

Leaves that the reference does not move (their received gradient is zero,
or under a thousandth of the median moving leaf's: Block-RandK may select
none of a small leaf's blocks) are left out: they move, if at all, by
round-off alone.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np

#: A leaf counts where its reference gradient is at least this share of the
#: median leaf's.
KEEP_SHARE = 1e-3


def kept(ref_grad: Sequence[float]) -> np.ndarray:
    g = np.asarray(ref_grad, np.float64)
    moving = g > 0
    if not moving.any():
        return moving
    return moving & (g >= KEEP_SHARE * np.median(g[moving]))


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float],
                   keep: np.ndarray) -> float:
    p = np.asarray(prog, np.float64)[keep]
    r = np.asarray(ref, np.float64)[keep]
    if not np.all(np.isfinite(p)) or not keep.any():
        return math.inf
    scale = np.maximum(np.abs(r), np.median(np.abs(r)))
    return float(np.max(np.abs(p - r) / scale))


def relative_difference(prog: np.ndarray, ref: np.ndarray) -> float:
    """l2 norm of the difference over the reference's."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return math.inf
    return float(np.linalg.norm(p - r) / np.linalg.norm(r))


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return math.inf
    return float(np.max(np.abs(p - r) / np.abs(r)))


def training_gaps(prog: Dict[str, Sequence[float]],
                  ref: Dict[str, Sequence[float]]) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``loss`` (per step), ``first_grad`` and
    ``change`` (per leaf); ``ref`` also ``received`` (per leaf)."""
    return {
        "loss_gap": loss_gap(prog["loss"], ref["loss"]),
        "first_grad_gap": worst_leaf_gap(prog["first_grad"],
                                         ref["first_grad"],
                                         kept(ref["first_grad"])),
        "first_grad_diff": relative_difference(prog["first_rows"],
                                               ref["first_rows"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"],
                                     kept(ref["received"])),
    }


def checks(gaps: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    return {k: {"value": gaps[k], "limit": limits[k]} for k in limits}
