"""The comparisons that decide ``correct``, and their limits.

Each number compared is a gap between what the timed path produced and
the plain reference; each has a limit of its own in
``bench/limits/<cell>.json``, set from the readings of sound runs and of
the control (see PERF.md).
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

# leaves whose reference gradient is under this share of the median
# leaf's move by round-off alone under Adam; their change is not compared
STILL_LEAF = 1e-3


def _leaf_gaps(mine: List[float], ref: List[float], keep=None) -> List[float]:
    """Each leaf's |norm - reference norm| over the larger of its
    reference norm and the median leaf's."""
    idx = [i for i in range(len(ref)) if keep is None or keep[i]]
    if not idx:
        return [float("nan")]
    floor = float(np.median([ref[i] for i in idx]))
    return [abs(mine[i] - ref[i]) / max(ref[i], floor, 1e-30) for i in idx]


TRAIN_NUMBERS = ("loss_gap", "norm_gap", "grad_gap", "change_gap",
                 "grad_gap_median", "change_gap_median")


def train_gaps(mine: Dict, ref: Dict) -> Dict[str, float]:
    """Training: the worst relative loss gap over the compared steps; the
    relative gap of the first gradient's global norm before clipping; the
    worst leaf's gap of first-gradient norms (as clipped); the worst
    moving leaf's gap of the master weights' change over the compared
    steps; and the median leaf's gaps of the last two."""
    if (len(mine["losses"]) != len(ref["losses"])
            or len(mine["grad_norms"]) != len(ref["grad_norms"])
            or len(mine["change_norms"]) != len(ref["change_norms"])):
        return {k: float("inf") for k in TRAIN_NUMBERS}
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(mine["losses"], ref["losses"]))
    norm_gap = abs(mine["global_norm"] - ref["global_norm"]) / ref["global_norm"]
    g_ref = ref["grad_norms"]
    med = float(np.median(g_ref))
    moving = [g >= STILL_LEAF * med for g in g_ref]
    grad = _leaf_gaps(mine["grad_norms"], g_ref)
    change = _leaf_gaps(mine["change_norms"], ref["change_norms"], moving)
    return {
        "loss_gap": float(loss_gap),
        "norm_gap": float(norm_gap),
        "grad_gap": max(grad),
        "change_gap": max(change),
        "grad_gap_median": float(np.median(grad)),
        "change_gap_median": float(np.median(change)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit, and
    every limit has its number."""
    for name, limit in limits.items():
        v = numbers.get(name)
        if v is None or not math.isfinite(v) or v > limit:
            return False
    return True
