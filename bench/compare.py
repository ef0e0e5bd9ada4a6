"""The comparisons that decide ``correct``, on plain numbers."""
from __future__ import annotations

import math

import numpy as np


def loss_gap(program, reference) -> float:
    """Widest relative gap between the losses of the same steps."""
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r) / np.abs(r)))


def norm_gap(program: dict, reference: dict, keep=None) -> float:
    """Worst leaf's gap between two norms: |program - reference| over
    the larger of the reference's norm of that leaf and of the median
    leaf.  ``keep``: the leaves counted (all by default)."""
    names = sorted(reference if keep is None else keep)
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    med = float(np.median([float(reference[k]) for k in reference]))
    worst = 0.0
    for k in names:
        p, r = float(program[k]), float(reference[k])
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - r) / max(r, med))
    return worst


def moved_leaves(ref_grad: dict, share: float = 1e-3) -> list:
    """Leaves whose reference gradient is at least ``share`` of the
    median leaf's: the others move under Adam by round-off alone."""
    med = float(np.median([float(v) for v in ref_grad.values()]))
    return sorted(k for k, v in ref_grad.items() if float(v) >= share * med)
