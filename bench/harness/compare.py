"""Numbers compared with the plain reference, and their limits.

Training compares norms leaf by leaf: the gap between the program's norm of
a leaf and the reference's, measured against the larger of the reference's
norm of that leaf and of the median leaf. Leaves whose reference gradient is
nought to rounding (under a thousandth of the median leaf's) are left out by
that rule, never by name.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Tuple

import numpy as np

NOUGHT = 1e-3


def flat(tree, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, path + "/"))
        else:
            out[path] = np.asarray(v, np.float64)
    return out


def norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in flat(tree).items()}


def counted_leaves(ref_grad) -> List[str]:
    n = norms(ref_grad)
    med = float(np.median(list(n.values())))
    return [k for k, v in n.items() if v >= NOUGHT * med]


def worst_norm_gap(prog, ref, leaves: Iterable[str]) -> Tuple[float, str]:
    """Largest |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖) over ``leaves``,
    and the leaf that gives it."""
    pn, rn = norms(prog), norms(ref)
    leaves = list(leaves)
    med = float(np.median([rn[k] for k in leaves]))
    worst, which = 0.0, ""
    for k in leaves:
        gap = abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap >= worst:
            worst, which = gap, k
    return worst, which


def tree_sub(a, b):
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


class Checks:
    """Numbers compared in a run, each beside its limit; the run is correct
    when every number is finite and within its limit."""

    def __init__(self):
        self.items: List[Tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.items)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def lines(self) -> List[str]:
        return [f"check {n} = {v!r} limit {lim!r} "
                f"{'ok' if math.isfinite(v) and v <= lim else 'FAILED'}"
                for n, v, lim in self.items]
