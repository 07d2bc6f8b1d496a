"""Plain reference of the paper's Fig. 5 robustness study.

Given each network's energy and cycle grids over the same design points,
min-max normalize both per network, take their mean weighted by the
networks' shares, and keep the design points no other point dominates
(no worse in both objectives, better in one), by comparing every pair.
Float64 numpy throughout; nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def _normalize(x):
    x = np.asarray(x, np.float64)
    lo, hi = x.min(), x.max()
    return (x - lo) / (hi - lo) if hi > lo else np.zeros_like(x)


def robust(grids, weights):
    """(F, mask) of `grids` ({name: {"energy": (C,), "cycles": (C,)}})
    under `weights` ({name: share}, the same names): F is (C, 2), the
    weighted mean of the normalized (energy, cycles); mask marks the
    Pareto set of F."""
    total = sum(float(weights[n]) for n in grids)
    F = sum(float(weights[n]) * np.stack(
        [_normalize(g["energy"]), _normalize(g["cycles"])], 1)
        for n, g in grids.items()) / total
    return F, pareto(F)


def pareto(F):
    """Points of F ((C, k), all minimized) that no other point dominates."""
    no_worse = (F[None, :, :] <= F[:, None, :]).all(-1)   # [i, j]: j <= i
    better = (F[None, :, :] < F[:, None, :]).any(-1)
    return ~(no_worse & better).any(1)


def margins(F):
    """How far each point's membership of the Pareto set is from
    changing: for a point of the set, the least amount by which any
    other point misses dominating it; for a dominated point, the most
    by which a point dominates it. Both are |min_j max_k (F[j,k] -
    F[i,k])| over j != i; moving every F by at most d moves each
    difference by at most 2d, so a point whose margin exceeds 2d keeps
    its membership."""
    d = (F[None, :, :] - F[:, None, :]).max(-1)
    np.fill_diagonal(d, np.inf)
    return np.abs(d.min(1))
