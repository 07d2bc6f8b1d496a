"""Multi-objective optimization: exact Pareto sets + NSGA-II (paper Fig. 3
uses NSGA-II [Deb et al. 2002]; the grid is small enough that the exact
frontier is also computable, which doubles as the NSGA-II test oracle)."""
from __future__ import annotations

import numpy as np

from repro.obs.metrics import metrics as _obs_metrics


def pareto_mask(objectives: np.ndarray) -> np.ndarray:
    """objectives: (n, k), all MINIMIZED. Returns bool mask of the frontier:
    the points that no point dominates (no worse in every objective and
    better in one).

    Two objectives (k = 2): one lexicographic sort and one sweep,
    O(n log n) (`_pareto_mask_2d`), counted as `pareto.masks_sorted`. Any
    other k: all n x n comparisons at once, O(n^2 k) time and two n x n
    boolean temporaries (`_pareto_mask_all_pairs`), counted as
    `pareto.masks_all_pairs`. Both return the same mask: a row with a NaN
    compares false against every row, so it stays on the frontier and
    dominates nothing; equal rows do not dominate each other and both
    stay; -0.0 equals 0.0 and +-inf compare as numbers."""
    F = np.asarray(objectives)
    if F.shape[1] == 2:
        _obs_metrics().inc("pareto.masks_sorted")
        return _pareto_mask_2d(F)
    _obs_metrics().inc("pareto.masks_all_pairs")
    return _pareto_mask_all_pairs(F)


def _pareto_mask_all_pairs(F: np.ndarray) -> np.ndarray:
    n = F.shape[0]
    no_worse = np.ones((n, n), bool)       # [i, j]: point j <= point i
    better = np.zeros((n, n), bool)
    for f in F.T:
        no_worse &= f[None, :] <= f[:, None]
        better |= f[None, :] < f[:, None]
    return ~(no_worse & better).any(1)


def _pareto_mask_2d(F: np.ndarray) -> np.ndarray:
    """Sort the rows without a NaN by (f0, f1) and group equal f0: a row is
    dominated when a group of smaller f0 reaches its f1 or below, or its
    own group holds a smaller f1."""
    keep = np.ones(F.shape[0], bool)
    rows = np.flatnonzero(~np.isnan(F).any(1))
    if rows.size < 2:
        return keep
    rows = rows[np.lexsort((F[rows, 1], F[rows, 0]))]
    f0, f1 = F[rows, 0], F[rows, 1]
    first = np.r_[True, f0[1:] != f0[:-1]]     # -0.0 == 0.0: one group
    group = np.cumsum(first) - 1
    group_min = f1[first]                      # f1 ascends in a group
    # least f1 over the groups before each one (no NaN is left to poison it)
    below = np.minimum.accumulate(group_min)
    dominated = (group_min[group] < f1) | (
        (group > 0) & (below[group - 1] <= f1))
    keep[rows[dominated]] = False
    return keep


def fast_non_dominated_sort(F: np.ndarray) -> np.ndarray:
    """NSGA-II front ranks (0 = best). F: (n, k) minimized."""
    n = F.shape[0]
    dom_less = ((F[:, None, :] <= F[None, :, :]).all(-1)
                & (F[:, None, :] < F[None, :, :]).any(-1))   # i dominates j
    n_dom = dom_less.sum(axis=0)                             # dominated-by count
    ranks = np.full(n, -1)
    front = np.where(n_dom == 0)[0]
    r = 0
    while front.size:
        ranks[front] = r
        n_dom = n_dom - dom_less[front].sum(axis=0)
        n_dom[ranks >= 0] = np.iinfo(np.int32).max
        front = np.where(n_dom == 0)[0]
        r += 1
    return ranks


def crowding_distance(F: np.ndarray) -> np.ndarray:
    n, k = F.shape
    d = np.zeros(n)
    for j in range(k):
        order = np.argsort(F[:, j])
        fmin, fmax = F[order[0], j], F[order[-1], j]
        d[order[0]] = d[order[-1]] = np.inf
        if fmax > fmin and n > 2:
            d[order[1:-1]] += (F[order[2:], j] - F[order[:-2], j]) / (fmax - fmin)
    return d


def nsga2(eval_fn, bounds, *, pop: int = 64, gens: int = 40, seed: int = 0,
          quantum: int = 8, warm_start=None):
    """NSGA-II over integer (h, w) genomes.

    eval_fn: (pop, 2) int array -> (pop, k) objective array (minimized).
    bounds: ((h_lo, h_hi), (w_lo, w_hi)); genes snap to `quantum` steps
    (the paper sweeps 16..256 in steps of 8).

    `warm_start`, when given, is an (m, 2) array of genomes injected into
    the initial population (overwriting its first min(m, pop) rows AFTER
    the random draw, so the rng stream — and therefore every later
    generation's randomness — is unchanged vs a cold start). Seeding with
    exact grid-Pareto points keeps them in rank 0 under the elitist
    selection for the whole run: the warm frontier can only match or
    dominate the cold one — provided `pop` can hold the whole seed
    frontier (crowding truncation may evict rank-0 points otherwise)."""
    rng = np.random.default_rng(seed)
    (hl, hh), (wl, wh) = bounds

    def snap(x):
        x = np.round(x / quantum) * quantum
        return np.clip(x, [hl, wl], [hh, wh]).astype(int)

    P = snap(rng.uniform([hl, wl], [hh, wh], size=(pop, 2)))
    if warm_start is not None:
        ws = snap(np.asarray(warm_start, np.float64))[:pop]
        P[:len(ws)] = ws
    FP = eval_fn(P)
    for _ in range(gens):
        ranks = fast_non_dominated_sort(FP)
        crowd = crowding_distance(FP)
        # binary tournament
        idx = rng.integers(0, pop, size=(pop, 2))
        better = np.where(
            (ranks[idx[:, 0]] < ranks[idx[:, 1]])
            | ((ranks[idx[:, 0]] == ranks[idx[:, 1]])
               & (crowd[idx[:, 0]] > crowd[idx[:, 1]])),
            idx[:, 0], idx[:, 1])
        parents = P[better]
        # SBX-lite crossover + mutation
        partners = parents[rng.permutation(pop)]
        alpha = rng.uniform(size=(pop, 1))
        children = alpha * parents + (1 - alpha) * partners
        mut = rng.normal(0, quantum * 2, size=children.shape)
        do_mut = rng.uniform(size=children.shape) < 0.2
        children = snap(children + do_mut * mut)
        FC = eval_fn(children)
        # elitist environmental selection
        allP = np.concatenate([P, children])
        allF = np.concatenate([FP, FC])
        _, uniq = np.unique(allP, axis=0, return_index=True)
        allP, allF = allP[uniq], allF[uniq]
        ranks = fast_non_dominated_sort(allF)
        crowd = crowding_distance(allF)
        order = np.lexsort((-crowd, ranks))[:pop]
        P, FP = allP[order], allF[order]
        if P.shape[0] < pop:   # refill after dedup
            extra = snap(rng.uniform([hl, wl], [hh, wh],
                                     size=(pop - P.shape[0], 2)))
            P = np.concatenate([P, extra])
            FP = np.concatenate([FP, eval_fn(extra)])
    final = pareto_mask(FP)
    return P[final], FP[final]
