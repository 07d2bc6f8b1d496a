"""Query kind `robust_sweep`: the paper's Fig. 5 study of one deployment.

Each query lowers every network of the configuration (`core.cnn_zoo`),
sweeps them all over the (h, w) grid in one fused kernel dispatch
(`core.dse.scenario_sweep(backend="pallas", fused=True)`) at one operand
precision `Precision(act_bits, weight_bits)`, and normalizes them under
one mix of network weights (`core.dse.robust_serving_config`): the three
public calls `robust_config` composes. A run walks a seeded order of all
precision pairs, again and again, and draws each query's weights from a
seeded Dirichlet distribution. The answer is every network's grids, the
weighted objectives F and the Pareto mask.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.compare import widest_gap
from bench.queries.grid_sweep import KEYS, grid_hw
from bench.reference import camuy
from bench.reference.robust import margins, robust


def prepare(cfg, mix, lower):
    axis, hw = grid_hw(mix["grid"])
    names = list(cfg["program"]["cnn_zoo"])
    rows = {n: lower(cfg, n) for n in names}
    return {"cfg": cfg, "mix": mix, "axis": axis, "hw": hw, "names": names,
            "bits": list(itertools.product(mix["bit_widths"], repeat=2)),
            "rows": rows, "n_rows": sum(len(r) for r in rows.values()),
            "refs": {}}


def variants(state):
    """Every precision, with uniform weights."""
    uniform = (1.0 / len(state["names"]),) * len(state["names"])
    return [(b, uniform) for b in state["bits"]]


def draw(state, rng, queue):
    """The next precision of a seeded order of all pairs, and the
    weights of one deployment's mix."""
    if not queue:
        queue[:] = [state["bits"][i]
                    for i in rng.permutation(len(state["bits"]))]
    alpha = state["mix"]["dirichlet_alpha"]
    weights = rng.dirichlet(np.full(len(state["names"]), alpha))
    return queue.pop(), tuple(float(w) for w in weights)


def run(state, params, span):
    from repro.core import cnn_zoo
    from repro.core.dse import robust_serving_config, scenario_sweep
    from repro.core.model_core import Precision
    (ab, wb), weights = params
    with span("lower"):
        named = {n: cnn_zoo.get_workloads(n) for n in state["names"]}
    with span("dse_call"):
        sweep = scenario_sweep(named, hs=state["axis"], ws=state["axis"],
                               backend="pallas", fused=True,
                               precision=Precision(ab, wb, max(ab, wb)))
    with span("normalize"):
        return sweep, robust_serving_config(
            sweep, dict(zip(state["names"], weights)))


def points(state, params):
    return len(state["hw"]) * len(state["names"])


def elements(state, params):
    return len(state["hw"]) * state["n_rows"]


def keep(state, params, answer):
    sweep, (_, F, mask) = answer
    S = len(sweep.names)
    return params, {"names": list(sweep.names), "F": np.asarray(F),
                    "mask": np.asarray(mask, bool),
                    **{k: np.asarray(getattr(sweep, k), np.float64)
                       .reshape(S, -1) for k in KEYS}}


def _grids(state, bits, dtype=np.float64):
    """The reference's grids of every network at `bits`, (S, C) each."""
    ab, wb = bits
    refs = [camuy.network(state["rows"][n], state["hw"],
                          bits=(ab, wb, max(ab, wb)), dtype=dtype)
            for n in state["names"]]
    return {k: np.stack([r[k] for r in refs]).astype(np.float64)
            for k in KEYS}


def _robust(state, grids, weights):
    return robust({n: {"energy": grids["energy"][i],
                       "cycles": grids["cycles"][i]}
                   for i, n in enumerate(state["names"])},
                  dict(zip(state["names"], weights)))


def control(state, params):
    """The reference's grids in bfloat16, normalized, in the program's
    place."""
    import ml_dtypes
    bits, weights = params
    grids = _grids(state, bits, ml_dtypes.bfloat16)
    F, mask = _robust(state, grids, weights)
    return params, {"names": list(state["names"]), "F": F, "mask": mask,
                    **grids}


def _widest_abs(program, reference):
    """Widest |program - reference|; a NaN or a missing value reads as
    infinitely far."""
    a, b = np.asarray(program, np.float64), np.asarray(reference)
    if a.shape != b.shape:
        return float("inf")
    d = np.abs(a - b)
    return float(np.max(np.where(np.isnan(d), np.inf, d)))


def check(state, kept):
    """Over every kept answer, against the reference on its own
    precision and weights:

    * `sweep_gap`: widest |program - reference| / (|reference| + 1) over
      every network's grid columns;
    * `robust_gap`: widest |F - F_ref| over both normalized objectives;
    * `pareto_miss`: the most design points of one answer whose Pareto
      membership differs from the reference's, counting only points the
      reference decides by a margin (`reference.robust.margins`) over
      twice `robust_gap`'s limit: within that limit F may move the
      difference of two points by twice as much.

    An answer that misses a network, or holds another, reads as
    infinitely far."""
    limit = state["mix"]["limits"]["robust_gap"]
    gaps = {"sweep_gap": 0.0, "robust_gap": 0.0, "pareto_miss": 0}
    for (bits, weights), ans in kept:
        if ans["names"] != state["names"]:
            return {k: float("inf") for k in gaps}
        if bits not in state["refs"]:
            state["refs"][bits] = _grids(state, bits)
        ref = state["refs"][bits]
        F, mask = _robust(state, ref, weights)
        decided = margins(F) > 2 * limit
        miss = (int(np.count_nonzero((ans["mask"] != mask) & decided))
                if ans["mask"].shape == mask.shape else float("inf"))
        gaps = {"sweep_gap": max([gaps["sweep_gap"]] + [
                    widest_gap(ans[k], ref[k]) for k in KEYS]),
                "robust_gap": max(gaps["robust_gap"],
                                  _widest_abs(ans["F"], F)),
                "pareto_miss": max(gaps["pareto_miss"], miss)}
    return gaps
