"""Query kind `grid_sweep`: one architect's precision study of one network.

Each query lowers the network (`core.cnn_zoo`) and sweeps it over the
whole (h, w) grid on the Pallas sweep kernel
(`core.dse.grid_sweep(backend="pallas")`) at one operand precision
`Precision(act_bits, weight_bits)`. A run walks a seeded order of all
precision pairs, again and again, so every seed does the same work.
The answer is the seven grids `SweepResult` carries (every kernel column
but `macs`, which the sweep does not return).
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.compare import widest_gap
from bench.reference import camuy

KEYS = ("cycles", "energy", "utilization", "m_ub", "m_inter_pe", "m_aa",
        "ub_bw_bits")


def grid_hw(grid):
    axis = np.arange(grid["lo"], grid["hi"] + 1, grid["step"])
    H, W = np.meshgrid(axis, axis, indexing="ij")
    return axis, np.stack([H.reshape(-1), W.reshape(-1)], 1)


def prepare(cfg, mix, lower):
    from repro.core import get_workloads
    axis, hw = grid_hw(mix["grid"])
    bits = list(itertools.product(mix["bit_widths"], repeat=2))
    rows = lower(cfg)
    name = cfg["program"]["cnn_zoo"]
    return {"cfg": cfg, "mix": mix, "lower": lower, "axis": axis, "hw": hw,
            "bits": bits, "rows": rows, "name": name,
            "get_workloads": get_workloads, "refs": {}}


def variants(state):
    """Parameters of every static variant the window can meet."""
    return list(state["bits"])


def draw(state, rng, queue):
    """The next precision of a seeded order of all pairs."""
    if not queue:
        queue[:] = [state["bits"][i]
                    for i in rng.permutation(len(state["bits"]))]
    return queue.pop()


def run(state, params, span):
    from repro.core.dse import grid_sweep
    from repro.core.model_core import Precision
    ab, wb = params
    with span("lower"):
        wl = state["get_workloads"](state["name"])
    with span("dse_call"):
        return grid_sweep(wl, hs=state["axis"], ws=state["axis"],
                          backend="pallas",
                          precision=Precision(ab, wb, max(ab, wb)))


def points(state, params):
    return len(state["hw"])


def elements(state, params):
    return len(state["hw"]) * len(state["rows"])


def keep(state, params, answer):
    return params, {k: np.asarray(getattr(answer, k), np.float64).reshape(-1)
                    for k in KEYS}


def _reference(state, params, dtype=np.float64):
    ab, wb = params
    return camuy.network(state["rows"], state["hw"],
                         bits=(ab, wb, max(ab, wb)), dtype=dtype)


def control(state, params):
    """The reference in bfloat16, in the program's place."""
    import ml_dtypes
    ref = _reference(state, params, ml_dtypes.bfloat16)
    return params, {k: ref[k].astype(np.float64) for k in KEYS}


def check(state, kept):
    """Widest normalized gap |program - reference| / (|reference| + 1)
    over every kept answer and grid column."""
    gap = 0.0
    for params, ans in kept:
        if params not in state["refs"]:
            state["refs"][params] = _reference(state, params)
        ref = state["refs"][params]
        gap = max([gap] + [widest_gap(ans[k], ref[k]) for k in KEYS])
    return {"sweep_gap": gap}
