"""Query kind `scenario_sweep`: one serving-scenario study of one LM.

Each query draws `draw_batches` batch sizes and `draw_seq_lens` sequence
lengths from the mix, lowers the model at every (phase, batch, seq)
scenario (`scenarios.matrix`), and sweeps all of them over the (h, w)
grid in one fused kernel dispatch (`core.dse.scenario_sweep(backend=
"pallas", fused=True)`). The kernel's shape is the same in every query.
The answer is the seven (scenario, h, w) grids of `ScenarioSweepResult`.
"""
from __future__ import annotations

import numpy as np

from bench.compare import widest_gap
from bench.queries.grid_sweep import KEYS, grid_hw
from bench.reference import camuy


def prepare(cfg, mix, lower):
    from repro.scenarios.matrix import named_workloads, serving_matrix
    axis, hw = grid_hw(mix["grid"])
    return {"cfg": cfg, "mix": mix, "lower": lower, "axis": axis, "hw": hw,
            "arch": cfg["program"]["arch"], "matrix": serving_matrix,
            "named": named_workloads, "rows": {}}


def variants(state):
    mix = state["mix"]
    return [(tuple(mix["batches"][:mix["draw_batches"]]),
             tuple(mix["seq_lens"][:mix["draw_seq_lens"]]))]


def draw(state, rng, queue):
    mix = state["mix"]
    b = rng.choice(mix["batches"], mix["draw_batches"], replace=False)
    s = rng.choice(mix["seq_lens"], mix["draw_seq_lens"], replace=False)
    return tuple(sorted(int(x) for x in b)), tuple(sorted(int(x) for x in s))


def _shapes(state, params):
    batches, seqs = params
    return [(ph, b, s) for ph in state["mix"]["phases"]
            for b in batches for s in seqs]


def run(state, params, span):
    from repro.core.dse import scenario_sweep
    batches, seqs = params
    with span("lower"):
        named = state["named"](state["matrix"](
            [state["arch"]], phases=state["mix"]["phases"], batches=batches,
            seq_lens=seqs))
    with span("dse_call"):
        return scenario_sweep(named, hs=state["axis"], ws=state["axis"],
                              backend="pallas", fused=True)


def points(state, params):
    return len(state["hw"]) * len(_shapes(state, params))


def elements(state, params):
    rows = state["rows"]
    if params not in rows:
        rows[params] = sum(len(state["lower"](state["cfg"], s))
                           for s in _shapes(state, params))
    return len(state["hw"]) * rows[params]


def _name_shape(name):
    """(phase, batch, seq) of a scenario named arch/phase/b<B>/s<S>."""
    _, phase, b, s = name.split("/")
    return phase, int(b[1:]), int(s[1:])


def keep(state, params, answer):
    S = len(answer.names)
    return params, {"shapes": [_name_shape(n) for n in answer.names],
                    **{k: np.asarray(getattr(answer, k), np.float64)
                       .reshape(S, -1) for k in KEYS}}


def control(state, params):
    """The reference in bfloat16, in the program's place."""
    import ml_dtypes
    shapes = _shapes(state, params)
    refs = [camuy.network(state["lower"](state["cfg"], s), state["hw"],
                          dtype=ml_dtypes.bfloat16) for s in shapes]
    return params, {"shapes": shapes,
                    **{k: np.stack([r[k] for r in refs]).astype(np.float64)
                       for k in KEYS}}


def check(state, kept):
    """Widest normalized gap over every kept answer, scenario and grid
    column; a scenario missing from an answer reads as infinitely far."""
    gap = 0.0
    for params, ans in kept:
        want = _shapes(state, params)
        if sorted(ans["shapes"]) != sorted(want):
            return {"sweep_gap": float("inf")}
        for i, shape in enumerate(ans["shapes"]):
            ref = camuy.network(state["lower"](state["cfg"], shape),
                                state["hw"])
            gap = max([gap] + [widest_gap(ans[k][i], ref[k]) for k in KEYS])
    return {"sweep_gap": gap}
