"""The Fig. 5 cell `cnn_zoo.robust`: its plain references agree with the
program where they must, and the comparison that decides `correct`
fails with each fault below planted in the program (small CPU
rehearsals). Each fault takes a `setattr(owner, name, value)` that it
patches the program with, as `bench/calibrate.py`'s do."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from bench.reference import lower_cnn_zoo, robust
from bench.tests.conftest import ROOT

CELL = "cnn_zoo.robust"


def drop_network(setattr):
    """The fused sweep leaves the last network out of the batch, and the
    normalization passes over its weight in silence."""
    from repro.core import dse
    sweep, normalize = dse.scenario_sweep, dse.robust_serving_config

    def dropped(named, *a, **kw):
        return sweep(dict(list(named.items())[:-1]), *a, **kw)

    def silent(res, weights=None):
        return normalize(res, {n: weights[n] for n in res.names})
    setattr(dse, "scenario_sweep", dropped)
    setattr(dse, "robust_serving_config", silent)


def swap_energy(setattr):
    """The first two networks' energy grids come back swapped."""
    from repro.core import dse
    real = dse.scenario_sweep

    def sweep(*a, **kw):
        res = real(*a, **kw)
        res.energy[[0, 1]] = res.energy[[1, 0]]
        return res
    setattr(dse, "scenario_sweep", sweep)


def ignore_weights(setattr):
    """The normalization weighs every network alike, whatever the
    query's weights."""
    from repro.core import dse
    real = dse.robust_serving_config
    setattr(dse, "robust_serving_config",
            lambda sweep, weights=None: real(sweep))


def flip_pareto_point(setattr):
    """The most dominated design point is reported on the Pareto set."""
    from repro.core import dse
    real = dse.robust_serving_config

    def normalize(*a, **kw):
        configs, F, mask = real(*a, **kw)
        mask = mask.copy()
        mask[np.argmax(F.sum(1))] = True
        return configs, F, mask
    setattr(dse, "robust_serving_config", normalize)


def no_se_rows(setattr):
    """MobileNetV3 and EfficientNet lower without their squeeze-and-
    excitation FC pairs."""
    from repro.core import cnn_zoo
    setattr(cnn_zoo, "_squeeze_excite", lambda c, se: [])


FAULTS = {f.__name__: f for f in (drop_network, swap_energy, ignore_weights,
                                  flip_pareto_point, no_se_rows)}


def _cfg():
    with open(os.path.join(ROOT, "bench", "configs", "cnn_zoo.json")) as f:
        return json.load(f)


def test_reference_lowering_gives_the_programs_rows():
    from repro.core import get_workloads
    cfg = _cfg()
    total = 0
    for name in cfg["program"]["cnn_zoo"]:
        rows = lower_cnn_zoo.lower(cfg, name)
        assert Counter(rows) == Counter(map(tuple, get_workloads(name)))
        assert len(rows) == cfg["networks"][name]["gemm_rows"]
        total += len(rows)
    assert total == cfg["gemm_rows"] == 807


def test_reference_robust_is_the_programs_normalization():
    from repro.core.dse import robust_serving_config, scenario_sweep
    rng = np.random.default_rng(5)
    cfg = _cfg()
    names = cfg["program"]["cnn_zoo"]
    axis = np.array([16, 40, 96, 160, 256])
    sweep = scenario_sweep({n: lower_cnn_zoo.lower(cfg, n) for n in names},
                           hs=axis, ws=axis, backend="numpy")
    weights = dict(zip(names, rng.dirichlet(np.ones(len(names)))))
    _, F, mask = robust_serving_config(sweep, weights)
    F_ref, mask_ref = robust.robust(
        {n: {"energy": sweep.energy[i].reshape(-1),
             "cycles": sweep.cycles[i].reshape(-1)}
         for i, n in enumerate(names)}, weights)
    np.testing.assert_allclose(F, F_ref, rtol=0, atol=1e-15)
    assert (mask == mask_ref).all()


def test_margins_say_how_far_membership_is_from_changing():
    F = np.array([[0.0, 1.0], [0.5, 0.5], [1.0, 0.0], [0.6, 0.7],
                  [0.5, 0.5]])
    assert robust.pareto(F).tolist() == [True, True, True, False, True]
    # (0, 1) is missed by (0.5, 0.5) by 0.5; the two (0.5, 0.5) tie;
    # (0.6, 0.7) is dominated by (0.5, 0.5) by 0.1 in both objectives
    np.testing.assert_allclose(robust.margins(F), [0.5, 0, 0.5, 0.1, 0])


def _failing(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch.setattr)
    w = cell.window(2 ** 31 + 3, 0.2)
    assert w["failed"] == 0 and w["kept"]
    checks = cell.check(w["kept"])
    return [k for k, v in checks.items() if not v["value"] <= v["limit"]]


@pytest.mark.parametrize("fault,caught_by", [
    ("drop_network", "sweep_gap"), ("swap_energy", "sweep_gap"),
    ("ignore_weights", "robust_gap"), ("flip_pareto_point", "pareto_miss"),
    ("no_se_rows", "sweep_gap")])
def test_planted_fault_fails(small_cell, monkeypatch, fault, caught_by):
    assert caught_by in _failing(small_cell(CELL), fault, monkeypatch)
