"""Device-resident search tests (`core.search`): lockstep bisection is
probe-for-probe the scalar search, batched capacity tables are bit-identical
to sequential sweeps across every replay backend, the jnp NSGA-2 matches
the numpy oracle bitwise, warm-started frontiers dominate cold ones, and
the gradient refiner is never-worse than its seed under exact re-evaluation
(hypothesis property)."""
import functools

import numpy as np
import pytest

from repro.core import get_workloads
from repro.core.dse import (FleetSpec, PoolSpec, fleet_capacity_sweep,
                            pareto_nsga2, slo_capacity_sweep)
from repro.core.search import (batched_bisect, batched_max_sustainable_qps,
                               nsga2_device, refine_design_point)
from repro.traffic import (SLO, SimConfig, TrafficModel, build_cost_tables,
                           max_sustainable_qps)
from repro.traffic.slo import QPS_CAP, bisect_max_qps

from _hyp import given, settings, st

ARCHS = ("h2o-danube-3-4b", "xlstm-125m")
HW = ((64, 64), (128, 128))


@functools.lru_cache(maxsize=None)
def _tables():
    return build_cost_tables(archs=list(ARCHS), hw=HW,
                             slot_lattice=(1, 2, 4, 8),
                             kv_lattice=(64, 128, 256, 512),
                             prompt_lattice=(16, 64, 256, 1024),
                             backend="numpy", block_c=2)


# ---------------------------------------------------- lockstep bisection ---

def _threshold_probe(threshold, log):
    """Synthetic capacity probe: passes iff qps <= threshold."""
    def probe(qps):
        log.append(qps)
        return qps <= threshold, ("res", qps)
    return probe


def test_batched_bisect_matches_scalar_probe_sequence():
    """Every lane of the lockstep search must issue EXACTLY the probe
    sequence of the scalar `bisect_max_qps` and land on the same answer —
    including zero-capacity, grow-bracket and saturated-at-cap lanes."""
    cases = [(37.0, 50.0), (400.0, 50.0), (0.001, 50.0),
             (2e6, 50.0),                 # needs the one-extra doubling
             (np.inf, 50.0)]              # saturates at the cap
    scalar, scalar_logs = [], []
    for thresh, hi in cases:
        log = []
        q, res, sat = bisect_max_qps(_threshold_probe(thresh, log), hi)
        scalar.append((q, res, sat))
        scalar_logs.append(log)

    batch_logs = [[] for _ in cases]

    def probe_batch(reqs):
        outs = []
        for lane, qps in reqs:
            batch_logs[lane].append(qps)
            outs.append((qps <= cases[lane][0], ("res", qps)))
        return outs

    batched, rounds = batched_bisect(probe_batch, [hi for _, hi in cases])
    assert batched == scalar
    assert batch_logs == scalar_logs
    # lockstep: total rounds is the LONGEST lane, not the sum
    assert rounds == max(len(lg) for lg in scalar_logs)


def test_saturated_at_bracket_flag():
    always = lambda qps: (True, None)
    q, _, sat = bisect_max_qps(always, 100.0)
    assert sat and q == QPS_CAP
    q, _, sat = bisect_max_qps(_threshold_probe(37.0, []), 50.0)
    assert not sat and 0 < q < 50.0
    # surfaced by the capacity summary
    _, out = max_sustainable_qps(_tables().table(ARCHS[0], 64, 64),
                                 TrafficModel(), SLO(ttft_s=2.0, tpot_s=0.1),
                                 n_requests=120)
    assert out["saturated_at_bracket"] is False


# ------------------------------------------------- batched == sequential ---

def _summaries_equal(a, b):
    for k in a:
        va, vb = a[k], b.get(k)
        if isinstance(va, float) and np.isnan(va):
            assert np.isnan(vb), k
        else:
            assert va == vb, (k, va, vb)


@pytest.mark.parametrize("arrival", ["poisson", "mmpp"])
def test_batched_capacity_bit_identical(arrival):
    ts = _tables()
    tm = TrafficModel(arrival=arrival)
    slo = SLO(ttft_s=2.0, tpot_s=0.1)
    sim = SimConfig()
    tables = [ts.table(a, h, w) for a in ARCHS for h, w in HW]
    traffics = [tm] * len(tables)
    seq = [max_sustainable_qps(t, tr, slo, sim=sim, n_requests=200, seed=0)
           for t, tr in zip(tables, traffics)]
    for backend in ("xla", "scalar"):
        bat = batched_max_sustainable_qps(tables, traffics, slo, sim=sim,
                                          n_requests=200, seed=0,
                                          backend=backend)
        for (q0, s0), (q1, s1) in zip(seq, bat):
            assert q0 == q1
            _summaries_equal(s0, s1)


@pytest.mark.parametrize("backend", ["auto", "xla"])
def test_tpu_backend_never_picks_lockstep(monkeypatch, backend):
    """On a TPU backend the x64 lockstep engine is not bit-identical to
    the scalar reference (emulated float64): `auto` without the native
    executor takes the scalar path, and an explicit "xla" raises."""
    import jax

    from repro.traffic import native
    ts = _tables()
    tables = [ts.table(a, h, w) for a in ARCHS for h, w in HW]
    tm = TrafficModel()
    slo = SLO(ttft_s=2.0, tpot_s=0.1)
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if backend == "xla":
        with pytest.raises(RuntimeError, match="not bit-identical"):
            batched_max_sustainable_qps(tables, [tm] * len(tables), slo,
                                        n_requests=200, backend=backend)
        return
    stats = {}
    bat = batched_max_sustainable_qps(tables, [tm] * len(tables), slo,
                                      n_requests=200, backend=backend,
                                      stats=stats)
    assert stats["backend"] == "scalar"
    seq = [max_sustainable_qps(t, tm, slo, n_requests=200, seed=0)
           for t in tables]
    assert [q for q, _ in bat] == [q for q, _ in seq]


def test_slo_sweep_batched_equals_sequential():
    tm = TrafficModel()
    slo = SLO(ttft_s=2.0, tpot_s=0.1)
    kw = dict(archs=list(ARCHS), hw=HW, n_requests=200, seed=0,
              tables=_tables())
    seq = slo_capacity_sweep(tm, slo, search="sequential", **kw)
    bat = slo_capacity_sweep(tm, slo, search="batched", **kw)
    assert np.array_equal(seq.max_qps, bat.max_qps)
    assert np.array_equal(seq.goodput_qps, bat.goodput_qps)
    assert np.array_equal(seq.energy_per_token, bat.energy_per_token)


def test_fleet_sweep_batched_equals_sequential():
    fleets = [
        FleetSpec("4x[64x64]", (PoolSpec(64, 64, 4),)),
        FleetSpec("2x[128x128] jsq", (PoolSpec(128, 128, 2),),
                  routing="jsq"),
        FleetSpec("disagg", (PoolSpec(128, 128, 1, role="prefill"),
                             PoolSpec(128, 128, 1, role="decode"))),
    ]
    tm = TrafficModel()
    slo = SLO(ttft_s=2.5, tpot_s=0.12)
    kw = dict(archs=[ARCHS[1]], n_requests=200, seed=0, backend="numpy")
    seq = fleet_capacity_sweep(tm, slo, fleets, search="sequential", **kw)
    bat = fleet_capacity_sweep(tm, slo, fleets, search="batched", **kw)
    assert np.array_equal(seq.max_qps, bat.max_qps)
    assert np.array_equal(seq.energy_per_token, bat.energy_per_token)
    for rs, rb in zip(seq.summaries, bat.summaries):
        for ss, sb in zip(rs, rb):
            _summaries_equal(ss, sb)


# ------------------------------------------------------- on-device NSGA-2 --

def _toy_eval(pop):
    h = pop[:, 0].astype(np.float64)
    w = pop[:, 1].astype(np.float64)
    return np.stack([(h - 120.0) ** 2 + w, (w - 200.0) ** 2 + h], axis=1)


@pytest.mark.parametrize("seed", [0, 3])
def test_nsga2_device_matches_numpy_oracle(seed):
    bounds = ((16, 256), (16, 256))
    Pj, Fj = nsga2_device(_toy_eval, bounds, pop=32, gens=12, seed=seed)
    Pn, Fn = nsga2_device(_toy_eval, bounds, pop=32, gens=12, seed=seed,
                          backend="numpy")
    assert np.array_equal(Pj, Pn)
    assert np.array_equal(Fj, Fn)


def test_warm_start_dominates_cold():
    # pop must hold the whole grid frontier: crowding truncation may
    # otherwise evict warm rank-0 points and break the guarantee
    wls = get_workloads("alexnet")
    Pc, Fc = pareto_nsga2(wls, pop=32, gens=12, seed=3)
    Pw, Fw = pareto_nsga2(wls, pop=32, gens=12, seed=3, warm_start="grid")
    # every cold frontier point is matched-or-dominated by a warm one
    assert all(((Fw <= f).all(axis=1)).any() for f in Fc)
    # warm_start=None leaves the rng stream — and the result — unchanged
    Pc2, Fc2 = pareto_nsga2(wls, pop=32, gens=12, seed=3)
    assert np.array_equal(Pc, Pc2) and np.array_equal(Fc, Fc2)


# -------------------------------------------------------- gradient refiner --

_REFINE_WL = ((64, 128, 256, 1, 1), (32, 64, 64, 1, 2))


@settings(max_examples=10, deadline=None)
@given(hi=st.integers(2, 32), wi=st.integers(2, 32))
def test_refiner_never_worse_than_seed(hi, wi):
    """Exact re-evaluation + seed-in-candidate-set makes the refiner
    never-worse by construction; this property pins that contract."""
    r = refine_design_point(list(_REFINE_WL), (8 * hi, 8 * wi), steps=6)
    assert r["objective"] <= r["seed_objective"] + 1e-12
    assert r["device_dispatches"] == 1
    assert r["candidates_evaluated"] >= 1


def test_refiner_improves_bad_seed():
    wls = list(get_workloads("alexnet"))
    r = refine_design_point(wls, (128, 128), steps=32)
    assert r["improved"] and r["objective"] < r["seed_objective"]
    assert (r["h"], r["w"]) != (128, 128)
    # multi-model dict loss: per-model exact objectives are reported
    d = {"alexnet": wls, "vgg16": list(get_workloads("vgg16"))}
    r2 = refine_design_point(d, (128, 128), steps=16)
    assert set(r2["objectives"]) == {"alexnet", "vgg16"}
    assert r2["objective"] <= r2["seed_objective"] + 1e-12
