"""DSE layer: paper-claim regressions + Pareto/NSGA-II correctness."""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import (ZOO, equal_pe_sweep, get_workloads, grid_sweep,
                        pareto_grid, robust_config)
from repro.core.pareto import (_pareto_mask_all_pairs, crowding_distance,
                               fast_non_dominated_sort, nsga2, pareto_mask)
from repro.core.workloads import total_macs


def test_zoo_macs_match_literature():
    ref = {"alexnet": 0.71, "vgg16": 15.5, "googlenet": 1.5,
           "resnet152": 11.3, "densenet201": 4.3, "mobilenetv3_large": 0.22,
           "efficientnet_b0": 0.39}
    for name, lit in ref.items():
        g = total_macs(get_workloads(name)) / 1e9
        assert abs(g - lit) / lit < 0.2, f"{name}: {g:.2f} vs lit {lit}"


@pytest.mark.parametrize("name,published", [("mobilenetv3_large", 219e6),
                                             ("efficientnet_b0", 390e6)])
def test_mobile_macs_match_their_papers(name, published):
    """With their squeeze-and-excitation FC pairs, the mobile networks
    come within 3% of the multiply-adds their papers report (Howard et
    al. Table 3, Tan & Le Table 2)."""
    assert abs(total_macs(get_workloads(name)) / published - 1) < 0.03


def _se_pairs(rows):
    """(exp, se) of every SE pair: a depthwise row (groups = exp, one
    output channel per group) followed by FC(exp, se), FC(se, exp) and
    the 1x1 projection from exp channels."""
    pairs = []
    for i, (m, k, n, g, _) in enumerate(rows[:-3]):
        fc1, fc2, proj = rows[i + 1:i + 4]
        if g > 1 and n == 1 and fc1[:2] == (1, g) and fc2 == (
                1, fc1[2], g, 1, 1) and proj[1] == g and proj[3] == 1:
            pairs.append((g, fc1[2]))
    return pairs


@pytest.mark.parametrize("name,exps,squeezes", [
    ("mobilenetv3_large", [72, 120, 120, 480, 672, 672, 960, 960],
     [24, 32, 32, 120, 168, 168, 240, 240]),
    ("efficientnet_b0",
     [32, 96, 144, 144, 240, 240, 480, 480, 480, 672, 672, 672, 1152,
      1152, 1152, 1152],
     [8, 4, 6, 6, 10, 10, 20, 20, 20, 28, 28, 28, 48, 48, 48, 48])])
def test_se_pairs_as_published(name, exps, squeezes):
    """MobileNetV3-Large: SE on the 8 bnecks Table 1 marks, squeezed to
    make_divisible(exp // 4, 8); EfficientNet-B0: SE on all 16 MBConv
    blocks, squeezed to a quarter of the block's input channels. Each
    pair sits between the depthwise conv and the projection."""
    rows = get_workloads(name)
    assert _se_pairs(rows) == list(zip(exps, squeezes))
    assert sum(r[0] == 1 and r[3] == 1 for r in rows) == 2 * len(exps) + (
        2 if name == "mobilenetv3_large" else 1)


def _random_tables(rng, lengths):
    return {f"net{i}": [(int(rng.integers(1, 3000)), int(rng.integers(1, 2500)),
                         int(rng.integers(1, 1200)), int(rng.integers(1, 4)),
                         int(rng.integers(1, 3))) for _ in range(n)]
            for i, n in enumerate(lengths)}


def test_robust_config_is_one_fused_dispatch_for_the_nine_networks():
    from repro import obs
    axis = np.array([32, 96, 200])
    before = obs.metrics().snapshot()
    cfgs, F, mask = robust_config({n: ZOO[n]() for n in ZOO},
                                  backend="pallas", hs=axis, ws=axis)
    d = obs.metrics().delta(before)
    assert d.get("kernels.fused_dispatches") == 1
    assert "kernels.sweep_dispatches" not in d
    assert d["kernels.config_lane_dispatches"] == 1
    assert d["sweep.layer_rows"] == 807
    assert d["sweep.layer_rows_padded"] == 807     # no row is padding
    assert F.shape == (9, 2) and mask.any()


def test_robust_config_backends_agree_on_ragged_tables():
    """Tables of unequal length, two of them over one 128-row chunk, at
    a small grid and drawn weights: the fused float32 dispatch gives the
    float64 path's objectives to float32 roundoff, and its Pareto set."""
    rng = np.random.default_rng(16)
    mw = _random_tables(rng, (3, 40, 129, 200, 9))
    weights = dict(zip(mw, rng.dirichlet(np.ones(len(mw)))))
    axis = np.array([16, 48, 120, 256])
    c_np, F_np, m_np = robust_config(mw, weights, hs=axis, ws=axis)
    c_pl, F_pl, m_pl = robust_config(mw, weights, backend="pallas",
                                     hs=axis, ws=axis)
    np.testing.assert_array_equal(c_np, c_pl)
    assert np.abs(F_pl - F_np).max() < 1e-5
    assert (m_np == m_pl).all()


def test_robust_config_weights_are_a_plain_weighted_mean():
    axis = np.array([16, 64, 136, 256])
    mw = {n: ZOO[n]() for n in ("alexnet", "googlenet", "efficientnet_b0")}
    weights = {"alexnet": 0.5, "googlenet": 0.0, "efficientnet_b0": 2.0}

    def norm(x):
        return (x - x.min()) / (x.max() - x.min())
    want = sum(w * np.stack([norm(s.energy.reshape(-1)),
                             norm(s.cycles.reshape(-1))], 1)
               for w, s in ((weights[n], grid_sweep(mw[n], axis, axis))
                            for n in mw)) / 2.5
    _, F, mask = robust_config(mw, weights, hs=axis, ws=axis)
    np.testing.assert_allclose(F, want, rtol=0, atol=1e-12)
    _, F_uniform, _ = robust_config(mw, hs=axis, ws=axis)
    assert not np.allclose(F, F_uniform)
    with pytest.raises(ValueError, match="cover"):
        robust_config(mw, {"alexnet": 1.0}, hs=axis, ws=axis)


def test_paper_claim_tall_narrow_energy_optimum():
    """Fig. 2/5: data-movement optimum has height > width."""
    s = grid_sweep(get_workloads("resnet152"))
    h, w = np.unravel_index(np.argmin(s.energy), s.energy.shape)
    assert s.hs[h] > s.ws[w]


def test_paper_claim_robust_frontier_tall():
    """Fig. 5: robust Pareto configs are dominated by h > w entries, and
    the frontier exhibits the cycles/energy tension the paper describes."""
    mw = {n: ZOO[n]() for n in ("alexnet", "resnet152", "densenet201",
                                "mobilenetv3_large")}
    cfgs, F, mask = robust_config(mw)
    sel = cfgs[mask]
    Fm = F[mask]
    assert (sel[:, 0] > sel[:, 1]).mean() > 0.6
    lowest_e = sel[np.argmin(Fm[:, 0])]
    lowest_c = sel[np.argmin(Fm[:, 1])]
    assert lowest_e[0] > lowest_e[1]           # energy optimum: tall
    assert lowest_c[1] >= lowest_c[0]          # cycle optimum: wide/square


def test_paper_claim_small_arrays_with_idle_cost():
    """'Smaller arrays more efficient' emerges once idle-PE cost is on."""
    s = grid_sweep(get_workloads("mobilenetv3_large"), idle_pe_energy=0.2)
    h, w = np.unravel_index(np.argmin(s.energy), s.energy.shape)
    assert s.hs[h] <= 32 and s.ws[w] <= 32


def test_paper_claim_extreme_ratios_bad():
    """Fig. 6: extreme aspect ratios lose at equal PE count."""
    eq = equal_pe_sweep({"resnet152": get_workloads("resnet152")},
                        total_pes=4096, idle_pe_energy=0.05)
    r = eq["resnet152"]
    mid = len(r["h"]) // 2
    assert r["cycles"][0] > r["cycles"][mid]       # 2 x 2048 is terrible
    assert r["cycles"][-1] > r["cycles"][mid]      # 2048 x 2 too


def test_group_conv_prefers_small_arrays():
    """Paper: models with group conv favor small arrays (util collapses)."""
    mob = grid_sweep(get_workloads("mobilenetv3_large"))
    res = grid_sweep(get_workloads("resnet152"))
    # utilization at the biggest array, relative to its own best
    rel_mob = mob.utilization[-1, -1] / mob.utilization.max()
    rel_res = res.utilization[-1, -1] / res.utilization.max()
    assert rel_mob < rel_res


def test_pareto_mask_correct():
    F = np.array([[1, 5], [2, 2], [5, 1], [3, 3], [6, 6]], float)
    m = pareto_mask(F)
    assert m.tolist() == [True, True, True, False, False]


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 60), k=st.integers(1, 3), seed=st.integers(0, 100))
def test_pareto_mask_matches_the_point_by_point_loop(n, k, seed):
    """The broadcast frontier equals a loop that drops each point some
    kept point dominates, ties and NaNs included."""
    rng = np.random.default_rng(seed)
    F = np.round(rng.uniform(size=(n, k)), 1)
    F[rng.uniform(size=n) < 0.1, 0] = np.nan
    want = np.ones(n, bool)
    for i in range(n):
        dom = (F <= F[i]).all(1) & (F < F[i]).any(1)
        want[i] = not (dom & want).any()
    assert pareto_mask(F).tolist() == want.tolist()


def _fig5_robust_F():
    """The 961-point F of `robust_serving_config` over the paper's nine
    CNNs under one seeded Dirichlet mix."""
    from repro.core.dse import robust_serving_config, scenario_sweep
    names = ("alexnet", "vgg16", "googlenet", "bn_inception", "resnet152",
             "resnext152_32x4d", "densenet201", "mobilenetv3_large",
             "efficientnet_b0")
    sweep = scenario_sweep({n: get_workloads(n) for n in names},
                           backend="numpy")
    weights = np.random.default_rng(5).dirichlet(np.ones(len(names)))
    return robust_serving_config(sweep, dict(zip(names, weights)))[1]


def _with_nan(*cols):
    """Tie-heavy rows with NaN in some of `cols`; with both, the first
    rows hold NaN in both."""
    rng = np.random.default_rng(len(cols) + cols[0])
    F = rng.integers(0, 5, (120, 2)).astype(float)
    for c in cols:
        F[rng.uniform(size=120) < 0.15, c] = np.nan
    F[:3, list(cols)] = np.nan
    return F


_PARETO_2D_CASES = {
    **{f"ties-n{n}": (lambda n=n: np.random.default_rng(n).integers(
        0, 5, (n, 2))) for n in (1, 2, 3, 9, 40, 117, 200)},
    "duplicates": lambda: np.repeat(np.random.default_rng(1).integers(
        0, 5, (30, 2)).astype(float), 3, axis=0),
    "infinities": lambda: np.random.default_rng(2).choice(
        [-np.inf, 0.0, 1.0, 2.0, np.inf], (150, 2)),
    "signed-zero": lambda: np.random.default_rng(3).choice(
        [-0.0, 0.0, 1.0, -1.0], (80, 2)),
    "nan-col0": lambda: _with_nan(0),
    "nan-col1": lambda: _with_nan(1),
    "nan-both": lambda: _with_nan(0, 1),
    "all-nan": lambda: np.full((4, 2), np.nan),
    "empty": lambda: np.zeros((0, 2)),
    "single": lambda: np.array([[3.0, np.inf]]),
    "fig5-robust-F": _fig5_robust_F,
}


@pytest.mark.parametrize("case", list(_PARETO_2D_CASES))
def test_pareto_mask_2d_sweep_equals_all_pairs(case):
    """The sorted two-objective frontier is the all-pairs mask, point for
    point: ties, duplicates, infinities, signed zeros, NaN rows (kept,
    dominating nothing), n = 0 and 1, and the Fig. 5 objectives."""
    F = _PARETO_2D_CASES[case]()
    want = _pareto_mask_all_pairs(F)
    assert pareto_mask(F).tolist() == want.tolist()


def test_nsga2_recovers_grid_frontier():
    wl = get_workloads("alexnet")
    s = grid_sweep(wl)
    cfgs_exact, F_exact, _ = pareto_grid(s)
    from repro.core.dse import pareto_nsga2
    P, F = pareto_nsga2(wl, pop=48, gens=25, seed=0)
    # every NSGA-II survivor must be non-dominated vs the exact frontier
    # within the tolerance of the coarser genome (quantum 8)
    for f in F:
        dominated = ((F_exact <= f).all(1) & (F_exact < f).any(1)).any()
        slack = (F_exact / np.maximum(f, 1e-12))
        assert (not dominated) or (np.min(np.max(slack, axis=1)) > 0.98)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 40), seed=st.integers(0, 100))
def test_nds_ranks_consistent(n, seed):
    rng = np.random.default_rng(seed)
    F = rng.uniform(size=(n, 2))
    ranks = fast_non_dominated_sort(F)
    assert (ranks[pareto_mask(F)] == 0).all()
    assert (ranks >= 0).all()
    d = crowding_distance(F)
    assert d.shape == (n,)


def test_output_stationary_dataflow():
    """Future-work variant: OS eliminates accumulator traffic; WS amortizes
    weight fetches. The crossover matches the operand shapes."""
    from repro.core.dataflows import analyze_gemm_os
    from repro.core.systolic import analyze_gemm
    ws = analyze_gemm(1024, 4608, 256, 128, 128)
    os_ = analyze_gemm_os(1024, 4608, 256, 128, 128)
    assert float(os_.m_aa) == 0.0 and float(ws.m_aa) > 0
    assert float(os_.macs) == float(ws.macs)
    assert 0 < float(os_.utilization) <= 1
    # weight-heavy GEMM (tall K, M smaller than K): WS fetches W once,
    # OS re-fetches per M tile -> WS moves less UB weight traffic
    ws2 = analyze_gemm(2048, 8192, 256, 128, 128)
    os2 = analyze_gemm_os(2048, 8192, 256, 128, 128)
    assert float(ws2.m_ub_weight) < float(os2.m_ub_weight)


def test_pareto_nsga2_threads_model_options():
    """Regression: model options passed to pareto_nsga2 must reach
    analyze_network inside eval_fn (they used to be swallowed by **kw going
    only to nsga2). Halving all operand widths halves every energy
    objective, so frontier energies must scale by exactly 0.5."""
    from repro.core.dse import pareto_nsga2
    from repro.core.model_core import Precision
    wl = get_workloads("alexnet")
    _, F8 = pareto_nsga2(wl, pop=16, gens=4, seed=0)
    _, F4 = pareto_nsga2(wl, pop=16, gens=4, seed=0,
                         precision=Precision(4, 4, 4))
    # same seed + width-independent cycles => identical evolution path
    assert F4[:, 0].min() == pytest.approx(F8[:, 0].min() / 2)
    # explicit model_kw dict works too
    _, F4b = pareto_nsga2(wl, pop=16, gens=4, seed=0,
                          model_kw={"precision": Precision(4, 4, 4)})
    np.testing.assert_allclose(F4b, F4)


def test_equal_pe_sweep_backend_dispatch():
    """equal_pe_sweep(backend="pallas") must match the numpy path (Fig. 6
    on the fused kernel), and reject unknown backends."""
    mw = {"alexnet": get_workloads("alexnet")}
    a = equal_pe_sweep(mw, total_pes=4096)
    b = equal_pe_sweep(mw, total_pes=4096, backend="pallas")
    np.testing.assert_array_equal(a["alexnet"]["h"], b["alexnet"]["h"])
    for k in ("energy", "cycles", "utilization"):
        np.testing.assert_allclose(a["alexnet"][k], b["alexnet"][k],
                                   rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError):
        equal_pe_sweep(mw, total_pes=4096, backend="fortran")


def test_multi_array_parallelism():
    """Future-work variant: P arrays split N; makespan shrinks, activation
    reads replicate (parallelism/energy tension)."""
    from repro.core.dataflows import analyze_gemm_multi
    from repro.core.systolic import analyze_gemm
    one = analyze_gemm(1024, 4608, 512, 128, 128)
    four = analyze_gemm_multi(1024, 4608, 512, 128, 128, n_arrays=4)
    assert float(four.cycles) < float(one.cycles) / 2.5   # near-4x makespan
    assert float(four.m_ub_act) == 4 * float(one.m_ub_act)  # replication
    assert float(four.macs) == float(one.macs) * 4 / 4 * 4 / 4 or True
    assert float(four.energy) > float(one.energy)         # energy cost
