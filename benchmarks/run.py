"""Benchmark harness — one function per paper table/figure, plus the
beyond-paper LM-architecture analysis. Prints ``name,us_per_call,derived``
CSV and writes machine-readable results to results/benchmarks/.

  fig2  ResNet-152 heatmaps (961-config sweep)           [paper Fig. 2]
  fig3  Pareto sets, exact + NSGA-II                     [paper Fig. 3]
  fig4  per-model data-movement heatmaps (9 CNNs)        [paper Fig. 4]
  fig5  robust configuration across the model mix        [paper Fig. 5]
  fig6  equal-PE-count aspect-ratio study                [paper Fig. 6]
  lm    the 10 assigned LM archs on the same DSE         [paper future work]
  scenarios  serving-scenario DSE: the (arch x phase x batch x seq) matrix
        in ONE fused batched Pallas dispatch vs the per-scenario loop,
        robust serving config + tokens/sec scoring       [beyond paper]
  traffic  traffic-driven serving simulation: fused cost-table build vs the
        per-lattice-point dispatch loop, a 1M-request Poisson replay, and
        the SLO capacity sweep + robust traffic config   [beyond paper]
  kv     KV-reuse & speculative serving: cache-hit and acceptance-rate
        capacity sweeps, the robust-winner flip table, and the
        no-reuse == plain-sweep CI gate                  [beyond paper]
  fleet  fleet-scale serving: per-block stage tables from ONE fused
        dse_eval_batched dispatch vs the per-stage loop, a 1M-request
        multi-server fleet replay, and the fleet-composition capacity
        sweep + robust fleet config                      [beyond paper]
  obs    observability: tracing-disabled overhead on the 1M-request
        replay, deterministic Perfetto export of a seeded disagg fleet
        trace, and the metrics-registry counter totals  [beyond paper]
  windowed  windowed telemetry & SLO burn rate: windowing overhead on
        the 1M-request replay, the merged-window == whole-run histogram
        identity, the canonical burst-replay alert sequence, and the
        peak-burn (day-average passes, budget burns) flag [beyond paper]
  connectivity  graph-IR liveness: peak UB residency + finite-UB spill for
        chain vs residual vs dense-concat networks       [beyond paper]
  ablations  model-accounting options (act_reread, idle-PE, load hops)
  backends   grid_sweep numpy-float64 vs fused Pallas sweep kernel
  precision  bitwidth DSE: (h, w, act_bits, weight_bits) design points
  kernels    Pallas kernel microbenches (Mosaic on TPU, interpret mode
             elsewhere; each row is named after the backend that ran it)

``--quick`` runs the reduced capacity sweep, the serving-scenario sweep,
the traffic, kv, fleet, search, obs and windowed stages, writing
results/benchmarks/BENCH_graph.json, BENCH_scenarios.json,
BENCH_traffic.json, BENCH_kv.json, BENCH_fleet.json, BENCH_search.json,
BENCH_obs.json and BENCH_windowed.json (the CI smoke/perf-trajectory
probes).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks")


def _timeit(fn, n=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    dt = (time.perf_counter() - t0) / n
    return out, dt * 1e6


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def _save(name, obj):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=lambda o: np.asarray(o).tolist())
    if name.startswith("BENCH_"):
        _append_history(name, obj)


def _append_history(name, obj):
    """Append the stage's headline scalars to BENCH_history.jsonl — the
    accumulating perf-trajectory log (one JSON line per BENCH_* stage per
    run; nested tables stay in the per-stage BENCH_*.json snapshots)."""
    scalars = {k: v for k, v in obj.items()
               if isinstance(v, (bool, int, float))}
    rec = {"bench": name, "unix_time": round(time.time(), 3),
           "scalars": {k: scalars[k] for k in sorted(scalars)}}
    with open(os.path.join(RESULTS, "BENCH_history.jsonl"), "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")


def _stage(fn, *args, **kw):
    """Run one benchmark stage on a CLEAN process-wide metrics registry so
    per-stage counter reports never leak across stages (the obs stage
    asserts this purity on entry)."""
    from repro.obs import reset_metrics
    reset_metrics()
    return fn(*args, **kw)


def fig2_resnet_heatmap():
    from repro.core import get_workloads, grid_sweep
    wl = get_workloads("resnet152")
    s, us = _timeit(lambda: grid_sweep(wl))
    be = np.unravel_index(np.argmin(s.energy), s.energy.shape)
    bu = np.unravel_index(np.argmax(s.utilization), s.utilization.shape)
    # index of the TPU-like 128x128 config, derived from the actual axes
    # (the nearest grid point if 128 is not on the grid)
    i128 = int(np.argmin(np.abs(s.hs - 128)))
    j128 = int(np.argmin(np.abs(s.ws - 128)))
    derived = (f"minE=({s.hs[be[0]]}x{s.ws[be[1]]})"
               f";maxUtil=({s.hs[bu[0]]}x{s.ws[bu[1]]})"
               f";util{s.hs[i128]}x{s.ws[j128]}="
               f"{s.utilization[i128][j128]:.3f}")
    _emit("fig2_resnet152_961cfg_sweep", us, derived)
    _save("fig2", {"hs": s.hs, "ws": s.ws, "energy": s.energy,
                   "cycles": s.cycles, "utilization": s.utilization})
    return s


def fig3_pareto():
    from repro.core import get_workloads, grid_sweep, pareto_grid
    from repro.core.dse import pareto_nsga2
    wl = get_workloads("resnet152")
    s = grid_sweep(wl)
    (cfgs, F, mask), us = _timeit(lambda: pareto_grid(s))
    _emit("fig3_pareto_exact_energy_cycles", us,
          f"frontier={int(mask.sum())};best_cfgs={cfgs[:3].tolist()}")
    (cfgs_u, F_u, mask_u), us2 = _timeit(
        lambda: pareto_grid(s, objectives=("utilization", "cycles")))
    _emit("fig3_pareto_exact_util_cycles", us2,
          f"frontier={int(mask_u.sum())}")
    (P, FN), us3 = _timeit(lambda: pareto_nsga2(wl, pop=48, gens=20), n=1)
    _emit("fig3_pareto_nsga2", us3, f"frontier={len(P)}")
    _save("fig3", {"exact_cfgs": cfgs, "exact_F": F,
                   "nsga2_cfgs": P, "nsga2_F": FN})


def fig4_model_heatmaps():
    from repro.core import ZOO, grid_sweep
    out = {}
    for name in ZOO:
        s, us = _timeit(lambda n=name: grid_sweep(ZOO[n]()), n=1)
        be = np.unravel_index(np.argmin(s.energy), s.energy.shape)
        spread = float((s.energy.max() - s.energy.min()) / s.energy.min())
        out[name] = {"minE_h": int(s.hs[be[0]]), "minE_w": int(s.ws[be[1]]),
                     "spread": spread, "energy": s.energy}
        _emit(f"fig4_{name}", us,
              f"minE=({s.hs[be[0]]}x{s.ws[be[1]]});spread={spread:.3f}")
    _save("fig4", out)


def fig5_robust():
    from repro.core import ZOO, robust_config
    mw = {n: ZOO[n]() for n in ZOO}
    (cfgs, F, mask), us = _timeit(lambda: robust_config(mw), n=1)
    sel, Fm = cfgs[mask], F[mask]
    tall = float((sel[:, 0] > sel[:, 1]).mean())
    lowE = sel[np.argmin(Fm[:, 0])].tolist()
    lowC = sel[np.argmin(Fm[:, 1])].tolist()
    _emit("fig5_robust_config", us,
          f"frontier={int(mask.sum())};tall_frac={tall:.2f}"
          f";minE={lowE};minCycles={lowC}")
    _save("fig5", {"cfgs": sel, "F": Fm, "tall_frac": tall})


def fig6_equal_pe():
    from repro.core import ZOO, equal_pe_sweep
    mw = {n: ZOO[n]() for n in ZOO}
    eq, us = _timeit(lambda: equal_pe_sweep(mw, total_pes=16384,
                                            idle_pe_energy=0.05), n=1)
    worst = {n: int(np.argmax(v["energy"])) for n, v in eq.items()}
    extreme_bad = sum(1 for n, i in worst.items()
                      if i in (0, len(eq[n]["h"]) - 1))
    _emit("fig6_equal_pe_aspect", us,
          f"models_with_extreme_worst={extreme_bad}/{len(eq)}")
    _save("fig6", eq)


def lm_architectures():
    from repro.configs.base import SHAPES, cells_for, get_config, list_archs
    from repro.core import extract_workloads, grid_sweep
    out = {}
    for arch in list_archs():
        cfg = get_config(arch)
        for shape_name in ("train_4k", "decode_32k"):
            if shape_name not in cells_for(arch):
                continue
            wl = extract_workloads(cfg, SHAPES[shape_name])
            s, us = _timeit(lambda w=wl: grid_sweep(w), n=1)
            be = np.unravel_index(np.argmin(s.energy), s.energy.shape)
            bu = np.unravel_index(np.argmax(s.utilization),
                                  s.utilization.shape)
            key = f"{arch}/{shape_name}"
            out[key] = {
                "minE": [int(s.hs[be[0]]), int(s.ws[be[1]])],
                "maxUtil": [int(s.hs[bu[0]]), int(s.ws[bu[1]])],
                "util_256x256": float(s.utilization[-1, -1]),
                "util_best": float(s.utilization.max()),
            }
            _emit(f"lm_{arch}_{shape_name}", us,
                  f"minE=({s.hs[be[0]]}x{s.ws[be[1]]})"
                  f";maxUtil=({s.hs[bu[0]]}x{s.ws[bu[1]]})"
                  f";util256={s.utilization[-1, -1]:.3f}")
    _save("lm_archs", out)


def scenarios_bench(quick: bool = False):
    """Serving-scenario DSE: the (arch x phase x batch x seq_len) matrix —
    one fused batched Pallas dispatch over (scenario, h, w) vs the
    per-scenario dispatch loop vs the numpy float64 loop, plus the robust
    serving configuration and tokens/sec-at-clock scores. Writes
    BENCH_scenarios.json (the CI perf-trajectory probe for the fusion)."""
    from repro.core.dse import (grid_axes, robust_serving_config,
                                scenario_sweep)
    from repro.scenarios import (DEFAULT_CLOCK_HZ, named_workloads,
                                 score_scenarios, serving_matrix)
    scs = serving_matrix(batches=(1, 8), seq_lens=(512, 2048))
    nw = named_workloads(scs)
    # the batched config space: many small per-scenario sweeps is exactly
    # the regime the fusion targets (dispatch overhead dominates); the
    # full 961-grid study of a single model stays with grid_sweep.
    # quick (CI) keeps the same space but times a single rep per backend.
    reps = 1 if quick else 3
    hs = grid_axes()[::4]                     # 8x8 = 64 configs
    kw = dict(hs=hs, ws=hs)
    s_fu, us_fu = _timeit(lambda: scenario_sweep(nw, **kw), n=reps)
    s_lp, us_lp = _timeit(
        lambda: scenario_sweep(nw, fused=False, **kw), n=reps)
    s_np, us_np = _timeit(lambda: scenario_sweep(nw, backend="numpy", **kw),
                          n=reps)
    rel = 0.0
    for k in ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
              "m_aa", "ub_bw_bits"):
        a = getattr(s_np, k)
        b = getattr(s_fu, k)
        rel = max(rel, float((np.abs(a - b) / (np.abs(a) + 1.0)).max()))
    _emit("scenario_sweep_fused", us_fu,
          f"{len(scs)}scenarios_x_{hs.size**2}cfgs"
          f";max_rel_vs_numpy={rel:.2e}")
    _emit("scenario_sweep_pallas_loop", us_lp,
          f"fused_speedup={us_lp / us_fu:.2f}x")
    _emit("scenario_sweep_numpy_loop", us_np,
          f"fused_speedup={us_np / us_fu:.2f}x")

    # robust serving config: uniform mix + a decode-heavy production mix
    cfgs, F, mask = robust_serving_config(s_fu)
    sel, Fm = cfgs[mask], F[mask]
    robust = sel[np.argmin(Fm.sum(axis=1))]
    decode_heavy = {n: (4.0 if "/decode/" in n else 1.0) for n in s_fu.names}
    _, Fd, maskd = robust_serving_config(s_fu, weights=decode_heavy)
    seld = cfgs[maskd]
    robust_d = seld[np.argmin(Fd[maskd].sum(axis=1))]
    _emit("scenario_robust_config", 0.0,
          f"frontier={int(mask.sum())};uniform={robust.tolist()}"
          f";decode_heavy={robust_d.tolist()}")

    recs = score_scenarios(s_fu, scs, at=(int(robust[0]), int(robust[1])))
    worst = min(recs, key=lambda r: r["tps_at_frac_of_best"])
    _emit("scenario_tokens_per_sec", 0.0,
          f"clock={DEFAULT_CLOCK_HZ/1e6:.0f}MHz"
          f";worst_frac_of_best={worst['tps_at_frac_of_best']:.3f}"
          f";worst={worst['scenario']}")
    _save("BENCH_scenarios", {
        "scenarios": len(scs), "configs": int(hs.size ** 2),
        "grid": hs.tolist(),
        "fused_us_per_call": us_fu,
        "pallas_loop_us_per_call": us_lp,
        "numpy_loop_us_per_call": us_np,
        "speedup_fused_over_pallas_loop": us_lp / us_fu,
        "speedup_fused_over_numpy_loop": us_np / us_fu,
        "max_rel_fused_vs_numpy": rel,
        "robust_uniform_hw": robust.tolist(),
        "robust_decode_heavy_hw": robust_d.tolist(),
        "frontier_size": int(mask.sum()),
        "clock_hz": DEFAULT_CLOCK_HZ,
        "scores": recs,
    })


def traffic_bench(quick: bool = False):
    """Traffic-driven serving simulation probes, written to
    BENCH_traffic.json:

      * the FULL 10-arch x default-(h, w) cost-table lattice from one
        fused dse_eval_batched dispatch vs the per-lattice-point dispatch
        loop (the fusion's perf-trajectory number);
      * a 1,000,000-request Poisson replay through the discrete-event
        simulator — cost-table lookups only, zero model evaluations —
        reporting requests simulated per wall-second (acceptance: 1M in
        under 60 s on one CPU host);
      * the SLO capacity sweep (max QPS under p99 TTFT/TPOT per config)
        and the mix-weighted robust traffic config.
    """
    from repro.core.dse import robust_traffic_config, slo_capacity_sweep
    from repro.traffic import (SLO, SimConfig, TrafficModel,
                               build_cost_tables, simulate)

    # 1. cost-table build: the full 10-arch x default grid, fused vs loop
    ts, us_fu = _timeit(lambda: build_cost_tables(backend="pallas"), n=1)
    _, us_lp = _timeit(lambda: build_cost_tables(backend="pallas-loop"),
                       n=1)
    _emit("traffic_cost_tables_fused", us_fu,
          f"{ts.n_scenarios}lattice_pts_x_{ts.n_configs}cfgs"
          f"->{len(ts)}tables;1_dispatch")
    _emit("traffic_cost_tables_loop", us_lp,
          f"{ts.n_scenarios}_dispatches;fused_speedup={us_lp / us_fu:.2f}x")

    # 2. the 1M-request replay (cheapest arch: wall time is event-bound,
    # but a fast table keeps the simulated span sane)
    n_replay = 1_000_000
    tab = ts.table("xlstm-125m", 128, 128)
    tm = TrafficModel(rate_qps=200.0, prompt_median=256, output_median=48)
    trace = tm.sample(n_replay, seed=0)
    res = simulate(tab, trace, SimConfig(slots=64))
    _emit("traffic_replay_1m_requests", res.wall_seconds * 1e6,
          f"{res.requests_per_wall_sec:.0f}req_per_wall_sec"
          f";steps={res.decode_steps};tokens={res.tokens_out}")

    # 3. SLO capacity sweep + robust traffic config on a reduced space
    archs = ["h2o-danube-3-4b", "xlstm-125m"]
    hw = ((64, 64), (128, 128), (256, 256), (64, 256))
    slo = SLO(ttft_s=2.0, tpot_s=0.15)
    mix = {
        "h2o-danube-3-4b": TrafficModel(rate_qps=1.0, prompt_median=256,
                                        output_median=64),
        "xlstm-125m": TrafficModel(rate_qps=1.0, prompt_median=128,
                                   output_median=32, arrival="mmpp"),
    }
    n_req = 300 if quick else 1200
    sweep, us_slo = _timeit(
        lambda: slo_capacity_sweep(mix, slo, archs=archs, hw=hw,
                                   sim=SimConfig(slots=16),
                                   n_requests=n_req, tables=ts), n=1)
    weights = {"h2o-danube-3-4b": 3.0, "xlstm-125m": 1.0}
    hw_out, F, mask, winner = robust_traffic_config(sweep, weights=weights)
    best = {a: sweep.best(a) for a in archs}
    _emit("traffic_slo_capacity_sweep", us_slo,
          ";".join(f"{a}_max_qps={q:.2f}@{h}x{w}"
                   for a, (h, w, q) in best.items()))
    _emit("traffic_robust_config", 0.0,
          f"winner={int(hw_out[winner, 0])}x{int(hw_out[winner, 1])}"
          f";frontier={int(mask.sum())}")
    _save("BENCH_traffic", {
        "lattice_points": ts.n_scenarios, "configs": ts.n_configs,
        "tables": len(ts),
        "cost_table_fused_us": us_fu, "cost_table_loop_us": us_lp,
        "cost_table_fused_speedup": us_lp / us_fu,
        "replay_requests": n_replay,
        "replay_wall_seconds": res.wall_seconds,
        "replay_requests_per_wall_sec": res.requests_per_wall_sec,
        "replay_decode_steps": res.decode_steps,
        "replay_tokens_out": res.tokens_out,
        "slo": {"ttft_s": slo.ttft_s, "tpot_s": slo.tpot_s,
                "pct": slo.pct},
        "slo_sweep_us": us_slo, "slo_sweep_n_requests": n_req,
        "archs": archs, "hw": [list(p) for p in hw],
        "max_qps": sweep.max_qps.tolist(),
        "energy_per_token": sweep.energy_per_token.tolist(),
        "robust_weights": weights,
        "robust_winner_hw": [int(hw_out[winner, 0]),
                             int(hw_out[winner, 1])],
        "robust_frontier": int(mask.sum()),
    })


def kv_bench(quick: bool = False):
    """KV-reuse & speculative serving probes, written to BENCH_kv.json:

      * the no-reuse gate row: the traffic stage's SLO capacity sweep
        re-run through the `cache_hit=0` path — CI asserts it matches
        BENCH_traffic.json exactly (the KV machinery must be a no-op
        when off);
      * cache-hit sweep: max QPS + the Fig. 5 robust array-shape winner
        at increasing shared-prefix fractions (prefix-cache tier on);
      * acceptance-rate sweep: draft/verify speculative decoding at
        increasing acceptance rates, same tracking;
      * the winner-flip table: every (scenario, SLO) point whose robust
        winner differs from the no-reuse winner (acceptance: >= 1).
    """
    from repro.core.dse import robust_traffic_config, slo_capacity_sweep
    from repro.traffic import (SLO, KVReuseConfig, SimConfig,
                               SpecDecodeConfig, TrafficModel,
                               build_cost_tables)

    n_req = 300 if quick else 1200
    sim = SimConfig(slots=16)
    tables = build_cost_tables(backend="pallas")

    # ---- no-reuse gate: the traffic stage's sweep through cache_hit=0 ----
    # (same archs/hw/mix/SLO/tables as traffic_bench; CI asserts the
    # numbers below equal BENCH_traffic.json's)
    g_archs = ["h2o-danube-3-4b", "xlstm-125m"]
    g_hw = ((64, 64), (128, 128), (256, 256), (64, 256))
    g_slo = SLO(ttft_s=2.0, tpot_s=0.15)
    g_mix = {
        "h2o-danube-3-4b": TrafficModel(rate_qps=1.0, prompt_median=256,
                                        output_median=64),
        "xlstm-125m": TrafficModel(rate_qps=1.0, prompt_median=128,
                                   output_median=32, arrival="mmpp"),
    }
    gate = slo_capacity_sweep(g_mix, g_slo, archs=g_archs, hw=g_hw,
                              sim=sim, n_requests=n_req, tables=tables,
                              cache_hit=0.0)
    plain = slo_capacity_sweep(g_mix, g_slo, archs=g_archs, hw=g_hw,
                               sim=sim, n_requests=n_req, tables=tables)
    gate_ok = bool((gate.max_qps == plain.max_qps).all())
    assert gate_ok, "cache_hit=0 drifted from the plain sweep"
    _emit("kv_no_reuse_gate", 0.0, f"identical_to_plain={gate_ok}")

    # ---- scenario sweeps: iso-PE aspect ratios, where reuse can flip ----
    # the robust winner (a 256x256 vs 64x64 comparison is a PE-count
    # contest, not a shape question)
    arch = "h2o-danube-3-4b"
    hw = ((128, 128), (64, 256), (256, 64))     # 16384 PEs each
    mix = TrafficModel(rate_qps=1.0, prompt_median=128, output_median=256,
                       prompt_range=(16, 1024), output_range=(16, 1024))
    slos = {"tight": SLO(ttft_s=0.5, tpot_s=0.05),
            "relaxed": SLO(ttft_s=2.0, tpot_s=0.15)}
    spec_k = 4
    spec_tables = build_cost_tables(
        [arch, "xlstm-125m"], hw, backend="pallas",
        spec=SpecDecodeConfig("xlstm-125m", k=spec_k))

    def winner(sw):
        hw_out, _F, mask, win = robust_traffic_config(
            sw, weights={arch: 1.0})
        return [int(hw_out[win, 0]), int(hw_out[win, 1])], int(mask.sum())

    rows, flips = [], []
    t0 = time.perf_counter()
    for slo_name, slo in slos.items():
        def sweep(**kw):
            return slo_capacity_sweep(mix, slo, archs=[arch], hw=hw,
                                      sim=sim, n_requests=n_req, **kw)

        w0, _ = winner(sweep(tables=tables))
        scen = [("no_reuse", {"tables": tables})]
        for share in (0.25, 0.5, 0.85):
            scen.append((f"cache_hit_{share}", {
                "tables": tables,
                "cache_hit": KVReuseConfig(share=share, prefix_len=1024,
                                           n_prefixes=4,
                                           cache_mib=4096.0)}))
        for acc in (0.5, 0.7, 0.9):
            scen.append((f"spec_accept_{acc}", {
                "tables": spec_tables,
                "spec_decode": SpecDecodeConfig("xlstm-125m", k=spec_k,
                                                acceptance=acc)}))
        scen.append(("combined_0.85_0.9", {
            "tables": spec_tables,
            "cache_hit": KVReuseConfig(share=0.85, prefix_len=1024,
                                       n_prefixes=4, cache_mib=4096.0),
            "spec_decode": SpecDecodeConfig("xlstm-125m", k=spec_k,
                                            acceptance=0.9)}))
        for name, kw in scen:
            sw = sweep(**kw)
            w, front = winner(sw)
            flip = w != w0
            rows.append({"slo": slo_name, "scenario": name,
                         "winner_hw": w, "no_reuse_winner_hw": w0,
                         "flip": flip, "frontier": front,
                         "max_qps": sw.max_qps.tolist(),
                         "energy_per_token":
                             sw.energy_per_token.tolist()})
            if flip:
                flips.append({"slo": slo_name, "scenario": name,
                              "winner_hw": w, "no_reuse_winner_hw": w0})
            _emit(f"kv_{slo_name}_{name}", 0.0,
                  f"winner={w[0]}x{w[1]};flip={flip}")
    us_rows = (time.perf_counter() - t0) * 1e6
    _emit("kv_winner_flip_table", us_rows,
          f"flips={len(flips)}of{len(rows)}"
          + (f";first={flips[0]['slo']}/{flips[0]['scenario']}"
             f"@{flips[0]['winner_hw'][0]}x{flips[0]['winner_hw'][1]}"
             if flips else ""))
    _save("BENCH_kv", {
        "gate": {
            "archs": g_archs, "hw": [list(p) for p in g_hw],
            "slo": {"ttft_s": g_slo.ttft_s, "tpot_s": g_slo.tpot_s,
                    "pct": g_slo.pct},
            "no_reuse_max_qps": gate.max_qps.tolist(),
            "cache_hit0_identical": gate_ok,
        },
        "arch": arch, "hw": [list(p) for p in hw],
        "slos": {k: {"ttft_s": v.ttft_s, "tpot_s": v.tpot_s,
                     "pct": v.pct} for k, v in slos.items()},
        "n_requests": n_req,
        "scenarios": rows,
        "winner_flips": flips,
    })


def fleet_bench(quick: bool = False):
    """Fleet-scale serving probes, written to BENCH_fleet.json:

      * per-block stage tables for 2 archs x (h, w) x tp from ONE fused
        dse_eval_batched dispatch vs the one-dispatch-per-stage loop (the
        fleet fusion's perf-trajectory number);
      * a 1,000,000-request fleet replay: 8 two-stage pipelined servers
        behind round-robin routing — routing is O(n) and each server runs
        the O(events) bulk-advance on its sub-trace (acceptance: under
        30 s wall on one CPU host);
      * the fleet-composition capacity sweep (partition -> stage tables ->
        multi-server sim -> SLO bisection) over an iso-PE budget and the
        mix-weighted robust fleet config.
    """
    from repro.core.dse import (FleetSpec, PoolSpec, fleet_capacity_sweep,
                                robust_fleet_config)
    from repro.fleet import (DEFAULT_LINK, FleetSimConfig, FleetTables,
                             build_stage_tables, partition_server_table,
                             simulate_fleet)
    from repro.traffic import SLO, SimConfig, TrafficModel

    # 1. stage tables: one fused dispatch vs the per-stage dispatch loop
    archs = ["yi-9b", "mixtral-8x22b"]
    hw = ((64, 64), (128, 128))
    lat = dict(slot_lattice=(1, 8, 32), kv_lattice=(256, 2048),
               prompt_lattice=(256, 2048)) if quick else {}
    ts, us_fu = _timeit(lambda: build_stage_tables(
        archs, hw=hw, tps=(1, 2), backend="pallas", **lat), n=1)
    _, us_lp = _timeit(lambda: build_stage_tables(
        archs, hw=hw, tps=(1, 2), backend="pallas-loop", **lat), n=1)
    _emit("fleet_stage_tables_fused", us_fu,
          f"{ts.n_scenarios}stage_pts_x_{ts.n_configs}cfgs"
          f"->{len(ts)}tables;1_dispatch")
    _emit("fleet_stage_tables_loop", us_lp,
          f"{ts.n_scenarios}_dispatches;fused_speedup={us_lp / us_fu:.2f}x")

    # 2. the 1M-request fleet replay: 8 pipelined xlstm servers
    n_replay = 1_000_000
    st_x = build_stage_tables(["xlstm-125m"], hw=((128, 128),),
                              backend="numpy")
    srv = partition_server_table(st_x.table("xlstm-125m", 128, 128),
                                 n_stages=2, link=DEFAULT_LINK).table
    tm = TrafficModel(rate_qps=200.0, prompt_median=256, output_median=48)
    trace = tm.sample(n_replay, seed=0)
    res = simulate_fleet(FleetTables(mixed=[srv] * 8), trace,
                         FleetSimConfig(server=SimConfig(slots=64)))
    _emit("fleet_replay_1m_requests", res.wall_seconds * 1e6,
          f"{res.requests_per_wall_sec:.0f}req_per_wall_sec"
          f";servers={res.n_servers};tokens={res.tokens_out}")

    # 3. composition sweep under an iso-PE budget + robust fleet config
    budget = 4 * 128 * 128
    fleets = [
        FleetSpec("16x[64x64]", (PoolSpec(64, 64, 16),)),
        FleetSpec("4x[128x128]", (PoolSpec(128, 128, 4),)),
        FleetSpec("8x[tp2 64x64]", (PoolSpec(64, 64, 8, tp=2),)),
        FleetSpec("disagg 1x128 + 3x128",
                  (PoolSpec(128, 128, 1, role="prefill"),
                   PoolSpec(128, 128, 3, role="decode"))),
    ]
    mix = {"yi-9b": TrafficModel(rate_qps=1.0, prompt_median=256,
                                 output_median=64),
           "mixtral-8x22b": TrafficModel(rate_qps=1.0, prompt_median=512,
                                         output_median=128,
                                         arrival="mmpp")}
    slo = SLO(ttft_s=8.0, tpot_s=3.0)
    n_req = 300 if quick else 1000
    sweep, us_sw = _timeit(lambda: fleet_capacity_sweep(
        mix, slo, fleets, archs=archs,
        sim=FleetSimConfig(server=SimConfig(slots=16)),
        n_requests=n_req, stage_tables=ts, pe_budget=budget), n=1)
    weights = {"yi-9b": 3.0, "mixtral-8x22b": 1.0}
    fl, F, mask, winner = robust_fleet_config(sweep, weights=weights)
    best = {a: sweep.best(a) for a in archs}
    _emit("fleet_capacity_sweep", us_sw,
          ";".join(f"{a}_max_qps={q:.2f}@{f.name}"
                   for a, (f, q) in best.items()))
    _emit("fleet_robust_config", 0.0,
          f"winner={fl[winner].name};frontier={int(mask.sum())}")
    _save("BENCH_fleet", {
        "stage_points": ts.n_scenarios, "configs": ts.n_configs,
        "tables": len(ts),
        "stage_tables_fused_us": us_fu, "stage_tables_loop_us": us_lp,
        "stage_tables_fused_speedup": us_lp / us_fu,
        "replay_requests": n_replay,
        "replay_servers": res.n_servers,
        "replay_wall_seconds": res.wall_seconds,
        "replay_requests_per_wall_sec": res.requests_per_wall_sec,
        "replay_tokens_out": res.tokens_out,
        "slo": {"ttft_s": slo.ttft_s, "tpot_s": slo.tpot_s,
                "pct": slo.pct},
        "sweep_us": us_sw, "sweep_n_requests": n_req,
        "pe_budget": budget,
        "fleets": [f.name for f in fleets],
        "archs": archs,
        "max_qps": sweep.max_qps.tolist(),
        "energy_per_token": sweep.energy_per_token.tolist(),
        "robust_weights": weights,
        "robust_winner": fl[winner].name,
        "robust_frontier": int(mask.sum()),
    })


def connectivity():
    """Graph-IR study: how connectivity (skip / dense-concat edges) changes
    peak UB residency and finite-capacity spill energy, chain baseline
    (VGG-16) vs residual (ResNet-152) vs dense concat (DenseNet-201)."""
    from repro.core.dse import UB_KIBS, capacity_sweep
    from repro.graph import build_graph
    from repro.graph.schedule import occupancy_profile
    out = {"ub_kibs": list(UB_KIBS), "models": {}}
    for name in ("vgg16", "resnet152", "densenet201"):
        g = build_graph(name)
        (cs, us) = _timeit(lambda gg=g: capacity_sweep(gg), n=1)
        chain = occupancy_profile(g.as_chain(), "dfs")
        bfs = occupancy_profile(g, "bfs")
        mib = 1.0 / (8.0 * 2 ** 20)
        rec = {
            "peak_mib_dfs": cs.peak_bits * mib,
            "peak_mib_bfs": bfs.peak_bits * mib,
            "peak_mib_chain": chain.peak_bits * mib,
            "connectivity_ratio": cs.peak_bits / chain.peak_bits,
            "spill_energy": cs.spill_energy.tolist(),
            # the best (h, w) is capacity-independent by construction (the
            # spill term is a scalar offset per ub); store it once
            "best_h_w": cs.best(0)[:2],
            "best_energy_total_per_ub": [cs.best(u)[2]
                                         for u in range(len(cs.ub_kibs))],
        }
        out["models"][name] = rec
        _emit(f"connectivity_{name}", us,
              f"peak={rec['peak_mib_dfs']:.2f}MiB"
              f";chain_ratio={rec['connectivity_ratio']:.2f}"
              f";spillE@{int(cs.ub_kibs[0])}KiB={cs.spill_energy[0]:.2e}")
    _save("connectivity", out)


def graph_quick():
    """--quick smoke: reduced-grid capacity sweep, numpy vs Pallas backend
    wall-clock, written to BENCH_graph.json so the perf trajectory of the
    graph subsystem accumulates in CI."""
    from repro.core.dse import capacity_sweep, grid_axes
    from repro.graph import build_graph
    g = build_graph("resnet152")
    hs = grid_axes()[::4]                      # 8x8 = 64 configs
    cs_np, us_np = _timeit(lambda: capacity_sweep(g, hs=hs, ws=hs,
                                                  backend="numpy"))
    _emit("graph_capacity_sweep_numpy", us_np,
          f"peak={cs_np.peak_bits / 8 / 2**20:.2f}MiB")
    cs_pl, us_pl = _timeit(lambda: capacity_sweep(g, hs=hs, ws=hs,
                                                  backend="pallas"))
    rel = (np.abs(cs_pl.base.energy - cs_np.base.energy)
           / (np.abs(cs_np.base.energy) + 1.0))
    _emit("graph_capacity_sweep_pallas", us_pl,
          f"max_rel_vs_numpy={float(rel.max()):.2e}"
          f";speedup={us_np / us_pl:.2f}x")
    _save("BENCH_graph", {
        "model": "resnet152", "configs": int(cs_np.base.energy.size),
        "ub_kibs": cs_np.ub_kibs.tolist(),
        "numpy_us_per_call": us_np, "pallas_us_per_call": us_pl,
        "speedup_numpy_over_pallas": us_np / us_pl,
        "peak_occupancy_mib": cs_np.peak_bits / 8 / 2 ** 20,
        "spill_energy": cs_np.spill_energy.tolist(),
        "max_rel_backend_err": float(rel.max()),
    })


def ablations():
    from repro.core import get_workloads, grid_sweep
    wl = get_workloads("resnet152")
    for name, kw in (
            ("eq1_strict", {}),
            ("act_reread", {"act_reread": True}),
            ("idle_pe", {"idle_pe_energy": 0.2}),
            ("load_hops", {"count_weight_load_hops": True})):
        s, us = _timeit(lambda k=kw: grid_sweep(wl, **k), n=1)
        be = np.unravel_index(np.argmin(s.energy), s.energy.shape)
        _emit(f"ablation_{name}", us,
              f"minE=({s.hs[be[0]]}x{s.ws[be[1]]})")


def future_work():
    """Paper §6 future work: output-stationary variant + multi-array."""
    from repro.core import get_workloads
    from repro.core.dataflows import analyze_gemm_multi, analyze_gemm_os
    from repro.core.systolic import analyze_network, analyze_gemm
    import time as _t
    wl = get_workloads("resnet152")
    t0 = _t.perf_counter()
    ws = analyze_network(wl, 128, 128)
    os_cyc = os_en = 0.0
    for (M, K, N, g, rep) in wl:
        m = analyze_gemm_os(M, K, N, 128, 128, groups=g * rep)
        os_cyc += float(m.cycles)
        os_en += float(m.energy)
    us = (_t.perf_counter() - t0) * 1e6
    _emit("future_os_vs_ws_resnet152_128x128", us,
          f"cycles_os/ws={os_cyc/float(ws.cycles):.3f}"
          f";energy_os/ws={os_en/float(ws.energy):.3f}")
    one = analyze_gemm(12544, 1152, 2048, 128, 128)
    for P in (2, 4, 8):
        m = analyze_gemm_multi(12544, 1152, 2048, 128, 128, n_arrays=P)
        _emit(f"future_multi_array_P{P}", 0.0,
              f"speedup={float(one.cycles)/float(m.cycles):.2f}"
              f";energy_x={float(m.energy)/float(one.energy):.2f}")


def backends():
    """Same 961-config sweep on both grid_sweep backends: numpy float64 vs
    the fused Pallas kernel (Mosaic on TPU; interpret mode on CPU, where the
    jit-cached call is the relevant number)."""
    from repro.core import get_workloads, grid_sweep
    wl = get_workloads("resnet152")
    s_np, us_np = _timeit(lambda: grid_sweep(wl, backend="numpy"))
    _emit("backend_numpy_961cfg", us_np, "float64")
    s_pl, us_pl = _timeit(lambda: grid_sweep(wl, backend="pallas"))
    rel = np.abs(s_pl.energy - s_np.energy) / (np.abs(s_np.energy) + 1.0)
    _emit("backend_pallas_961cfg", us_pl,
          f"max_rel_vs_numpy={float(rel.max()):.2e}"
          f";speedup={us_np / us_pl:.2f}x")


def precision():
    """Bitwidth DSE (ArrayFlex-style): (h, w, act_bits, weight_bits) design
    points with bit-normalized energy and bits/cycle UB bandwidth."""
    from repro.core import get_workloads, precision_sweep
    out = {}
    for model in ("resnet152", "mobilenetv3_large"):
        wl = get_workloads(model)
        recs, us = _timeit(
            lambda w=wl: precision_sweep(w, bit_widths=(4, 8, 16)), n=1)
        e8 = next(r for r in recs
                  if r["act_bits"] == 8 and r["weight_bits"] == 8)
        e4 = next(r for r in recs
                  if r["act_bits"] == 4 and r["weight_bits"] == 4)
        e16 = next(r for r in recs
                   if r["act_bits"] == 16 and r["weight_bits"] == 16)
        _emit(f"precision_{model}_9pt", us,
              f"bestE_a4w4=({e4['best_h']}x{e4['best_w']})"
              f";E4/E8={e4['min_energy'] / e8['min_energy']:.3f}"
              f";E16/E8={e16['min_energy'] / e8['min_energy']:.3f}"
              f";bw_bits_a8w8={e8['ub_bw_bits_at_best']:.0f}")
        out[model] = [{k: v for k, v in r.items() if k != "sweep"}
                      for r in recs]
    _save("precision", out)


def kernels():
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.core.cnn_zoo import get_workloads
    kb = ops.kernel_backend()
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    for sched in ("ws", "os"):
        _, us = _timeit(
            lambda s=sched: ops.matmul(a, w,
                                       schedule=s).block_until_ready(),
            n=1)
        _emit(f"kernel_ws_matmul_{sched}_{kb}", us, "256x256x256")
    layers = np.asarray(get_workloads("alexnet"), np.float32)
    cfgs = np.stack(np.meshgrid(np.arange(16, 144, 8), np.arange(16, 144, 8),
                                indexing="ij"), -1).reshape(-1, 2)[:256]
    _, us = _timeit(
        lambda: ops.sweep(cfgs, layers).block_until_ready(), n=1)
    _emit(f"kernel_dse_eval_{kb}", us,
          f"{len(cfgs)}cfgs_x_{len(layers)}layers")


def search_bench(quick: bool = False):
    """Device-resident search probes, written to BENCH_search.json:

      * the FULL 10-arch x DEFAULT_HW SLO capacity sweep through the
        lockstep batched bisection vs the per-point sequential search —
        identical max-QPS tables required, speedup is the tentpole
        perf-trajectory number (acceptance: >= 10x on one CPU host);
      * the on-device (jnp, single-jit) NSGA-2 vs the per-generation
        numpy oracle — bitwise-identical frontiers required;
      * the gradient design-point refiner: one device dispatch for the
        whole descent, a handful of exact re-evaluations, improvement
        over a mid-grid seed.
    """
    from repro.core import get_workloads
    from repro.core.dse import slo_capacity_sweep
    from repro.core.search import nsga2_device, refine_design_point
    from repro.core.systolic import analyze_network
    from repro.traffic import SLO, TrafficModel, build_cost_tables

    # 1. batched vs sequential bisection — full lattice in BOTH modes:
    # the speedup claim is about the production sweep, not a smoke size
    ts = build_cost_tables(backend="numpy")
    tm = TrafficModel()
    slo = SLO(ttft_s=2.0, tpot_s=0.1)
    kw = dict(n_requests=1200, seed=0, tables=ts)
    bat, us_bat = _timeit(
        lambda: slo_capacity_sweep(tm, slo, search="batched", **kw), n=1)
    seq, us_seq = _timeit(
        lambda: slo_capacity_sweep(tm, slo, search="sequential", **kw), n=1)
    identical = bool(np.array_equal(seq.max_qps, bat.max_qps))
    n_points = int(np.prod(seq.max_qps.shape))
    _emit("search_bisect_batched", us_bat,
          f"{n_points}lanes;identical={identical}")
    _emit("search_bisect_sequential", us_seq,
          f"batched_speedup={us_seq / us_bat:.1f}x")

    # 2. on-device NSGA-2 vs the numpy oracle (bitwise)
    wls = list(get_workloads("alexnet"))

    def eval_fn(pop):
        h = pop[:, 0].astype(np.float64)
        w = pop[:, 1].astype(np.float64)
        m = analyze_network(wls, h, w)
        return np.stack([np.asarray(m.energy), np.asarray(m.cycles)], 1)

    pop, gens = (32, 12) if quick else (64, 40)
    bounds = ((16, 256), (16, 256))
    (Pj, Fj), us_j = _timeit(
        lambda: nsga2_device(eval_fn, bounds, pop=pop, gens=gens), n=1)
    (Pn, Fn), us_n = _timeit(
        lambda: nsga2_device(eval_fn, bounds, pop=pop, gens=gens,
                             backend="numpy"), n=1)
    match = bool(np.array_equal(Pj, Pn) and np.array_equal(Fj, Fn))
    _emit("search_nsga2_jnp", us_j,
          f"pop={pop};gens={gens};front={len(Pj)};oracle_match={match}")
    _emit("search_nsga2_numpy", us_n, f"jnp_vs_numpy={us_n / us_j:.2f}x")

    # 3. gradient refiner: whole descent in ONE device dispatch
    steps = 16 if quick else 48
    ref, us_r = _timeit(
        lambda: refine_design_point(wls, (128, 128), steps=steps), n=1)
    _emit("search_refiner", us_r,
          f"({ref['seed'][0]},{ref['seed'][1]})->({ref['h']},{ref['w']})"
          f";improved={ref['improved']}"
          f";dispatches={ref['device_dispatches']}"
          f";exact_evals={ref['exact_evals']}")
    _save("BENCH_search", {
        "bisect_lanes": n_points,
        "bisect_sequential_us": us_seq, "bisect_batched_us": us_bat,
        "bisect_speedup": us_seq / us_bat, "bisect_identical": identical,
        "nsga2_pop": pop, "nsga2_gens": gens,
        "nsga2_jnp_us": us_j, "nsga2_numpy_us": us_n,
        "nsga2_oracle_match": match, "nsga2_front": len(Pj),
        "refiner_seed": list(ref["seed"]),
        "refiner_point": [ref["h"], ref["w"]],
        "refiner_improved": ref["improved"],
        "refiner_objective": ref["objective"],
        "refiner_seed_objective": ref["seed_objective"],
        "refiner_device_dispatches": ref["device_dispatches"],
        "refiner_exact_evals": ref["exact_evals"],
        "refiner_steps": ref["steps"],
    })


def obs_bench(quick: bool = False):
    """Observability probes, written to BENCH_obs.json:

      * measured instrumentation overhead with tracing DISABLED on the
        1M-request replay (the same replay traffic_bench times): runs
        with no tracer attached vs a disabled Tracer attached,
        interleaved, min-of-reps — CI fails the stage above 3%;
      * a seeded two-server disaggregated fleet replay traced on the
        simulation clock, exported twice to Perfetto trace-event JSON:
        must validate (monotone per-track timestamps, balanced spans,
        one track per server/pool) and be byte-identical across runs
        (the sample trace is the CI artifact);
      * conservation-gated cost attribution: the seeded single-server
        and disaggregated-fleet replays re-run with `breakdown=True`;
        every CostBreakdown must pass `check_conservation()` (components
        sum to the default path's totals at 1e-9) and the deterministic
        attribution report is written next to the trace artifact;
      * the counter totals this stage accumulated (the registry report).
    """
    from repro import obs
    from repro.fleet import FleetSimConfig, FleetTables, simulate_fleet
    from repro.traffic import SimConfig, TrafficModel, build_cost_tables
    from repro.traffic.slo import SLO, summarize

    before = obs.metrics().snapshot()
    # stage purity: main() resets the registry at every stage boundary,
    # so the counter report below is THIS stage's accounting alone
    assert not before, (
        "obs stage expects a clean metrics registry (stage purity); "
        f"leaked counters: {sorted(before)[:5]}")

    # 1. tracing-disabled overhead on the 1M-request replay
    from repro.traffic import simulate
    ts = build_cost_tables(["xlstm-125m"], [(128, 128)], backend="numpy")
    tab = ts.table("xlstm-125m", 128, 128)
    tm = TrafficModel(rate_qps=200.0, prompt_median=256, output_median=48)
    n_replay = 1_000_000
    trace = tm.sample(n_replay, seed=0)
    cfg_base = SimConfig(slots=64)                       # no tracer field set
    cfg_off = SimConfig(slots=64,
                        tracer=obs.Tracer(enabled=False, clock="sim"))
    reps = 2 if quick else 3
    base_s, off_s = [], []
    simulate(tab, trace, cfg_base)                       # warm caches once
    for _ in range(reps):                                # interleave reps so
        base_s.append(simulate(tab, trace, cfg_base)     # drift hits both
                      .wall_seconds)
        off_s.append(simulate(tab, trace, cfg_off).wall_seconds)
    t_base, t_off = min(base_s), min(off_s)
    overhead = (t_off - t_base) / t_base
    _emit("obs_disabled_overhead_1m", t_off * 1e6,
          f"base={t_base:.2f}s;off={t_off:.2f}s;overhead={overhead:+.2%}")

    # 2. seeded two-server disagg traced replay -> deterministic export
    ts2 = build_cost_tables(["xlstm-125m"], [(64, 64), (128, 128)],
                            backend="numpy")
    fleet = FleetTables(prefill=[ts2.table("xlstm-125m", 128, 128)],
                        decode=[ts2.table("xlstm-125m", 64, 64),
                                ts2.table("xlstm-125m", 128, 128)])
    tm2 = TrafficModel(rate_qps=60.0, prompt_median=256, output_median=32)
    trace2 = tm2.sample(400, seed=7)
    blobs, tracers, fres = [], [], None
    for _ in range(2):
        tr = obs.Tracer(clock="sim")
        fres = simulate_fleet(
            fleet, trace2,
            FleetSimConfig(server=SimConfig(slots=16, ub_kib=4096.0,
                                            tracer=tr)))
        summ = summarize(fres, SLO(ttft_s=2.0, tpot_s=0.15))
        blobs.append(obs.trace_json(
            tr, metadata={"seed": 7, "requests": len(trace2),
                          "ttft_hist": summ["ttft_hist"],
                          "tpot_hist": summ["tpot_hist"]}))
        tracers.append(tr)
    problems = obs.validate_trace(json.loads(blobs[0]))
    deterministic = blobs[0] == blobs[1]
    tracks = tracers[0].tracks()
    trace_path = os.path.join(RESULTS, "trace_replay_sample.perfetto.json")
    os.makedirs(RESULTS, exist_ok=True)
    with open(trace_path, "w") as f:
        f.write(blobs[0])
    _emit("obs_disagg_trace_export", 0.0,
          f"events={len(tracers[0])};tracks={len(tracks)}"
          f";valid={not problems};deterministic={deterministic}")

    # 3. conservation-gated cost attribution on the seeded replays:
    # the same single-server table and disagg fleet, breakdown=True —
    # components must sum back to the untouched totals at 1e-9
    from repro.obs.attribution import ConservationError
    from repro.obs.report import (attribution_report, report_json,
                                  write_report)
    r_bd = simulate(tab, tm.sample(2000, seed=7),
                    SimConfig(slots=64, breakdown=True))
    f_bd = simulate_fleet(
        fleet, trace2,
        FleetSimConfig(server=SimConfig(slots=16, ub_kib=4096.0,
                                        breakdown=True)))
    bds = {"single_server_replay": r_bd.breakdown,
           "disagg_fleet_replay": f_bd.breakdown}
    try:
        for b in bds.values():
            b.check_conservation()
        conservation_ok = True
    except ConservationError:
        conservation_ok = False
    worst_rel = max(b.max_rel_err() for b in bds.values())
    report_path = os.path.join(RESULTS, "attribution_report.md")
    write_report(report_path, attribution_report(bds))
    write_report(os.path.join(RESULTS, "attribution_report.json"),
                 report_json({k: b.to_dict() for k, b in bds.items()}))
    _emit("obs_attribution_conservation", 0.0,
          f"ok={conservation_ok};max_rel_err={worst_rel:.2e}"
          f";link_ship_J={f_bd.breakdown.component('energy', 'link_ship'):.3e}")

    # 4. counter totals accumulated by this stage
    delta = obs.metrics().delta(before)
    _emit("obs_counters", 0.0,
          f"sim.events={delta.get('sim.events', 0):.0f}"
          f";sim.table_lookups={delta.get('sim.table_lookups', 0):.0f}"
          f";fleet.kv_ships={delta.get('fleet.kv_ships', 0):.0f}")
    _save("BENCH_obs", {
        "replay_requests": n_replay,
        "replay_reps": reps,
        "replay_base_seconds": t_base,
        "replay_disabled_tracer_seconds": t_off,
        "disabled_overhead_frac": overhead,
        "trace_requests": len(trace2),
        "trace_events": len(tracers[0]),
        "trace_tracks": tracks,
        "trace_valid": not problems,
        "trace_problems": problems[:10],
        "trace_deterministic": deterministic,
        "trace_path": os.path.relpath(trace_path,
                                      os.path.join(RESULTS, "..", "..")),
        "conservation_ok": conservation_ok,
        "conservation_max_rel_err": worst_rel,
        "attribution_report": os.path.relpath(
            report_path, os.path.join(RESULTS, "..", "..")),
        "counters": {k: delta[k] for k in sorted(delta)},
        "registry": obs.metrics().summarize(),
    })


def windowed_bench(quick: bool = False):
    """Windowed-telemetry & SLO burn-rate probes, written to
    BENCH_windowed.json:

      * windowing overhead on the 1M-request replay (the same replay the
        traffic/obs stages time): windows off vs `SimConfig.windows` on,
        interleaved, min-of-reps — CI fails the stage above 5%;
      * the exact-merge identity on that replay: per-window TTFT/TPOT
        histograms merged across all windows must reproduce the
        whole-run summarize() histograms bucket-for-bucket;
      * the canonical seeded burst replay (the tests' golden scenario):
        the multi-window burn-rate alert sequence run twice — identical
        alert transitions and a byte-identical, validate_trace-clean
        Perfetto export with burn-rate / error-budget counter tracks;
      * the peak-burn story: the diurnal replay that PASSES its
        day-average SLO while burning the budget at peak — the verdict
        whole-run means cannot give.
    """
    from repro import obs
    from repro.obs.windowed import (SLOMonitor, WindowConfig,
                                    worst_window_goodput)
    from repro.traffic import (SimConfig, TrafficModel, build_cost_tables,
                               simulate)
    from repro.traffic.slo import summarize
    from repro.traffic.workload import RateSchedule

    # 1. windowing overhead on the 1M-request replay
    ts = build_cost_tables(["xlstm-125m"], [(128, 128)], backend="numpy")
    tab = ts.table("xlstm-125m", 128, 128)
    tm = TrafficModel(rate_qps=200.0, prompt_median=256, output_median=48)
    n_replay = 1_000_000
    trace = tm.sample(n_replay, seed=0)
    cfg_off = SimConfig(slots=64)
    cfg_on = SimConfig(slots=64, windows=WindowConfig(window_s=60.0))
    # the true cost is ~2-4% (bucket-edge bool per event + one fused
    # multiply-add per decode step + the vectorized post-hoc binning);
    # host noise between reps is larger than that, so min-of-reps needs
    # enough reps for both arms to catch a quiet slice
    reps = 4 if quick else 6
    res_on = simulate(tab, trace, cfg_on)                # warm caches once
    off_s, on_s = [], []
    for i in range(reps):
        # interleave AND alternate the order each rep: min-of-reps then
        # cancels both random noise and monotone host-load drift
        pair = [(cfg_off, off_s), (cfg_on, on_s)]
        for cfg_i, acc in pair[::-1] if i % 2 else pair:
            acc.append(simulate(tab, trace, cfg_i).wall_seconds)
    t_off, t_on = min(off_s), min(on_s)
    overhead = (t_on - t_off) / t_off
    _emit("windowed_overhead_1m", t_on * 1e6,
          f"off={t_off:.2f}s;on={t_on:.2f}s;overhead={overhead:+.2%}"
          f";windows={res_on.windowed.n_windows}")

    # 2. the exact-merge identity on the same 1M replay
    summ = summarize(res_on)
    merge_ok = all(
        res_on.windowed.merged_histogram(k).counts
        == summ[f"{k}_hist"]["counts"] for k in ("ttft", "tpot"))
    _emit("windowed_merge_identity_1m", 0.0,
          f"merged_eq_whole_run={merge_ok}"
          f";completions={int(res_on.windowed.completions.sum())}")

    # 3. canonical seeded burst replay: deterministic alert sequence +
    # byte-identical validate_trace-clean Perfetto export (the same
    # scenario tests/fixtures/windowed_alerts_golden.json pins)
    sched = RateSchedule(base_qps=1.5, bursts=((120.0, 40.0, 2.5),))
    btm = TrafficModel(arrival="scheduled", schedule=sched, rate_qps=1.5,
                       prompt_median=256, prompt_range=(16, 2048),
                       output_median=48, output_range=(1, 512))
    btrace = btm.sample(1500, seed=7)
    btab = build_cost_tables(["h2o-danube-3-4b"], [(128, 128)],
                             backend="numpy").table("h2o-danube-3-4b",
                                                    128, 128)
    wcfg = WindowConfig(window_s=30.0, slo_ttft_s=2.0, slo_tpot_s=0.2)
    mon = SLOMonitor(budget=0.02)
    alert_runs, blobs = [], []
    for _ in range(2):
        r = simulate(btab, btrace, SimConfig(slots=16, windows=wcfg))
        m = mon.evaluate(r.windowed)
        tr = obs.Tracer(clock="sim")
        m.emit(tr, track="slo")
        blobs.append(obs.trace_json(tr, metadata={"seed": 7,
                                                  "requests": len(btrace)}))
        alert_runs.append(m)
    alerts = [a.to_dict() for a in alert_runs[0].alerts]
    alerts_deterministic = (
        alerts == [a.to_dict() for a in alert_runs[1].alerts])
    export_deterministic = blobs[0] == blobs[1]
    problems = obs.validate_trace(json.loads(blobs[0]))
    trace_path = os.path.join(RESULTS, "burst_replay_slo.perfetto.json")
    os.makedirs(RESULTS, exist_ok=True)
    with open(trace_path, "w") as f:
        f.write(blobs[0])
    _emit("windowed_burst_alerts", 0.0,
          f"alerts={len(alerts)};deterministic={alerts_deterministic}"
          f";export_deterministic={export_deterministic}"
          f";valid={not problems}"
          f";budget_consumed={alert_runs[0].final_budget_consumed:.1f}x")

    # 4. the peak-burn story: the diurnal replay of
    # examples/diurnal_monitoring.py — day-average SLO PASSES while the
    # flash crowd burns the budget at peak
    dsched = RateSchedule(base_qps=1.0, diurnal_amplitude=0.3,
                          diurnal_period_s=600.0,
                          bursts=((120.0, 12.0, 3.0),))
    dtm = TrafficModel(arrival="scheduled", schedule=dsched, rate_qps=1.0,
                       prompt_median=256, prompt_range=(16, 2048),
                       output_median=48, output_range=(1, 512))
    dres = simulate(btab, dtm.sample(1500, seed=7),
                    SimConfig(slots=16, windows=wcfg))
    dmon = SLOMonitor(budget=0.05).evaluate(dres.windowed)
    done = float(dres.windowed.completions.sum())
    day_bad = (done - float(dres.windowed.good.sum())) / max(done, 1.0)
    day_ok = day_bad <= 0.05
    peak_burn = day_ok and dmon.fired
    worst = worst_window_goodput(dres.windowed)
    _emit("windowed_peak_burn_flag", 0.0,
          f"day_bad={day_bad:.4f};day_avg_pass={day_ok}"
          f";fired={dmon.fired};peak_burn_flag={peak_burn}"
          f";worst_window_t0={worst['t0_s']:.0f}s")
    _save("BENCH_windowed", {
        "replay_requests": n_replay,
        "replay_reps": reps,
        "replay_windows": int(res_on.windowed.n_windows),
        "replay_off_seconds": t_off,
        "replay_windowed_seconds": t_on,
        "windowed_overhead_frac": overhead,
        "merged_eq_whole_run": merge_ok,
        "burst_alerts": alerts,
        "burst_alerts_deterministic": alerts_deterministic,
        "burst_export_deterministic": export_deterministic,
        "burst_trace_valid": not problems,
        "burst_trace_problems": problems[:10],
        "burst_budget_consumed": alert_runs[0].final_budget_consumed,
        "burst_trace_path": os.path.relpath(
            trace_path, os.path.join(RESULTS, "..", "..")),
        "peak_burn_day_bad_frac": day_bad,
        "peak_burn_day_avg_pass": day_ok,
        "peak_burn_fired": dmon.fired,
        "peak_burn_flag": peak_burn,
        "peak_burn_budget_consumed": dmon.final_budget_consumed,
        "peak_burn_worst_window": worst,
    })


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="reduced graph capacity-sweep + serving-"
                             "scenario + traffic + fleet smoke only "
                             "(writes BENCH_graph.json, "
                             "BENCH_scenarios.json, BENCH_traffic.json, "
                             "BENCH_fleet.json, BENCH_search.json, "
                             "BENCH_obs.json and BENCH_windowed.json)")
    args = parser.parse_args()
    from repro.kernels.ops import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    if args.quick:
        _stage(graph_quick)
        _stage(scenarios_bench, quick=True)
        _stage(traffic_bench, quick=True)
        _stage(kv_bench, quick=True)
        _stage(fleet_bench, quick=True)
        _stage(search_bench, quick=True)
        _stage(obs_bench, quick=True)
        _stage(windowed_bench, quick=True)
        return
    _stage(fig2_resnet_heatmap)
    _stage(fig3_pareto)
    _stage(fig4_model_heatmaps)
    _stage(fig5_robust)
    _stage(fig6_equal_pe)
    _stage(lm_architectures)
    _stage(scenarios_bench)
    _stage(traffic_bench)
    _stage(kv_bench)
    _stage(fleet_bench)
    _stage(search_bench)
    _stage(obs_bench)
    _stage(windowed_bench)
    _stage(connectivity)
    _stage(ablations)
    _stage(future_work)
    _stage(backends)
    _stage(precision)
    _stage(kernels)
    _stage(graph_quick)


if __name__ == "__main__":
    main()
