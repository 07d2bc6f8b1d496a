"""Query kind `slo_capacity`: what rate each design point sustains under
an SLO.

Each query takes `points_per_query` (h, w) points of the grid and a
trace seed from the mix's fixed pool, builds their cost tables in one
fused kernel dispatch
(`traffic.build_cost_tables`), and bisects each point's highest rate
that meets the SLO (`core.dse.slo_capacity_sweep(tables=..., search=
"auto")`). The answer is the cost tables and, per point, the rate and
the summary of the probe that set it.

The check rebuilds every kept table from the closed forms and replays
each kept point on the reference engine twice: at its answered rate,
where the summary the program states must be what that rate gives and
the SLO must hold, and one bisection step above it, where the SLO must
fail. A point answered 0 is replayed once, at the lowest rate the
bisection probes, where the SLO must fail.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.compare import rel_gap, widest_gap
from bench.queries.grid_sweep import grid_hw
from bench.reference import serving

TABLE_KEYS = ("decode_cycles", "decode_energy", "decode_macs",
              "prefill_cycles", "prefill_energy")
# what the check compares of each point's stated summary, under the names
# of the compared numbers. p99 TPOT and energy per token aggregate over
# the whole replay; the medians and TTFT are left out, since a cost-table
# value one float32 rounding away can reorder an admission against a
# decode step and move one request's latency by a whole step.
SUMMARY_KEYS = {"tpot_p99_s": "tpot_gap", "energy_per_token": "energy_gap"}
# the answer itself: how far the reference misses the SLO at the answered
# rate (too high an answer), and how far it still meets it one bisection
# step above (too low an answer, as from a search that stops early); each
# a share of the SLO's targets, 0 where the answer is right
QPS_KEYS = ("qps_high_gap", "qps_low_gap")


def prepare(cfg, mix, lower):
    from repro.configs.base import get_config
    from repro.traffic import SLO, SimConfig, TrafficModel
    arch = cfg["program"]["arch"]
    prog = get_config(arch)
    stated = {"hidden_size": prog.d_model, "intermediate_size": prog.d_ff,
              "num_attention_heads": prog.num_heads,
              "num_key_value_heads": prog.num_kv_heads,
              "num_hidden_layers": prog.num_layers,
              "num_experts": prog.num_experts,
              "num_experts_per_tok": prog.experts_per_token,
              "vocab_size": prog.vocab_size}
    differ = {k: (v, cfg[k]) for k, v in stated.items() if cfg[k] != v}
    if differ:
        raise SystemExit(f"program config {arch} differs from the file: "
                         f"{differ}")
    tr = mix["traffic"]
    tm = TrafficModel(
        arrival=tr["arrival"], rate_qps=tr["rate_qps"],
        burst_ratio=tr["burst_ratio"], mean_sojourn_s=tr["mean_sojourn_s"],
        prompt_median=tr["prompt_median"], prompt_sigma=tr["prompt_sigma"],
        prompt_range=tuple(tr["prompt_range"]),
        output_median=tr["output_median"], output_sigma=tr["output_sigma"],
        output_range=tuple(tr["output_range"]))
    _, hw = grid_hw(mix["grid"])
    from repro.traffic import native
    t = time.perf_counter()
    native.available()            # the C replay engine's one-off build
    build_s = time.perf_counter() - t
    state = {"setup": {"native_build_s": build_s}, "cfg": cfg, "mix": mix,
             "lower": lower, "arch": arch, "grid": hw, "traffic": tm,
             "slo": SLO(mix["slo"]["ttft_s"], mix["slo"]["tpot_s"],
                        mix["slo"]["pct"]),
             "sim": SimConfig(slots=mix["sim"]["slots"],
                              clock_hz=mix["sim"]["clock_hz"])}
    state["pool"] = _pool(state)
    state["rows"] = sum(len(lower(cfg, s)) for s in
                        serving.lattice_shapes(mix["lattice"]))
    return state


def _pool(state):
    """The mix's fixed set of queries: passes over the grid, the grid
    sorted by PE count and cut into `points_per_query` strata, each
    stratum shuffled, one point of each stratum to a query, each query
    with its own trace seed. Drawn from the mix's `pool.seed`, so every
    run does the same work; the run's seed only orders it."""
    k = state["mix"]["points_per_query"]
    g = state["grid"]
    rng = np.random.default_rng(state["mix"]["pool"]["seed"])
    by_pe = g[np.lexsort((g[:, 0], g[:, 0] * g[:, 1]))]
    n = len(g) // k
    pool = []
    while len(pool) < state["mix"]["pool"]["queries"]:
        strata = [rng.permutation(by_pe[i * n:(i + 1) * n])
                  for i in range(k)]
        pool += [(tuple((int(s[j, 0]), int(s[j, 1])) for s in strata),
                  int(rng.integers(2 ** 31))) for j in range(n)]
    return pool[:state["mix"]["pool"]["queries"]]


def variants(state):
    return [state["pool"][0]]


def draw(state, rng, queue):
    """The next query of a seeded order of the pool."""
    if not queue:
        queue[:] = [state["pool"][i]
                    for i in rng.permutation(len(state["pool"]))]
    return queue.pop()


def _tables(state, hw):
    from repro.traffic import build_cost_tables
    lat = state["mix"]["lattice"]
    return build_cost_tables([state["arch"]], hw, slot_lattice=lat["slots"],
                             kv_lattice=lat["kv"],
                             prompt_lattice=lat["prompt"])


def _search(state, hw, seed, tables):
    from repro.core.dse import slo_capacity_sweep
    return slo_capacity_sweep(state["traffic"], state["slo"],
                              archs=[state["arch"]], hw=hw, sim=state["sim"],
                              n_requests=state["mix"]["n_requests"],
                              seed=seed, tables=tables,
                              search=state["mix"]["search"])


def run(state, params, span):
    hw, seed = params
    with span("table_build"):
        tables = _tables(state, hw)
    with span("search"):
        return tables, _search(state, hw, seed, tables)


def points(state, params):
    return len(params[0])


def elements(state, params):
    return len(params[0]) * state["rows"]


def keep(state, params, answer):
    tables, res = answer
    hw, _ = params
    out = []
    for c, (h, w) in enumerate(hw):
        t = tables.table(state["arch"], h, w)
        out.append({"table": {k: np.asarray(getattr(t, k), np.float64)
                              for k in TABLE_KEYS},
                    "qps": float(res.max_qps[0, c]),
                    "saturated": bool(
                        res.summaries[0][c]["saturated_at_bracket"]),
                    "summary": {k: float(res.summaries[0][c][k])
                                for k in SUMMARY_KEYS}})
    return params, out


def control(state, params):
    """The timed path with the reference's tables, computed in bfloat16,
    in place of the kernel's."""
    import ml_dtypes
    hw, seed = params
    tables = _tables(state, hw)
    ref = serving.tables(state["lower"], state["cfg"],
                         state["mix"]["lattice"], hw,
                         dtype=ml_dtypes.bfloat16)
    for key, t in tables.tables.items():
        r = ref[(t.h, t.w)]
        tables.tables[key] = dataclasses.replace(
            t, **{k: r[k].tolist() for k in TABLE_KEYS})
    return keep(state, params, (tables, _search(state, hw, seed, tables)))


def check(state, kept):
    mix = state["mix"]
    tr, slo, sim = mix["traffic"], mix["slo"], mix["sim"]
    n = mix["n_requests"]

    def replay(tab, rate, seed):
        return serving.summary(*serving.replay(
            tab, *serving.trace(tr, rate, n, seed), sim["slots"],
            sim["clock_hz"]), slo)

    gaps = dict.fromkeys(["table_gap", *SUMMARY_KEYS.values(), *QPS_KEYS],
                         0.0)
    for (hw, seed), answers in kept:
        ref = serving.tables(state["lower"], state["cfg"], mix["lattice"],
                             hw)
        for (h, w), ans in zip(hw, answers):
            tab = ref[(h, w)]
            gaps["table_gap"] = max([gaps["table_gap"]] + [
                widest_gap(ans["table"][k], tab[k]) for k in TABLE_KEYS])
            lowest = serving.lowest_probe_qps(tab, tr, sim["slots"],
                                              sim["clock_hz"])
            q = ans["qps"]
            s = replay(tab, q or lowest, seed)
            for k, name in SUMMARY_KEYS.items():
                gaps[name] = max(gaps[name], rel_gap(ans["summary"][k],
                                                     s[k]))
            if q:
                gaps["qps_high_gap"] = max(
                    gaps["qps_high_gap"],
                    min(serving.slo_load(s, n, slo) - 1.0, 1.0))
                if ans["saturated"]:
                    continue
                above = replay(tab, q + serving.bracket_step(
                    q, lowest, mix["bisect_iters"]), seed)
            else:
                above = s
            gaps["qps_low_gap"] = max(
                gaps["qps_low_gap"], 1.0 - serving.slo_load(above, n, slo))
    return gaps
