"""Conservation-gated cost attribution, every layer: closed-form GEMMs
(all three dataflows, hypothesis-random points), graph capacity sweeps,
seeded traffic replays (prefix cache + speculative decoding included),
disaggregated / pipelined fleet replays, and the DSE winner explanation —
components must sum back to the DEFAULT path's totals at 1e-9, and the
default path itself must stay byte-identical to the pinned goldens."""
import dataclasses
import functools
import json
import os

import numpy as np
import pytest

from repro.core.dse import capacity_sweep, explain_winner, slo_capacity_sweep
from repro.core.model_core import Precision, analyze_gemm_core
from repro.fleet import (FleetSimConfig, FleetTables, LinkModel,
                         build_stage_tables, partition_server_table,
                         simulate_fleet)
from repro.graph import build_graph
from repro.graph.occupancy import analyze_graph
from repro.obs import metrics, reset_metrics
from repro.obs.attribution import (COMPONENTS, ConservationError,
                                   CostBreakdown, gemm_breakdown,
                                   network_breakdown)
from repro.obs.export import validate_trace
from repro.obs.metrics import Histogram
from repro.obs.report import (attribution_report, report_json, winner_report,
                              write_report)
from repro.traffic import (SLO, KVReuseConfig, SimConfig, SpecDecodeConfig,
                           TrafficModel, build_cost_tables, simulate)
from repro.traffic.sim import TPOT_PARTS, TTFT_PARTS

from _hyp import given, settings, st

ARCH = "h2o-danube-3-4b"
DRAFT = "xlstm-125m"
REL = 1e-9

TRAFFIC = TrafficModel(rate_qps=1.5, prompt_median=256,
                       prompt_range=(16, 2048), output_median=48,
                       output_range=(1, 512))
KV = KVReuseConfig(share=0.6, prefix_len=512, n_prefixes=4, cache_mib=2048.0)
SPEC = SpecDecodeConfig(draft_arch=DRAFT, k=4, acceptance=0.7)


@functools.lru_cache(maxsize=None)
def _table(arch=ARCH, h=128, w=128, spec=None):
    return build_cost_tables(archs=sorted({arch, spec.draft_arch})
                             if spec else [arch],
                             hw=((h, w),), backend="numpy",
                             spec=spec).table(arch, h, w)


# ------------------------------------------------ CostBreakdown contract --

def test_breakdown_rejects_unknown_components():
    with pytest.raises(ValueError, match="unknown"):
        CostBreakdown(1.0, 1.0, cycles={"warp_drive": 1.0})


def test_conservation_error_raises_and_chains():
    good = CostBreakdown(2.0, 3.0, cycles={"compute": 2.0},
                         energy={"compute": 1.0, "queueing": 2.0})
    assert good.check_conservation() is good
    bad = CostBreakdown(2.0, 3.0, cycles={"compute": 1.0})
    with pytest.raises(ConservationError, match="cycles"):
        bad.check_conservation()
    nan = CostBreakdown(2.0, 3.0, cycles={"compute": float("nan")})
    with pytest.raises(ConservationError):
        nan.check_conservation()


def test_breakdown_algebra_preserves_conservation():
    a = CostBreakdown(2.0, 4.0, cycles={"compute": 2.0},
                      energy={"compute": 3.0, "dram_spill": 1.0})
    b = CostBreakdown(1.0, 2.0, cycles={"compute": 0.5, "queueing": 0.5},
                      energy={"compute": 2.0})
    s = (a + b).check_conservation()
    assert s.component("cycles", "queueing") == 0.5
    assert s.component("energy", "compute") == 5.0
    s.scaled(1.0 / 3.0).check_conservation()
    d = a.delta(b)
    assert d["energy"]["dram_spill"] == 1.0
    assert a.dominant("energy") == "compute"


# -------------------------------------------- closed forms (Eq. 1 split) --

DATAFLOWS = ("ws", "os", "multi_array")


def _gemm_point(mi, ki, ni, hi, wi, bi):
    dims = (32, 96, 256, 1024)
    grid = (16, 64, 128, 224)
    bits = (4, 8, 16)
    return dict(M=dims[mi], K=dims[ki], N=dims[ni], h=grid[hi], w=grid[wi],
                precision=Precision(act_bits=bits[bi]))


@settings(max_examples=40, deadline=None)
@given(mi=st.integers(min_value=0, max_value=3),
       ki=st.integers(min_value=0, max_value=3),
       ni=st.integers(min_value=0, max_value=3),
       hi=st.integers(min_value=0, max_value=3),
       wi=st.integers(min_value=0, max_value=3),
       bi=st.integers(min_value=0, max_value=2),
       di=st.integers(min_value=0, max_value=2),
       idle=st.integers(min_value=0, max_value=1))
def test_gemm_breakdown_conserves_and_matches_default(mi, ki, ni, hi, wi,
                                                      bi, di, idle):
    """Random (dims, shape, bits, dataflow, idle-PE) points: components
    sum to the totals at 1e-9 AND the totals are bitwise the default
    (breakdown=False) path's."""
    p = _gemm_point(mi, ki, ni, hi, wi, bi)
    kw = dict(dataflow=DATAFLOWS[di], groups=2.0,
              idle_pe_energy=0.1 * idle, n_arrays=4,
              precision=p["precision"])
    b = gemm_breakdown(p["M"], p["K"], p["N"], p["h"], p["w"], **kw)
    b.check_conservation(REL)
    f = lambda x: np.asarray(x, np.float64)
    d0 = analyze_gemm_core(np, f(p["M"]), f(p["K"]), f(p["N"]), f(p["h"]),
                           f(p["w"]), **kw)
    assert float(b.total_cycles) == float(d0["cycles"])
    assert float(b.total_energy) == float(d0["energy"])


def test_default_metric_dict_has_no_breakdown_keys():
    """breakdown=False returns exactly the legacy keys (no accidental
    payload growth on the hot numpy/Pallas paths)."""
    f = lambda x: np.asarray(x, np.float64)
    d = analyze_gemm_core(np, f(64.0), f(64.0), f(64.0), f(16.0), f(16.0))
    assert not any(k.startswith(("cycles_", "energy_")) for k in d)


def test_network_breakdown_bitwise_vs_analyze_network():
    from repro.core import systolic
    g = build_graph("alexnet")
    wls = g.flatten()
    hs = np.arange(16.0, 129.0, 16.0)
    H, W = np.meshgrid(hs, hs, indexing="ij")
    b = network_breakdown(wls, H, W).check_conservation(REL)
    m = systolic.analyze_network(wls, H, W)
    assert np.array_equal(np.asarray(b.total_cycles), np.asarray(m.cycles))
    assert np.array_equal(np.asarray(b.total_energy), np.asarray(m.energy))
    with pytest.raises(ValueError, match="empty"):
        network_breakdown([], 16.0, 16.0)


# --------------------------------------------------- graph + capacity DSE --

def test_analyze_graph_breakdown_attributes_spill():
    g = build_graph("resnet152")
    tight, roomy = 128.0, 1 << 20
    mt = analyze_graph(g, 64.0, 64.0, ub_kib=tight, breakdown=True)
    mr = analyze_graph(g, 64.0, 64.0, ub_kib=roomy, breakdown=True)
    for m in (mt, mr):
        m.breakdown.check_conservation(REL)
        assert float(np.asarray(m.breakdown.total_energy)) == \
            pytest.approx(float(np.asarray(m.energy_total)), rel=REL)
    assert mt.breakdown.component("energy", "dram_spill") == mt.spill_energy
    assert mt.spill_energy > 0.0
    assert mr.breakdown.component("energy", "dram_spill") == 0.0
    assert analyze_graph(g, 64.0, 64.0, ub_kib=tight).breakdown is None


def test_capacity_sweep_breakdown_conserves_per_capacity():
    hs = np.arange(16, 65, 16)
    g = build_graph("alexnet")
    cs0 = capacity_sweep(g, hs=hs, ws=hs, backend="numpy")
    cs = capacity_sweep(g, hs=hs, ws=hs, backend="numpy", breakdown=True)
    assert cs0.breakdowns is None
    assert np.array_equal(cs0.energy_total, cs.energy_total)
    assert len(cs.breakdowns) == len(cs.ub_kibs)
    spills = []
    for u, b in enumerate(cs.breakdowns):
        b.check_conservation(REL)
        assert np.array_equal(np.asarray(b.total_energy),
                              cs.energy_total[u])
        spills.append(b.component("energy", "dram_spill"))
    assert spills[0] > 0.0 and spills == sorted(spills, reverse=True)


# ------------------------------------------------------ traffic replays --

SIM_CASES = {
    "prefill_first": (None, SimConfig(slots=16)),
    "chunked": (None, SimConfig(slots=16, policy="chunked", chunk=128)),
    "tight_ub": (None, SimConfig(slots=16, ub_kib=24 * 1024.0)),
    "prefix_cache": ("kv", SimConfig(slots=16,
                                     prefix_cache_mib=KV.cache_mib)),
    "spec_decode": ("spec", SimConfig(slots=16, spec=SPEC)),
    "combined": ("both", SimConfig(slots=16, spec=SPEC,
                                   prefix_cache_mib=KV.cache_mib)),
}


def _sim_case(name, n=800, seed=1234):
    kind, cfg = SIM_CASES[name]
    tm = KV.apply(TRAFFIC) if kind in ("kv", "both") else TRAFFIC
    tab = _table(ARCH, 128, 128, SPEC) if kind in ("spec", "both") \
        else _table()
    return tab, tm.sample(n, seed), cfg


@pytest.mark.parametrize("case", sorted(SIM_CASES))
def test_sim_breakdown_conserves_and_default_is_byte_identical(case):
    """Aggregate conservation at 1e-9 AND the default path's outputs are
    byte-identical with attribution on vs off (same trace, same table)."""
    tab, tr, cfg = _sim_case(case)
    r0 = simulate(tab, tr, cfg)
    r1 = simulate(tab, tr, dataclasses.replace(cfg, breakdown=True))
    assert r0.breakdown is None and r0.ttft_parts is None
    b = r1.breakdown.check_conservation(REL)
    assert float(b.total_energy) == r0.energy_eq1     # bitwise
    assert np.array_equal(r0.ttft_s, r1.ttft_s, equal_nan=True)
    assert np.array_equal(r0.tpot_s, r1.tpot_s, equal_nan=True)
    assert r0.energy_eq1 == r1.energy_eq1
    assert r0.sim_seconds == r1.sim_seconds
    assert r0.tokens_out == r1.tokens_out


@pytest.mark.parametrize("case", sorted(SIM_CASES))
def test_sim_per_request_parts_sum_to_latencies(case):
    """ttft_parts rows sum to ttft_s and tpot_parts rows to
    tpot_s * output_len for every completed request, every scenario."""
    tab, tr, cfg = _sim_case(case)
    r = simulate(tab, tr, dataclasses.replace(cfg, breakdown=True))
    done = ~np.isnan(r.ttft_s)
    assert done.any()
    assert r.ttft_parts.shape == (len(tr), len(TTFT_PARTS))
    assert r.tpot_parts.shape == (len(tr), len(TPOT_PARTS))
    ttft_sum = r.ttft_parts[done].sum(axis=1)
    scale = np.maximum(np.abs(r.ttft_s[done]), 1.0)
    assert np.max(np.abs(ttft_sum - r.ttft_s[done]) / scale) <= REL
    dec = np.maximum(np.asarray(tr.output_len, np.float64), 1.0)[done]
    tpot_tot = r.tpot_s[done] * dec
    tpot_sum = r.tpot_parts[done].sum(axis=1)
    scale = np.maximum(np.abs(tpot_tot), 1.0)
    assert np.max(np.abs(tpot_sum - tpot_tot) / scale) <= REL


def test_sim_breakdown_components_land_where_expected():
    _, tr_kv, cfg_kv = _sim_case("prefix_cache")
    tab = _table()
    r = simulate(tab, tr_kv, dataclasses.replace(cfg_kv, breakdown=True))
    assert r.breakdown.component("energy", "dram_spill") >= 0.0
    assert r.breakdown.component("cycles", "queueing") > 0.0
    tabs = _table(ARCH, 128, 128, SPEC)
    _, tr, cfg = _sim_case("spec_decode")
    rs = simulate(tabs, tr, dataclasses.replace(cfg, breakdown=True))
    assert rs.breakdown.component("cycles", "draft_overhead") > 0.0
    assert rs.breakdown.component("energy", "draft_overhead") > 0.0
    assert rs.breakdown.meta["time_unit"] == "s"


def test_sim_breakdown_populates_registry_histograms():
    reg = metrics()
    before = {k for k in reg.histograms if k.startswith("sim.ttft")}
    tab, tr, cfg = _sim_case("prefill_first", n=300)
    simulate(tab, tr, dataclasses.replace(cfg, breakdown=True))
    h = reg.histograms.get("sim.ttft.queueing_s")
    assert h is not None and h.n > 0
    assert reg.histograms["sim.tpot.decode_s"].n > 0
    assert before or True   # registry is process-wide; no reset here


def test_sim_breakdown_counter_track_validates():
    from repro import obs
    tab, tr, cfg = _sim_case("prefill_first", n=300)
    tr_obs = obs.Tracer(clock="sim")
    simulate(tab, tr, dataclasses.replace(cfg, breakdown=True,
                                          tracer=tr_obs, track="srv"))
    events = obs.to_trace_events(tr_obs)
    assert not validate_trace(events)
    attrs = [e for e in events if e.get("ph") == "C"
             and e.get("name") == "attribution"]
    assert attrs and all("prefill_s" in e["args"] for e in attrs)


# ----------------------------------------------------- golden equivalence --

def test_breakdown_on_matches_traffic_golden_fixture():
    """The attributed run reproduces the pinned PR 8 golden stats —
    attribution must not perturb the event loop."""
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    import test_traffic_golden as g
    with open(g.FIXTURE) as f:
        want = json.load(f)
    tab, tr = g._table(), g._trace()
    slo = SLO(ttft_s=5.0, tpot_s=0.2)
    from repro.traffic import summarize
    for name, cfg in g.CASES.items():
        res = simulate(tab, tr, dataclasses.replace(cfg, breakdown=True))
        res.breakdown.check_conservation(REL)
        summ = summarize(res, slo)
        for k in g.PINNED:
            assert summ[k] == pytest.approx(want[name][k], rel=REL,
                                            abs=1e-12), (name, k)


def test_breakdown_on_matches_kv_golden_fixture():
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    import test_kv as g
    with open(g.FIXTURE) as f:
        want = json.load(f)
    slo = SLO(ttft_s=5.0, tpot_s=0.2)
    tab = g._table()
    spec_tab = g._table(g.ARCH, 128, 128, g.SPEC)
    tr = g.KV.apply(g.TRAFFIC).sample(g.N_GOLDEN, g.SEED_GOLDEN)
    block_mib = g.KV.prefix_len * tab.kv_bits_per_token / 8 / 2 ** 20
    cases = {
        "prefix_cache": (tab, SimConfig(slots=16,
                                        prefix_cache_mib=g.KV.cache_mib)),
        "prefix_cache_churn": (tab, SimConfig(
            slots=16, prefix_cache_mib=1.5 * block_mib)),
        "spec_decode": (spec_tab, SimConfig(slots=16, spec=g.SPEC)),
        "combined": (spec_tab, SimConfig(slots=16, spec=g.SPEC,
                                         prefix_cache_mib=g.KV.cache_mib)),
    }
    from repro.traffic import summarize
    for name, (t, cfg) in cases.items():
        res = simulate(t, tr, dataclasses.replace(cfg, breakdown=True))
        res.breakdown.check_conservation(REL)
        summ = summarize(res, slo)
        for k in g.PINNED:
            assert summ[k] == pytest.approx(want[name][k], rel=REL,
                                            abs=1e-12), (name, k)
        for k in g.COUNTERS:
            assert getattr(res, k) == want[name][k], (name, k)


# ------------------------------------------------------------ fleet layer --

LAT = dict(slot_lattice=(1, 4, 16), kv_lattice=(128, 512, 2048),
           prompt_lattice=(16, 256, 2048))
FLEET_TRAFFIC = TrafficModel(rate_qps=1.0, prompt_median=128,
                             output_median=32, prompt_range=(16, 1024),
                             output_range=(1, 256))


@functools.lru_cache(maxsize=None)
def _fleet_tables():
    return build_cost_tables([ARCH], hw=((64, 64), (128, 128)),
                             backend="numpy", **LAT)


def test_disagg_fleet_breakdown_conserves_with_link_ship():
    tabs = _fleet_tables()
    fleet = FleetTables(prefill=[tabs.table(ARCH, 128, 128)],
                        decode=[tabs.table(ARCH, 64, 64)] * 2)
    trace = FLEET_TRAFFIC.with_rate(4.0).sample(300, seed=2)
    cfg = FleetSimConfig(server=SimConfig(slots=8, breakdown=True),
                         kv_link=LinkModel(bits_per_cycle=8.0))
    fr = simulate_fleet(fleet, trace, cfg)
    b = fr.breakdown.check_conservation(REL)
    assert b.component("energy", "link_ship") == fr.link_energy > 0.0
    assert b.component("cycles", "link_ship") == fr.link_seconds > 0.0
    assert float(np.sum(np.asarray(b.total_energy))) == \
        pytest.approx(fr.energy_eq1, rel=REL)
    # default path untouched
    cfg0 = FleetSimConfig(server=SimConfig(slots=8),
                          kv_link=LinkModel(bits_per_cycle=8.0))
    fr0 = simulate_fleet(fleet, trace, cfg0)
    assert fr0.breakdown is None
    assert np.array_equal(fr0.ttft_s, fr.ttft_s, equal_nan=True)
    assert fr0.energy_eq1 == fr.energy_eq1


def test_partitioned_fleet_breakdown_attributes_pipeline_bubble():
    st_tab = build_stage_tables([ARCH], hw=((64, 64), (128, 128)),
                                tps=(1,), backend="numpy",
                                **LAT).table(ARCH, 64, 64)
    part = partition_server_table(st_tab, n_stages=2, n_micro=4,
                                  link=LinkModel(bits_per_cycle=32.0))
    t = part.table
    assert t.pipeline_bubble == pytest.approx(part.plan.bubble)
    assert t.pipeline_bubble > 0.0
    trace = FLEET_TRAFFIC.with_rate(2.0).sample(300, seed=1)
    fr = simulate_fleet(FleetTables(mixed=[t, t]), trace,
                        FleetSimConfig(server=SimConfig(slots=8,
                                                        breakdown=True)))
    b = fr.breakdown.check_conservation(REL)
    assert b.component("cycles", "pipeline_bubble") > 0.0
    assert float(np.sum(np.asarray(b.total_energy))) == \
        pytest.approx(fr.energy_eq1, rel=REL)


def test_fleet_latency_histograms_merge_all_servers():
    tabs = _fleet_tables()
    trace = FLEET_TRAFFIC.with_rate(2.0).sample(300, seed=1)
    fr = simulate_fleet(FleetTables(mixed=[tabs.table(ARCH, 64, 64)] * 2),
                        trace, FleetSimConfig(server=SimConfig(slots=8)))
    hists = fr.latency_histograms()
    n_done = sum(int(np.sum(~np.isnan(r.ttft_s))) for r in fr.per_server)
    assert hists["ttft_s"].n == n_done > 0
    assert hists["tpot_s"].n > 0


# -------------------------------------------------- Histogram.merge unit --

def test_histogram_merge_sums_buckets_and_stats():
    a, b = Histogram(lo=1e-2, hi=1e2), Histogram(lo=1e-2, hi=1e2)
    a.observe_many([0.05, 0.5, 5.0])
    b.observe_many([0.5, 50.0, 500.0])        # 500 overflows
    direct = Histogram(lo=1e-2, hi=1e2)
    direct.observe_many([0.05, 0.5, 5.0, 0.5, 50.0, 500.0])
    out = a.merge(b)
    assert out is a
    assert a.counts == direct.counts
    assert a.n == direct.n == 6
    assert a.total == pytest.approx(direct.total)
    assert a.vmin == direct.vmin and a.vmax == direct.vmax


def test_histogram_merge_rejects_bucket_mismatch():
    with pytest.raises(ValueError, match="bucket config mismatch"):
        Histogram(lo=1e-2, hi=1e2).merge(Histogram(lo=1e-3, hi=1e2))
    with pytest.raises(ValueError, match="bucket config mismatch"):
        Histogram(buckets_per_decade=4).merge(Histogram(buckets_per_decade=8))


# ---------------------------------------- validate_trace C-event finiteness --

def _c_event(args):
    return [{"name": "x", "ph": "C", "pid": 1, "tid": 1, "ts": 0.0,
             "args": args}]


def test_validate_trace_rejects_non_finite_counter_series():
    assert validate_trace(_c_event({"ok": 1.0, "also": 2})) == []
    bad = validate_trace(_c_event({"v": float("nan")}))
    assert bad and "non-finite" in bad[0]
    bad = validate_trace(_c_event({"v": float("inf")}))
    assert bad and "non-finite" in bad[0]
    bad = validate_trace(_c_event({"v": float("-inf")}))
    assert bad and "non-finite" in bad[0]
    bad = validate_trace(_c_event({"v": "fast"}))
    assert bad and "numeric" in bad[0]
    assert validate_trace(_c_event({})) != []


# --------------------------------------------------- winner explanation --

@functools.lru_cache(maxsize=None)
def _explained():
    hw = ((64, 64), (128, 128))
    tabs = build_cost_tables([ARCH], hw=hw, backend="numpy", **LAT)
    tm = FLEET_TRAFFIC
    sweep = slo_capacity_sweep(tm, SLO(ttft_s=2.0, tpot_s=0.1),
                               archs=[ARCH], hw=hw,
                               sim=SimConfig(slots=8), n_requests=200,
                               seed=0, tables=tabs)
    ex = explain_winner(sweep, tm, tabs, rivals=[c for c in range(len(hw))
                                                 if c != 0][:1] or [1],
                        sim=SimConfig(slots=8), n_requests=200, seed=0)
    return ex


def test_explain_winner_breakdowns_conserve_and_delta_names_component():
    ex = _explained()
    assert len(ex.breakdowns) == 1 + len(ex.rivals)
    for b in ex.breakdowns:
        b.check_conservation(REL)
    for j, d in enumerate(ex.deltas):
        assert set(d) == {"cycles", "energy"}
        dom = ex.dominant[j]
        assert dom["energy"] in COMPONENTS or dom["energy"] == ""
        if d["energy"]:
            assert dom["energy"] == max(d["energy"],
                                        key=lambda k: abs(d["energy"][k]))
    payload = ex.to_dict()
    assert payload["winner"]["h"] == int(ex.hw[ex.winner, 0])


def test_reports_are_byte_deterministic(tmp_path):
    ex = _explained()
    md1, md2 = winner_report(ex), winner_report(ex)
    assert md1 == md2 and "# Winner explanation" in md1
    j1 = report_json(ex)
    assert j1 == report_json(ex)
    json.loads(j1)                             # valid JSON
    bds = {b.label: b for b in ex.breakdowns}
    a1, a2 = attribution_report(bds), attribution_report(bds)
    assert a1 == a2 and "conservation max rel err" in a1
    p = write_report(str(tmp_path / "r.md"), a1)
    assert open(p).read() == a1 + ("" if a1.endswith("\n") else "\n")


# ------------------------------------------------------- stage purity hook --

def test_reset_metrics_gives_clean_registry():
    reg = metrics()
    reg.inc("attr.test_leak")
    reg.hist("attr.test_hist").observe(1.0)
    reset_metrics()
    assert not metrics().snapshot()
    assert "attr.test_hist" not in metrics().histograms
