"""Observability layer: tracer semantics, metrics registry, Perfetto
export validity/determinism, and the dispatch-count invariants that turn
PR 3/4 docstring claims ("ONE fused dispatch", "O(events) not O(tokens)",
"zero model evals in the replay loop") into regression tests."""
import functools
import json

import numpy as np
import pytest

from repro import obs
from repro.fleet.sim import FleetSimConfig, FleetTables, simulate_fleet
from repro.traffic.cost_table import build_cost_tables
from repro.traffic.sim import SimConfig, simulate
from repro.traffic.slo import SLO, summarize
from repro.traffic.workload import TrafficModel

ARCH = "h2o-danube-3-4b"
SLOTS = (1, 2, 4, 8)
KVS = (64, 128, 256, 512)
PROMPTS = (16, 64, 256, 1024)


@functools.lru_cache(maxsize=None)
def _tables():
    return build_cost_tables(archs=[ARCH], hw=((64, 64), (128, 128)),
                             slot_lattice=SLOTS, kv_lattice=KVS,
                             prompt_lattice=PROMPTS, backend="numpy",
                             block_c=2)


def _trace(n=300, qps=40.0, seed=0):
    return TrafficModel(rate_qps=qps, prompt_median=128,
                        output_median=16).sample(n, seed=seed)


# ------------------------------------------------------------- tracer API --

def test_tracer_span_nesting_and_balance():
    tr = obs.Tracer(clock="wall")
    with tr.span("outer", "t"):
        with tr.span("inner", "t", depth=1):
            pass
    assert [ev[obs.trace.PH] for ev in tr.events] == ["B", "B", "E", "E"]
    assert tr.open_spans() == {}
    # E pairs LIFO with the innermost B's name
    assert tr.events[2][obs.trace.NAME] == "inner"
    assert tr.events[3][obs.trace.NAME] == "outer"


def test_tracer_end_without_begin_raises():
    tr = obs.Tracer(clock="wall")
    with pytest.raises(RuntimeError):
        tr.end("t")


def test_sim_clock_requires_explicit_ts():
    tr = obs.Tracer(clock="sim")
    with pytest.raises(ValueError):
        tr.begin("x", "t")              # no ts on a sim-clock tracer
    tr.begin("x", "t", ts=1.0)
    tr.end("t", ts=2.0)
    assert len(tr) == 2


def test_disabled_tracer_records_nothing():
    tr = obs.Tracer(enabled=False, clock="sim")
    tr.begin("x", "t", ts=0.0)
    tr.complete("y", "t", 0.0, 1.0)
    tr.instant("z", "t", ts=0.5)
    tr.counter("c", "t", ts=0.5, v=1)
    tr.async_begin("r", "t", 0, 0.0)
    with tr.span("s", "t"):
        pass
    assert len(tr) == 0 and tr.open_spans() == {}


def test_tracks_first_appearance_order():
    tr = obs.Tracer(clock="sim")
    tr.instant("a", "z", ts=0.0)
    tr.instant("b", "a", ts=1.0)
    tr.instant("c", "z", ts=2.0)
    assert tr.tracks() == ["z", "a"]


# -------------------------------------------------------------- histogram --

def test_histogram_counts_and_quantiles():
    h = obs.Histogram(lo=1e-3, hi=1e3, buckets_per_decade=4)
    vals = [1e-4, 0.002, 0.02, 0.2, 2.0, 20.0, 2e4]
    for v in vals:
        h.observe(v)
    assert h.n == len(vals) == sum(h.counts)
    assert h.counts[0] == 1 and h.counts[-1] == 1   # under/overflow
    assert h.vmin == 1e-4 and h.vmax == 2e4
    q50 = h.quantile(0.5)
    assert 0.02 <= q50 <= 2.0
    json.dumps(h.to_dict())                         # JSON-ready


def test_histogram_observe_many_matches_loop():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(-2.0, 2.0, 5000)
    h1 = obs.Histogram()
    h2 = obs.Histogram()
    for v in vals:
        h1.observe(v)
    h2.observe_many(vals)
    assert h1.counts == h2.counts and h1.n == h2.n
    assert h1.total == pytest.approx(h2.total)


def test_histogram_observe_many_drops_non_finite():
    h = obs.Histogram()
    h.observe_many([1.0, np.nan, np.inf, 2.0])
    assert h.n == 2


# --------------------------------------------------------------- registry --

def test_registry_inc_add_many_delta():
    reg = obs.MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2)
    before = reg.snapshot()
    reg.add_many({"a": 1, "b": 5})
    assert reg.get("a") == 4 and reg.get("b") == 5
    assert reg.delta(before) == {"a": 1, "b": 5}
    reg.observe("lat", 0.5)
    s = reg.summarize()
    assert s["counters"]["a"] == 4 and s["histograms"]["lat"]["n"] == 1
    json.loads(reg.to_json())


# ----------------------------------------------------------------- export --

def test_validate_catches_unbalanced_and_nonmonotone():
    evs = [{"ph": "B", "name": "x", "pid": 1, "tid": 1, "ts": 2.0},
           {"ph": "I", "name": "y", "pid": 1, "tid": 1, "ts": 1.0}]
    probs = obs.validate_trace(evs)
    assert any("ts" in p for p in probs)            # non-monotone
    assert any("unbalanced" in p for p in probs)    # open B
    evs = [{"ph": "e", "name": "r", "pid": 1, "tid": 1, "ts": 0.0,
            "cat": "req", "id": "0"}]
    assert any("async end" in p for p in obs.validate_trace(evs))


def test_traced_replay_exports_valid_trace():
    tab = _tables().table(ARCH, 128, 128)
    tr = obs.Tracer(clock="sim")
    res = simulate(tab, _trace(), SimConfig(slots=8, tracer=tr,
                                            track="server0"))
    assert np.isfinite(res.tpot_s).all()
    assert len(tr) > 0 and tr.open_spans() == {}
    events = obs.to_trace_events(tr)
    assert obs.validate_trace(events) == []
    # every phase the lifecycle promises is present
    names = {e["name"] for e in events}
    assert {"request", "first_token", "queue", "decode"} <= names


def test_seeded_disagg_fleet_trace_byte_identical_and_per_server_tracks():
    """Acceptance bar: >= 2 servers, disagg enabled, valid trace, one
    track per server/pool, byte-identical across two seeded runs."""
    ts = _tables()
    fleet = FleetTables(prefill=[ts.table(ARCH, 64, 64)],
                        decode=[ts.table(ARCH, 64, 64),
                                ts.table(ARCH, 128, 128)])
    blobs = []
    for _ in range(2):
        tr = obs.Tracer(clock="sim")
        cfg = FleetSimConfig(routing="round_robin",
                             server=SimConfig(slots=8, tracer=tr))
        res = simulate_fleet(fleet, _trace(), cfg)
        assert res.disaggregated and res.n_servers == 3
        tracks = set(tr.tracks())
        assert {"prefill0", "kv_link", "decode0", "decode1"} <= tracks
        assert obs.validate_trace(obs.to_trace_events(tr)) == []
        blobs.append(obs.trace_json(tr))
        # per-server bounded timelines ride along on the result
        tls = res.server_timelines
        assert len(tls) == 2 and all(t.shape[1] == 3 for t in tls)
    assert blobs[0] == blobs[1]


def test_untraced_fleet_configs_stay_equal():
    """No tracer => per-server configs are the shared cfg.server object
    (SimConfig equality is what lets the batched search pack lanes)."""
    cfg = FleetSimConfig(server=SimConfig(slots=8))
    from repro.fleet.sim import _server_cfg
    assert _server_cfg(cfg, "server", 1) is cfg.server


# ------------------------------------------------- dispatch-count claims --

def test_scenario_sweep_is_one_fused_dispatch():
    from repro.core import get_workloads
    from repro.core.dse import scenario_sweep
    named = {"a": get_workloads("alexnet")[:3],
             "b": get_workloads("resnet152")[:3]}
    before = obs.metrics().snapshot()
    scenario_sweep(named, hs=(16, 32), ws=(16, 32), backend="pallas",
                   fused=True, block_c=2)
    d = obs.metrics().delta(before)
    assert d.get("kernels.fused_dispatches") == 1
    assert "kernels.sweep_dispatches" not in d


def test_build_stage_tables_is_one_fused_dispatch():
    from repro.fleet.partition import build_stage_tables
    before = obs.metrics().snapshot()
    build_stage_tables([ARCH], hw=((64, 64),), tps=(1,), backend="pallas",
                       block_c=2, slot_lattice=SLOTS[:2],
                       kv_lattice=KVS[:2], prompt_lattice=PROMPTS[:2])
    d = obs.metrics().delta(before)
    assert d.get("kernels.fused_dispatches") == 1


def test_replay_loop_does_zero_model_evals_and_is_o_events():
    tab = _tables().table(ARCH, 128, 128)
    trace = _trace(n=500, qps=60.0)
    before = obs.metrics().snapshot()
    res = simulate(tab, trace, SimConfig(slots=8))
    d = obs.metrics().delta(before)
    assert "model.network_evals" not in d          # zero evals in the loop
    assert "model.gemm_evals" not in d
    assert d["sim.replays"] == 1 and d["sim.requests"] == 500
    # O(events): loop iterations are a small multiple of requests, far
    # below the token count a step-per-token simulator would pay
    assert d["sim.events"] < 6 * 500
    assert res.tokens_out > d["sim.events"]
    assert d["sim.decode_steps"] == res.decode_steps
    assert d["sim.table_lookups"] > 0


def test_bisection_probe_counter():
    from repro.traffic.slo import max_sustainable_qps
    tab = _tables().table(ARCH, 128, 128)
    tm = TrafficModel(rate_qps=10.0, prompt_median=64, output_median=8)
    before = obs.metrics().snapshot()
    max_sustainable_qps(tab, tm, SLO(ttft_s=5.0, tpot_s=1.0),
                        SimConfig(slots=8), n_requests=100, iters=3)
    d = obs.metrics().delta(before)
    assert d.get("slo.bisection_probes", 0) >= 4   # bracket + 3 bisections


# --------------------------------------------------- timeline decimation --

def test_timeline_decimation_keeps_tail_and_bound():
    tab = _tables().table(ARCH, 128, 128)
    trace = _trace(n=2000, qps=100.0, seed=1)
    full = simulate(tab, trace, SimConfig(slots=8,
                                          timeline_samples=1 << 20))
    dec = simulate(tab, trace, SimConfig(slots=8, timeline_samples=8))
    assert len(full.timeline) > 2 * 8      # halving actually triggered
    assert len(dec.timeline) <= 2 * 8
    t_dec, t_full = dec.timeline[:, 0], full.timeline[:, 0]
    assert (np.diff(t_dec) > 0).all()
    assert set(t_dec) <= set(t_full)       # decimation only drops samples
    # the tail survives: the newest retained sample sits in the last
    # stretch of the replay, not half a trace ago
    assert t_dec[-1] >= 0.9 * t_full[-1]


# --------------------------------------------------- summarize histograms --

def test_summarize_carries_latency_histograms():
    tab = _tables().table(ARCH, 128, 128)
    res = simulate(tab, _trace(), SimConfig(slots=8))
    out = summarize(res, SLO(ttft_s=2.0, tpot_s=0.5))
    for key in ("ttft_hist", "tpot_hist"):
        h = out[key]
        assert h["n"] == out["completed"] == sum(h["counts"])
        json.dumps(h)
    # bucket CDF agrees with the percentile within bucket resolution
    hq = obs.Histogram(lo=1e-3, hi=1e3, buckets_per_decade=4)
    hq.observe_many(res.ttft_s)
    q99 = hq.quantile(0.99)
    edge = 10.0 ** (1.0 / 4)               # one log-bucket of slack
    assert q99 / edge <= max(out["ttft_p99_s"], 1e-3) * edge * edge


# -------------------------------------------------- wall spans in the DSE --

def test_dse_sweep_emits_wall_spans():
    from repro.core.dse import slo_capacity_sweep
    tm = TrafficModel(rate_qps=10.0, prompt_median=64, output_median=8)
    old = obs.set_tracer(obs.Tracer(enabled=True, clock="wall"))
    try:
        slo_capacity_sweep(tm, SLO(ttft_s=5.0, tpot_s=1.0), archs=[ARCH],
                           hw=((64, 64),), tables=_tables(),
                           sim=SimConfig(slots=4), n_requests=60, seed=0)
        tr = obs.tracer()
        names = [ev[obs.trace.NAME] for ev in tr.events]
        assert "capacity_search" in names
        assert "lockstep_round" in names   # search="auto" -> batched path
        assert tr.open_spans() == {}
        assert obs.validate_trace(obs.to_trace_events(tr)) == []
    finally:
        obs.set_tracer(old)


# ----------------------------------------- wall spans in the profiler trace --

def _profiler_spans(log_dir):
    """(name, start_ns, end_ns) of every `repro.` host event in the newest
    JAX profiler trace under `log_dir`, in order of start."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return sorted(((e.name, int(e.start_ns),
                    int(e.start_ns) + int(e.duration_ns))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith(obs.trace.PROFILER_PREFIX)),
                  key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _profiled(log_dir, tracing, fn):
    """Run `fn` under a JAX profiler session with the process tracer on
    (`tracing`) or off; returns the tracer it ran with."""
    import jax
    old = obs.set_tracer(obs.Tracer(enabled=False, clock="wall"))
    try:
        with jax.profiler.trace(str(log_dir)):
            tr = obs.enable_tracing() if tracing else obs.tracer()
            try:
                fn()
            finally:
                obs.disable_tracing()
        return tr
    finally:
        obs.set_tracer(old)


def _tiny_sweep():
    from repro.core import get_workloads
    from repro.core.dse import grid_sweep
    axis = np.array([64, 160, 256])
    grid_sweep(get_workloads("alexnet"), hs=axis, ws=axis, backend="pallas")


def test_wall_spans_land_in_the_profiler_trace(tmp_path):
    from repro.core.dse import slo_capacity_sweep

    def work():
        _tiny_sweep()
        slo_capacity_sweep(TrafficModel(rate_qps=10.0, prompt_median=64,
                                        output_median=8),
                           SLO(ttft_s=5.0, tpot_s=1.0), archs=[ARCH],
                           hw=((64, 64),), tables=_tables(),
                           sim=SimConfig(slots=4), n_requests=60, seed=0)

    tr = _profiled(tmp_path, True, work)
    spans = _profiler_spans(tmp_path)
    # one profiler event per span the tracer recorded, under its name
    begun = [ev[obs.trace.NAME] for ev in tr.events if ev[obs.trace.PH] == "B"]
    assert sorted(n[len("repro."):] for n, _, _ in spans) == sorted(begun)
    # the sweep: layer table, padding, dispatch, copy back, columns, each
    # closed before the next opens
    sweep = [s for s in spans if s[0].startswith("repro.sweep.")]
    assert [s[0] for s in sweep] == [
        "repro.sweep.put", "repro.sweep.put", "repro.sweep.enqueue",
        "repro.sweep.fetch", "repro.sweep.assemble"]
    assert all(a[2] <= b[1] for a, b in zip(sweep, sweep[1:]))
    # the search: every step inside a lockstep round, every round inside
    # the capacity search
    (search,) = [s for s in spans if s[0] == "repro.capacity_search"]
    rounds = [s for s in spans if s[0] == "repro.lockstep_round"]
    steps = [s for s in spans if s[0].startswith("repro.search.")]
    assert rounds and all(_inside(r, search) for r in rounds)
    assert {s[0] for s in steps} == {"repro.search.sample",
                                     "repro.search.replay",
                                     "repro.search.score"}
    assert all(any(_inside(s, r) for r in rounds) for s in steps)


def test_disabled_tracing_writes_no_profiler_spans(tmp_path):
    tr = _profiled(tmp_path, False, _tiny_sweep)
    assert not tr.enabled and len(tr) == 0
    assert tr.span("sweep.put", "dse") is obs.trace._NULL_SPAN
    assert _profiler_spans(tmp_path) == []


def test_packed_search_counts_requests_and_reports_its_engine():
    from repro.core.dse import slo_capacity_sweep
    before = obs.metrics().snapshot()
    res = slo_capacity_sweep(TrafficModel(rate_qps=10.0, prompt_median=64,
                                          output_median=8),
                             SLO(ttft_s=5.0, tpot_s=1.0), archs=[ARCH],
                             hw=((64, 64), (128, 128)), tables=_tables(),
                             sim=SimConfig(slots=4), n_requests=60, seed=0)
    d = obs.metrics().delta(before)
    stats = res.search_stats
    assert stats["backend"] in ("native", "xla")
    assert stats["probes"] == d["search.probes"] > 0
    assert stats["rounds"] == d["search.lockstep_rounds"]
    # the packed engines count requests, not replays (a replay counted
    # would read as the scalar engine)
    assert d["sim.requests"] == 60 * d["search.probes"]
    assert d.get("sim.replays", 0) == 0
