"""Traffic subsystem tests: workload generators, cost-table interpolation
(hypothesis property tests: monotone in KV span / slot count, exact at
lattice points), fused-vs-numpy table equivalence, simulator invariants,
the closed-loop saturation check against `scenario_sweep` tokens/sec, and
the SLO capacity sweep + robust traffic config."""
import functools

import numpy as np
import pytest

from repro.core.dse import (robust_traffic_config, scenario_sweep,
                            slo_capacity_sweep)
from repro.scenarios import Scenario, tokens_per_sec
from repro.traffic import (SLO, SimConfig, TrafficModel, build_cost_tables,
                           bucket_lengths, lognormal_lengths,
                           max_sustainable_qps, mmpp_arrivals,
                           poisson_arrivals, simulate)
from repro.obs.metrics import metrics as obs_metrics
from repro.traffic import native
from repro.traffic.workload import RequestTrace, _mmpp_arrivals_py

from _hyp import given, settings, st

ARCH = "h2o-danube-3-4b"
SLOTS = (1, 2, 4, 8)
KVS = (64, 128, 256, 512)
PROMPTS = (16, 64, 256, 1024)


@functools.lru_cache(maxsize=None)
def _tables(backend="numpy"):
    return build_cost_tables(archs=[ARCH], hw=((64, 64), (128, 128)),
                             slot_lattice=SLOTS, kv_lattice=KVS,
                             prompt_lattice=PROMPTS, backend=backend)


def _table():
    return _tables().table(ARCH, 128, 128)


# ------------------------------------------------------ arrival processes --

def test_poisson_arrivals_rate_and_order():
    rng = np.random.default_rng(0)
    arr = poisson_arrivals(50.0, 20_000, rng)
    assert (np.diff(arr) >= 0).all()
    rate = len(arr) / arr[-1]
    assert rate == pytest.approx(50.0, rel=0.05)


def test_mmpp_is_burstier_than_poisson():
    """Index of dispersion of per-window counts: ~1 for Poisson, > 1 for
    the 2-state MMPP at the same mean rate."""
    rng = np.random.default_rng(1)
    def iod(arr):
        counts = np.bincount(arr.astype(np.int64))     # 1 s windows
        return counts.var() / counts.mean()
    pois = poisson_arrivals(40.0, 40_000, rng)
    mmpp = mmpp_arrivals(16.0, 64.0, 40_000, rng, mean_sojourn_s=5.0)
    assert iod(pois) < 2.0 < iod(mmpp)
    assert (np.diff(mmpp) >= 0).all()


def _mmpp_rates(qps, ratio=4.0):
    lo = 2.0 * qps / (1.0 + ratio)          # as TrafficModel.sample
    return lo, lo * ratio


@pytest.mark.parametrize("seed", [0, 1, 3_000_000_001])
@pytest.mark.parametrize("paired", [False, True])
@pytest.mark.parametrize("qps", [5e-4, 1e-2, 1.0, 300.0, 5e3])
def test_native_mmpp_sampler_matches_python_loop(monkeypatch, qps, paired,
                                                 seed):
    """The compiled sojourn loop draws the same bytes as the Python loop,
    from the bisection's lowest probe rates to overshooting sojourns, and
    leaves the Generator in the same state (so the lengths match too)."""
    lib = native.mmpp_sampler()
    if lib is None:
        pytest.skip("the native MMPP sampler did not build")
    tm = TrafficModel(arrival="mmpp").with_rate(qps)
    a = tm.sample(1200, seed, paired=paired)
    monkeypatch.setattr(native, "mmpp_sampler", lambda: None)
    b = tm.sample(1200, seed, paired=paired)
    for f in ("arrival_s", "prompt_len", "output_len"):
        assert getattr(a, f).tobytes() == getattr(b, f).tobytes(), f
    r_c, r_py = np.random.default_rng(seed), np.random.default_rng(seed)
    x = native.mmpp_arrivals(lib, *_mmpp_rates(qps), 1200, r_c, 10.0)
    y = _mmpp_arrivals_py(*_mmpp_rates(qps), 1200, r_py, 10.0)
    assert x.tobytes() == y.tobytes()
    assert r_c.bit_generator.state == r_py.bit_generator.state


def test_mmpp_fallback_same_bytes_and_counted(monkeypatch):
    """With no compiled sampler the Python loop draws the same trace; the
    counters name the path each call took."""
    reg = obs_metrics()
    tm = TrafficModel(arrival="mmpp").with_rate(0.05)
    before = reg.snapshot()
    a = tm.sample(300, 4)
    built = native.mmpp_sampler() is not None
    assert reg.delta(before) == {("sample.mmpp_native" if built else
                                  "sample.mmpp_fallback"): 1.0}
    monkeypatch.setattr(native, "mmpp_sampler", lambda: None)
    before = reg.snapshot()
    b = tm.sample(300, 4)
    assert reg.delta(before) == {"sample.mmpp_fallback": 1.0}
    for f in ("arrival_s", "prompt_len", "output_len"):
        assert getattr(a, f).tobytes() == getattr(b, f).tobytes(), f


@pytest.mark.parametrize("rate_lo,rate_hi,sojourn", [
    (1e300, 1e300, 10.0),               # lam too large on the first draw
    (1.0, 1e300, 10.0),                 # ... on the first high sojourn
    (np.inf, np.inf, 10.0),
    (1.0, 4.0, np.nan),                 # lam is NaN
    (1.0, 4.0, np.inf),
    (1.0, 4.0, -1.0),                   # exponential refuses the scale
])
def test_mmpp_refused_draw_raises_alike_on_both_paths(monkeypatch, rate_lo,
                                                      rate_hi, sojourn):
    def refusal():
        with pytest.raises((ValueError, OverflowError)) as e:
            mmpp_arrivals(rate_lo, rate_hi, 100, np.random.default_rng(0),
                          mean_sojourn_s=sojourn)
        return type(e.value), str(e.value)
    first = refusal()
    monkeypatch.setattr(native, "mmpp_sampler", lambda: None)
    assert refusal() == first


def test_length_distributions():
    rng = np.random.default_rng(2)
    ln = lognormal_lengths(128.0, 0.8, 16, 512, 10_000, rng)
    assert ln.min() >= 16 and ln.max() <= 512
    assert np.median(ln) == pytest.approx(128.0, rel=0.1)
    bk = bucket_lengths((32, 128), (0.75, 0.25), 10_000, rng)
    assert set(np.unique(bk)) == {32, 128}
    assert (bk == 32).mean() == pytest.approx(0.75, abs=0.03)
    with pytest.raises(ValueError):
        bucket_lengths((32, 128), (0.5,), 10, rng)
    with pytest.raises(ValueError):
        lognormal_lengths(128.0, 0.8, 0, 512, 10, rng)


def test_traffic_model_deterministic_and_trace_replay():
    tm = TrafficModel(rate_qps=5.0)
    a = tm.sample(500, seed=3)
    b = tm.sample(500, seed=3)
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    assert a.offered_qps == pytest.approx(5.0, rel=0.2)
    times = (0.0, 0.5, 0.5, 2.0)
    tr = TrafficModel(arrival="trace", trace_arrival_s=times,
                      prompt_dist="const", prompt_median=64,
                      output_dist="const", output_median=8).sample(4, seed=0)
    np.testing.assert_array_equal(tr.arrival_s, times)
    assert (tr.prompt_len == 64).all() and (tr.output_len == 8).all()
    with pytest.raises(ValueError):
        RequestTrace(np.asarray([1.0, 0.5]), np.asarray([4, 4]),
                     np.asarray([1, 1]))


# ------------------------------------------------- cost-table interpolation --

def test_cost_table_exact_at_lattice_points():
    tab = _table()
    for i, b in enumerate(SLOTS):
        for j, s in enumerate(KVS):
            assert tab.decode_step(b, s) == tab.decode_cycles[i][j]
            assert tab.decode_step_energy(b, s) == tab.decode_energy[i][j]
    for i, p in enumerate(PROMPTS):
        c, e = tab.prefill(p)
        assert c == tab.prefill_cycles[i] and e == tab.prefill_energy[i]


def test_cost_table_piecewise_linear_and_clamped():
    tab = _table()
    mid = tab.decode_step(4, (64 + 128) / 2)
    i = SLOTS.index(4)
    assert mid == pytest.approx(
        0.5 * (tab.decode_cycles[i][0] + tab.decode_cycles[i][1]))
    # outside the lattice: clamped to the edge, never extrapolated
    assert tab.decode_step(0.5, 32) == tab.decode_cycles[0][0]
    assert tab.decode_step(64, 10_000) == tab.decode_cycles[-1][-1]
    assert tab.prefill(1)[0] == tab.prefill_cycles[0]


@settings(max_examples=60, deadline=None)
@given(active=st.integers(min_value=1, max_value=10),
       kv_a=st.integers(min_value=1, max_value=600),
       kv_b=st.integers(min_value=1, max_value=600))
def test_interpolated_cycles_monotone_in_kv_span(active, kv_a, kv_b):
    """Property: for any slot count, interpolated decode cycles are
    non-decreasing in the KV span (the closed forms grow with the
    attention span, and linear interpolation preserves monotonicity)."""
    tab = _table()
    lo, hi = sorted((kv_a, kv_b))
    assert tab.decode_step(active, lo) <= tab.decode_step(active, hi) \
        * (1 + 1e-12)


@settings(max_examples=60, deadline=None)
@given(kv=st.integers(min_value=1, max_value=600),
       act_a=st.integers(min_value=1, max_value=10),
       act_b=st.integers(min_value=1, max_value=10))
def test_interpolated_cycles_monotone_in_active_slots(kv, act_a, act_b):
    tab = _table()
    lo, hi = sorted((act_a, act_b))
    assert tab.decode_step(lo, kv) <= tab.decode_step(hi, kv) * (1 + 1e-12)


def test_fused_pallas_build_matches_numpy_reference():
    """The single fused dse_eval_batched dispatch must agree with the
    float64 per-lattice-point reference on every table entry."""
    np_t = _tables("numpy")
    pl_t = _tables("pallas")
    for key in np_t.tables:
        a, b = np_t.tables[key], pl_t.tables[key]
        for field in ("decode_cycles", "decode_energy", "decode_macs",
                      "prefill_cycles", "prefill_energy"):
            x = np.asarray(getattr(a, field))
            y = np.asarray(getattr(b, field))
            rel = np.abs(x - y) / (np.abs(x) + 1.0)
            assert rel.max() <= 1e-5, (key, field, rel.max())
        assert a.kv_bits_per_token == b.kv_bits_per_token


def test_pallas_loop_backend_matches_fused():
    lp = build_cost_tables(archs=[ARCH], hw=((64, 64), (128, 128)),
                           slot_lattice=SLOTS[:2], kv_lattice=KVS[:2],
                           prompt_lattice=PROMPTS[:2],
                           backend="pallas-loop")
    fu = build_cost_tables(archs=[ARCH], hw=((64, 64), (128, 128)),
                           slot_lattice=SLOTS[:2], kv_lattice=KVS[:2],
                           prompt_lattice=PROMPTS[:2],
                           backend="pallas")
    for key in fu.tables:
        np.testing.assert_allclose(lp.tables[key].decode_cycles,
                                   fu.tables[key].decode_cycles, rtol=1e-6)


# ------------------------------------------------------------- simulator ----

def _const_traffic(rate=4.0, prompt=64, out=32):
    return TrafficModel(rate_qps=rate, prompt_dist="const",
                        prompt_median=prompt, output_dist="const",
                        output_median=out)


def test_sim_deterministic_and_conserving():
    tab = _table()
    tm = TrafficModel(rate_qps=4.0, prompt_median=64,
                      prompt_range=(16, 512), output_median=16,
                      output_range=(1, 128))
    tr = tm.sample(3000, seed=5)
    a = simulate(tab, tr, SimConfig(slots=8))
    b = simulate(tab, tr, SimConfig(slots=8))
    np.testing.assert_array_equal(a.ttft_s, b.ttft_s)
    np.testing.assert_array_equal(a.tpot_s, b.tpot_s)
    assert a.energy_eq1 == b.energy_eq1
    # every request completes; every decoded token is accounted for
    assert np.isfinite(a.tpot_s).all()
    assert a.tokens_out == int(tr.output_len.sum())
    assert (a.ttft_s > 0).all() and (a.tpot_s > 0).all()
    assert a.decode_steps > 0 and a.sim_seconds > 0
    assert a.timeline.shape[1] == 3


def test_sim_policies_complete_and_chunked_bounds_stall():
    """Both admission policies drain the trace; chunked prefill replaces
    the whole-prompt head-of-line stall with per-chunk slices, so the
    worst inter-token gap a running request sees (`max_step_seconds`)
    must shrink when prompts dwarf the chunk."""
    tab = _table()
    tr = _const_traffic(rate=6.0, prompt=1024, out=64).sample(400, seed=9)
    pf = simulate(tab, tr, SimConfig(slots=4, policy="prefill_first"))
    ch = simulate(tab, tr, SimConfig(slots=4, policy="chunked", chunk=256))
    for r in (pf, ch):
        assert np.isfinite(r.tpot_s).all()
        assert r.tokens_out == int(tr.output_len.sum())
    assert ch.max_step_seconds < pf.max_step_seconds


def test_finite_ub_spill_slows_and_costs_energy():
    tab = _table()
    tr = _const_traffic(rate=4.0, prompt=256, out=64).sample(300, seed=11)
    free = simulate(tab, tr, SimConfig(slots=8, ub_kib=None))
    # KV @ 8 slots x ~300 tokens x kv_bits_per_token >> 1 MiB
    tight = simulate(tab, tr, SimConfig(slots=8, ub_kib=1024.0))
    assert free.spill_seconds == 0.0
    assert tight.spill_seconds > 0.0
    assert tight.energy_eq1 > free.energy_eq1
    assert np.percentile(tight.tpot_s, 50) > np.percentile(free.tpot_s, 50)
    # a capacity above peak residency behaves exactly like infinite
    huge = simulate(tab, tr, SimConfig(slots=8, ub_kib=16 * 2 ** 20))
    np.testing.assert_array_equal(huge.tpot_s, free.tpot_s)


def test_saturation_throughput_matches_scenario_sweep():
    """Closed loop: a saturated simulator (every slot always decoding)
    must reproduce the steady-state tokens/sec of the static scenario
    sweep at the mean KV span, within 5% (the gap is the lattice
    interpolation error — the sim only sees the table)."""
    tab = _table()
    slots, prompt, out = 8, 64, 256
    n = 64
    tm = TrafficModel(arrival="trace", trace_arrival_s=(0.0,) * n,
                      prompt_dist="const", prompt_median=prompt,
                      output_dist="const", output_median=out)
    res = simulate(tab, tm.sample(n, seed=0), SimConfig(slots=slots))
    sim_tps = res.tokens_out / res.decode_seconds

    mean_span = prompt + (out - 1) * 0.5      # spans grow 1/token decoded
    sc = Scenario(ARCH, "decode", batch=slots, seq_len=int(mean_span))
    sweep = scenario_sweep({sc.name: sc.workloads()}, hs=[128], ws=[128],
                           backend="numpy")
    ref_tps = float(tokens_per_sec(sc, sweep.cycles[0][0, 0]))
    assert sim_tps == pytest.approx(ref_tps, rel=0.05)


# ---------------------------------------------------------- SLO + capacity --

def test_max_sustainable_qps_monotone_in_slo():
    tab = _table()
    tm = _const_traffic(rate=1.0, prompt=64, out=16)
    sim = SimConfig(slots=8)
    loose = SLO(ttft_s=10.0, tpot_s=1.0)
    strict = SLO(ttft_s=0.5, tpot_s=0.08)
    q_loose, s_loose = max_sustainable_qps(tab, tm, loose, sim,
                                           n_requests=400, iters=6)
    q_strict, _ = max_sustainable_qps(tab, tm, strict, sim,
                                      n_requests=400, iters=6)
    assert q_loose > 0.0
    assert q_strict <= q_loose
    assert s_loose["meets_slo"]
    assert 0.0 < s_loose["goodput_qps"] <= s_loose["offered_qps"] * 1.01


def test_impossible_slo_reports_zero_capacity():
    tab = _table()
    q, summ = max_sustainable_qps(tab, _const_traffic(), SLO(1e-9, 1e-9),
                                  SimConfig(slots=4), n_requests=100,
                                  iters=3)
    assert q == 0.0 and not summ["meets_slo"]


def test_slo_capacity_sweep_and_robust_traffic_config():
    archs = [ARCH, "xlstm-125m"]
    hw = ((64, 64), (128, 128))
    tables = build_cost_tables(archs=archs, hw=hw, slot_lattice=SLOTS,
                               kv_lattice=KVS, prompt_lattice=PROMPTS,
                               backend="numpy")
    traffic = {ARCH: _const_traffic(prompt=64, out=16),
               "xlstm-125m": _const_traffic(prompt=128, out=32)}
    sweep = slo_capacity_sweep(traffic, SLO(ttft_s=5.0, tpot_s=0.5),
                               archs=archs, hw=hw, sim=SimConfig(slots=8),
                               n_requests=300, tables=tables)
    assert sweep.max_qps.shape == (2, 2)
    assert (sweep.max_qps > 0.0).any()
    assert sweep.best(ARCH)[2] == sweep.max_qps[0].max()
    assert len(sweep.summaries) == 2 and len(sweep.summaries[0]) == 2

    hw_out, F, mask, winner = robust_traffic_config(sweep)
    assert hw_out.shape == (2, 2) and F.shape == (2, 2)
    assert mask[winner]                       # winner is on the frontier
    # weighted mix: must cover the swept archs exactly
    hw_w, Fw, mw, ww = robust_traffic_config(
        sweep, weights={ARCH: 3.0, "xlstm-125m": 1.0})
    assert mw[ww]
    with pytest.raises(ValueError):
        robust_traffic_config(sweep, weights={ARCH: 1.0})
    with pytest.raises(ValueError):
        robust_traffic_config(sweep, weights={ARCH: 0.0,
                                              "xlstm-125m": 0.0})
    # missing traffic model for a swept arch is an error, not a silent skip
    with pytest.raises(ValueError):
        slo_capacity_sweep({ARCH: _const_traffic()}, SLO(5.0, 0.5),
                           archs=archs, hw=hw, tables=tables)
