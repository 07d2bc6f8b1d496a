"""Serving-scenario DSE: full-model LM graph flatten-equivalence vs the
flat `extract_workloads` lowering, KV-cache/state residency, the fused
batched scenario sweep vs per-scenario sweeps, robust serving config, and
tokens/sec scoring."""
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config, list_archs
from repro.core import analyze_network, extract_workloads, grid_sweep
from repro.core.dse import (grid_axes, robust_serving_config,
                            scenario_sweep)
from repro.core.workloads import aggregate_workloads, total_macs
from repro.graph import lm_graph
from repro.graph.schedule import occupancy_profile
from repro.scenarios import (Scenario, joules_per_token, named_workloads,
                             score_scenarios, serving_matrix,
                             tokens_per_sec)

SMALL = grid_axes()[::5]              # 5x5 grid for the cheap sweeps

# small shapes keep graph construction + aggregation fast in CI
PHASE_SHAPES = {
    "prefill": ShapeConfig("p", 512, 4, "prefill"),
    "decode": ShapeConfig("d", 4096, 8, "decode"),
    "train": ShapeConfig("t", 1024, 2, "train"),
}


# ------------------------------------------------- lm_graph flatten equiv --

@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("phase", sorted(PHASE_SHAPES))
def test_lm_graph_flatten_equivalent_to_flat_lowering(arch, phase):
    """Acceptance: the full-model graph's aggregated flatten() reproduces
    `extract_workloads` GEMM for GEMM — same (M, K, N, groups) keys, same
    total repeats — for every config family and phase."""
    cfg = get_config(arch)
    shape = PHASE_SHAPES[phase]
    g = lm_graph(cfg, shape)
    g.validate()
    flat = extract_workloads(cfg, shape)
    assert aggregate_workloads(g.flatten()) == aggregate_workloads(flat)
    assert total_macs(g.flatten()) == total_macs(flat)


def test_lm_graph_flatten_equivalent_off_zoo_variants():
    """The equivalence must hold for constructible configs beyond the zoo
    too — notably a sliding-window AUDIO config (the window caps the
    encoder's kv span in both lowerings) and an attention-gapped dense
    stack (non-hybrid layers without a mixer)."""
    import dataclasses
    variants = [
        dataclasses.replace(get_config("whisper-small"),
                            name="audio-swa", sliding_window=64),
        dataclasses.replace(get_config("yi-9b"), name="dense-gappy",
                            num_layers=6, attn_every=3, attn_offset=1),
    ]
    for cfg in variants:
        for shape in PHASE_SHAPES.values():
            g = lm_graph(cfg, shape)
            g.validate()
            flat = extract_workloads(cfg, shape)
            assert aggregate_workloads(g.flatten()) == \
                aggregate_workloads(flat), (cfg.name, shape.kind)


@pytest.mark.parametrize("arch", ["yi-9b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b", "xlstm-125m",
                                  "whisper-small"])
def test_lm_graph_metrics_match_flat_lowering(arch):
    """Equal aggregates => identical closed-form network metrics (every
    metric is linear in repeats; maxed fields see the same per-shape
    values)."""
    cfg = get_config(arch)
    shape = PHASE_SHAPES["decode"]
    m_graph = analyze_network(lm_graph(cfg, shape).flatten(), 64.0, 64.0)
    m_flat = analyze_network(extract_workloads(cfg, shape), 64.0, 64.0)
    for k in ("cycles", "energy", "macs", "m_ub", "m_inter_pe", "m_aa"):
        assert float(getattr(m_graph, k)) == float(getattr(m_flat, k)), k
    assert float(m_graph.utilization) == pytest.approx(
        float(m_flat.utilization), rel=1e-12)


# ------------------------------------------------------- serving residency --

def test_decode_kv_cache_pinned_to_end_of_pass():
    """Decode: every layer's KV cache enters up front and stays live to
    the terminal sink — peak occupancy is at least the total cache size."""
    cfg = get_config("yi-9b")
    shape = ShapeConfig("d", 4096, 8, "decode")
    g = lm_graph(cfg, shape)
    caches = [n for n in g.nodes if n.kind == "input"][1:]
    assert len(caches) == cfg.num_layers
    d = cfg.resolved_head_dim
    want_bits = 2 * 8 * 4096 * cfg.num_kv_heads * d * 8.0
    assert all(c.out.size_bits == want_bits for c in caches)
    for order in ("dfs", "bfs"):
        p = occupancy_profile(g, order)
        last = len(p.schedule) - 1
        assert p.schedule[last] == "sink"
        for c in caches:                  # pinned through the sink
            assert p.spans[c.name][1] == last
        assert p.peak_bits >= cfg.num_layers * want_bits


def test_decode_recurrent_state_pinned_for_ssm_and_hybrid():
    for arch in ("xlstm-125m", "jamba-1.5-large-398b"):
        cfg = get_config(arch)
        g = lm_graph(cfg, ShapeConfig("d", 2048, 4, "decode"))
        states = [n for n in g.nodes if n.kind == "input"][1:]
        assert len(states) == cfg.num_layers      # every layer has a mixer
        p = occupancy_profile(g, "dfs")
        last = len(p.schedule) - 1
        assert all(p.spans[s.name][1] == last for s in states)


def test_prefill_pins_kv_projections():
    """Prefill: the K/V projections being written ARE the cache — they
    stay live to the end of the pass instead of dying at attention."""
    cfg = get_config("qwen3-14b")
    shape = ShapeConfig("p", 512, 2, "prefill")
    g = lm_graph(cfg, shape)
    p = occupancy_profile(g, "dfs")
    last = len(p.schedule) - 1
    kv_nodes = [n.name for n in g.nodes
                if n.kind == "gemm" and n.layer.name in ("wk", "wv")]
    assert len(kv_nodes) == 2 * cfg.num_layers
    assert all(p.spans[nm][1] == last for nm in kv_nodes)
    # the training graph carries no cache: nothing outlives its consumers
    g_tr = lm_graph(cfg, ShapeConfig("t", 512, 2, "train"))
    p_tr = occupancy_profile(g_tr, "dfs")
    kv_tr = [n.name for n in g_tr.nodes
             if n.kind == "gemm" and n.layer.name in ("wk", "wv")]
    last_tr = len(p_tr.schedule) - 1
    assert all(p_tr.spans[nm][1] < last_tr for nm in kv_tr)


def test_decode_liveness_dwarfs_prefill_transients():
    """The point of the serving graph: decode peak residency is cache-
    dominated and far above the same model's chain ablation."""
    cfg = get_config("yi-9b")
    g = lm_graph(cfg, ShapeConfig("d", 4096, 8, "decode"))
    peak = occupancy_profile(g, "dfs").peak_bits
    chain = occupancy_profile(g.as_chain(), "dfs").peak_bits
    assert peak > 10 * chain


# ---------------------------------------------------------- scenario matrix --

def test_serving_matrix_covers_zoo():
    scs = serving_matrix()
    assert len(scs) == len(list_archs()) * 2
    assert {s.arch for s in scs} == set(list_archs())
    assert {s.phase for s in scs} == {"prefill", "decode"}
    names = [s.name for s in scs]
    assert len(set(names)) == len(names)
    with pytest.raises(ValueError):
        Scenario("yi-9b", "chat")


def test_scenario_tokens_per_pass():
    pre = Scenario("yi-9b", "prefill", batch=4, seq_len=256)
    dec = Scenario("yi-9b", "decode", batch=4, seq_len=256)
    assert pre.tokens_per_pass == 4 * 256
    assert dec.tokens_per_pass == 4
    assert tokens_per_sec(dec, 1e6, clock_hz=1e9) == 4 * 1e9 / 1e6


# ----------------------------------------------------------- fused sweep ----

def _matrix():
    scs = serving_matrix(batches=(4,), seq_lens=(1024,))
    return scs, named_workloads(scs)


def test_scenario_sweep_numpy_matches_per_scenario_grid_sweep():
    """The batched numpy path is bit-identical to looping grid_sweep."""
    _, nw = _matrix()
    s = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="numpy")
    for i, (name, wls) in enumerate(nw.items()):
        ref = grid_sweep(wls, hs=SMALL, ws=SMALL, backend="numpy")
        for k in ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
                  "m_aa", "ub_bw_bits"):
            assert np.array_equal(getattr(s, k)[i], getattr(ref, k)), \
                (name, k)
        sr = s.result(name)
        assert np.array_equal(sr.energy, ref.energy)


def test_scenario_sweep_fused_matches_numpy_full_matrix():
    """Acceptance: ONE fused batched Pallas dispatch over the full
    10-config x {prefill, decode} matrix matches the per-scenario numpy
    sweeps to <= 1e-6 on every metric grid."""
    _, nw = _matrix()
    assert len(nw) == 20
    s_np = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="numpy")
    s_pl = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="pallas")
    for k in ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
              "m_aa", "ub_bw_bits"):
        a = getattr(s_np, k)
        b = getattr(s_pl, k)
        rel = np.abs(a - b) / (np.abs(a) + 1.0)
        assert rel.max() <= 1e-6, (k, rel.max())


def test_scenario_sweep_fused_matches_dispatch_loop():
    """The fused batched kernel computes exactly what the per-scenario
    dispatch loop computes (same kernel body, same f32 math; the padding
    rows only add zeros to the sums and are masked out of the maxes)."""
    _, nw = _matrix()
    fused = scenario_sweep(nw, hs=SMALL, ws=SMALL)
    loop = scenario_sweep(nw, hs=SMALL, ws=SMALL, fused=False)
    for k in ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
              "m_aa", "ub_bw_bits"):
        np.testing.assert_allclose(getattr(fused, k), getattr(loop, k),
                                   rtol=1e-6, atol=0)


def test_scenario_sweep_rejects_unknown_backend():
    _, nw = _matrix()
    with pytest.raises(ValueError):
        scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="fortran")


# ------------------------------------------------- robust config + scoring --

def test_robust_serving_config_normalization_and_weights():
    scs, nw = _matrix()
    s = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="numpy")
    cfgs, F, mask = robust_serving_config(s)
    assert mask.any()
    assert F.min() >= 0.0 and F.max() <= 1.0 + 1e-12
    # weighting only decode cells == sweeping only decode cells
    dec_only = {n: 1.0 if "/decode/" in n else 0.0 for n in s.names}
    _, Fd, maskd = robust_serving_config(s, weights=dec_only)
    nw_dec = {n: w for n, w in nw.items() if "/decode/" in n}
    s_dec = scenario_sweep(nw_dec, hs=SMALL, ws=SMALL, backend="numpy")
    _, Fd_ref, maskd_ref = robust_serving_config(s_dec)
    np.testing.assert_allclose(Fd, Fd_ref)
    assert np.array_equal(maskd, maskd_ref)
    with pytest.raises(ValueError):
        robust_serving_config(s, weights={n: 0.0 for n in s.names})
    # weight dicts must cover the swept scenarios exactly: a typoed or
    # partial dict raises instead of silently changing the mix
    with pytest.raises(ValueError, match="missing"):
        robust_serving_config(s, weights={s.names[0]: 1.0})
    with pytest.raises(ValueError, match="unknown"):
        robust_serving_config(
            s, weights={**{n: 1.0 for n in s.names}, "typo/decode": 1.0})


def test_score_scenarios_records():
    scs, nw = _matrix()
    s = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="numpy")
    recs = score_scenarios(s, scs, clock_hz=1e9, at=(128, 128))
    assert len(recs) == len(scs)
    for r in recs:
        sc = next(x for x in scs if x.name == r["scenario"])
        assert r["tokens_per_pass"] == sc.tokens_per_pass
        assert 0 < r["tps_at_frac_of_best"] <= 1.0 + 1e-12
        assert r["best_tps"] >= r["tps_at_best_energy"] > 0
        i = s.index(r["scenario"])
        # tps at the best-cycles point is tokens_per_pass * f / min cycles
        want = sc.tokens_per_pass * 1e9 / s.cycles[i].min()
        assert r["best_tps"] == pytest.approx(want)


def test_joules_per_token_scoring():
    """The energy analogue of tokens/sec: bit-normalized Eq. 1 energy
    priced per serviced token, linear in the unit price, grid-shaped, and
    threaded through score_scenarios next to the throughput fields."""
    dec = Scenario("yi-9b", "decode", batch=4, seq_len=1024)
    pre = Scenario("yi-9b", "prefill", batch=4, seq_len=1024)
    # decode advances B tokens per pass, prefill B*S: same pass energy =>
    # prefill's per-token energy is S times cheaper
    assert joules_per_token(dec, 1e12, joules_per_unit=1e-12) == 4 ** -1 * 1.0
    assert joules_per_token(pre, 1e12, joules_per_unit=1e-12) == \
        pytest.approx(1.0 / (4 * 1024))
    grid = joules_per_token(dec, np.full((3, 3), 2e12))
    assert grid.shape == (3, 3)
    assert joules_per_token(dec, 1.0, joules_per_unit=2e-12) == \
        2 * joules_per_token(dec, 1.0, joules_per_unit=1e-12)

    scs, nw = _matrix()
    s = scenario_sweep(nw, hs=SMALL, ws=SMALL, backend="numpy")
    recs = score_scenarios(s, scs, at=(128, 128))
    for r in recs:
        sc = next(x for x in scs if x.name == r["scenario"])
        i = s.index(r["scenario"])
        # best_jpt sits at the min-energy point (shared denominator)
        want = float(joules_per_token(sc, s.energy[i].min()))
        assert r["best_jpt"] == pytest.approx(want)
        assert r["jpt_at"] >= r["best_jpt"] > 0
        assert r["jpt_at_frac_of_best"] >= 1.0 - 1e-12
