"""Fleet subsystem tests: the paper-equation differential anchor
(P identical arrays over a FREE link == the `multi_array` closed form),
per-block lowering vs the flat extraction, DP partitioner vs brute force
(hypothesis), the GPipe bubble closed form on the event recurrence,
partitioned-server tables vs the unpartitioned cost tables, link/array-
count monotonicity of fleet goodput, disaggregated KV shipping, graph
cut-edge accounting, paired (common-random-numbers) trace sampling, and
the fleet composition DSE end to end."""
import functools
from collections import defaultdict

import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config, list_archs
from repro.core import systolic
from repro.core.cnn_zoo import get_workloads
from repro.core.dse import (FleetSpec, PoolSpec, enumerate_fleet_specs,
                            fleet_capacity_sweep, robust_fleet_config)
from repro.core.lm_workloads import extract_workloads
from repro.fleet import (DEFAULT_LINK, FREE_LINK, FleetSimConfig,
                         FleetTables, LinkModel, arch_block_workloads,
                         brute_force_split, bubble_fraction,
                         build_stage_tables, dp_pipeline_split,
                         fleet_max_sustainable_qps, partition_server_table,
                         pipeline_pass_cycles, route_requests,
                         simulate_fleet, tp_parallel_metrics,
                         tp_split_workloads)
from repro.traffic import (SLO, SimConfig, TrafficModel, build_cost_tables,
                           simulate)
from repro.traffic.slo import saturation_qps, summarize

from _hyp import given, settings, st

SLOTS = (1, 4, 16)
KVS = (128, 512, 2048)
PROMPTS = (16, 256, 2048)
LATTICES = dict(slot_lattice=SLOTS, kv_lattice=KVS, prompt_lattice=PROMPTS)


@functools.lru_cache(maxsize=None)
def _stage_tables(arch="yi-9b", tp=1, backend="numpy"):
    return build_stage_tables([arch], hw=((64, 64), (128, 128)),
                              tps=(tp,), backend=backend,
                              **LATTICES)


@functools.lru_cache(maxsize=None)
def _cost_tables(arch="yi-9b"):
    return build_cost_tables([arch], hw=((64, 64), (128, 128)),
                             backend="numpy", **LATTICES)


# ------------------------------------------------------- per-block lowering --

def test_block_workloads_match_flat_lowering():
    """Concatenated per-block GEMMs reproduce `extract_workloads` totals
    exactly — (M, K, N, groups) -> repeats — for every arch and phase."""
    for arch in list_archs():
        cfg = get_config(arch)
        for kind in ("decode", "prefill", "train"):
            shape = ShapeConfig("t", 2048, 8, kind)
            agg = defaultdict(int)
            for wls in arch_block_workloads(cfg, shape):
                for (m, k, n, g, r) in wls:
                    agg[(m, k, n, g)] += r
            ref = defaultdict(int)
            for (m, k, n, g, r) in extract_workloads(cfg, shape):
                ref[(m, k, n, g)] += r
            assert agg == ref, (arch, kind)


# ------------------------------------------------ multi_array differential --

def test_free_link_fleet_reproduces_multi_array_closed_form():
    """THE differential anchor: P identical arrays, free interconnect,
    perfect (ceil) balance == the paper's `multi_array` dataflow — cycles
    equal, energy = P x per-array, within 1e-9 rel."""
    cases = [get_workloads("resnet152"),
             extract_workloads(get_config("yi-9b"),
                               ShapeConfig("d", 2048, 8, "decode"))]
    for wl in cases:
        one = systolic.analyze_network(list(wl), 96.0, 128.0)
        for P in (2, 3, 4, 8):
            ref = systolic.analyze_network(list(wl), 96.0, 128.0,
                                           dataflow="multi_array",
                                           n_arrays=P)
            agg = tp_parallel_metrics(wl, 96.0, 128.0, P, link=FREE_LINK,
                                      split="column")
            assert float(agg["cycles"]) == pytest.approx(
                float(ref.cycles), rel=1e-9)
            assert float(agg["energy"]) == pytest.approx(
                float(ref.energy), rel=1e-9)
            # and the split genuinely parallelizes vs one array
            assert float(agg["cycles"]) < float(one.cycles)


def test_free_link_collectives_cost_nothing_and_real_links_do():
    wl = get_workloads("alexnet")
    free = tp_parallel_metrics(wl, 64.0, 64.0, 4, link=FREE_LINK)
    paid = tp_parallel_metrics(wl, 64.0, 64.0, 4, link=DEFAULT_LINK)
    assert free["collective_bits"] == paid["collective_bits"] > 0
    assert float(paid["cycles"]) > float(free["cycles"])
    assert float(paid["energy"]) > float(free["energy"])


def test_tp_split_modes():
    wl = [(64, 32, 100, 1, 2), (8, 16, 24, 6, 1)]
    col = tp_split_workloads(wl, 4, split="column")
    assert col == [(64, 32, 25, 1, 2), (8, 16, 6, 6, 1)]
    auto = tp_split_workloads(wl, 4, split="auto")
    # grouped GEMMs split the group (head) axis instead of N
    assert auto == [(64, 32, 25, 1, 2), (8, 16, 24, 2, 1)]
    with pytest.raises(ValueError):
        tp_split_workloads(wl, 4, split="rows")


# --------------------------------------------------------- DP partitioner --

@settings(deadline=None, max_examples=40)
@given(seed=st.integers(min_value=0, max_value=10_000),
       L=st.integers(min_value=2, max_value=8),
       S=st.integers(min_value=1, max_value=8))
def test_dp_split_matches_brute_force(seed, L, S):
    """Exact DP == exhaustive enumeration on <= 8-block graphs, with and
    without boundary transfer costs."""
    S = min(S, L)
    rng = np.random.default_rng(seed)
    costs = rng.uniform(0.5, 10.0, L)
    bnd = rng.uniform(0.0, 4.0, L - 1) if seed % 3 else None
    bounds, bot = dp_pipeline_split(costs, S, bnd)
    bf_bounds, bf_bot = brute_force_split(costs, S, bnd)
    assert bot == pytest.approx(bf_bot, rel=1e-12)
    assert bounds[0] == 0 and bounds[-1] == L and len(bounds) == S + 1


def test_dp_split_balances_uniform_blocks():
    bounds, bot = dp_pipeline_split([3.0] * 12, 4)
    assert bounds == (0, 3, 6, 9, 12)
    assert bot == pytest.approx(9.0)


def test_dp_split_avoids_expensive_boundary():
    # cutting at the cheap boundary wins even against slight imbalance
    costs = [1.0, 1.0, 1.0, 1.0]
    bnd = [100.0, 0.0, 100.0]
    bounds, bot = dp_pipeline_split(costs, 2, bnd)
    assert bounds == (0, 2, 4)
    assert bot == pytest.approx(2.0)


# ----------------------------------------------------------- GPipe bubble --

@settings(deadline=None, max_examples=30)
@given(S=st.integers(min_value=1, max_value=12),
       M=st.integers(min_value=1, max_value=24))
def test_bubble_fraction_matches_event_recurrence(S, M):
    """On uniform stages with free links, the exact event-level fill-drain
    recurrence yields makespan (M + S - 1) * c — i.e. EXACTLY the GPipe
    closed-form bubble (S-1)/(M+S-1), same formula as
    sharding.pipeline.bubble_fraction."""
    c = 7.25
    total = float(pipeline_pass_cycles(np.full((S, 1), c), M)[0])
    assert total == pytest.approx((M + S - 1) * c, rel=1e-12)
    ideal = M * c
    assert (total - ideal) / total == pytest.approx(
        bubble_fraction(S, M), abs=1e-12)


def test_bubble_fraction_mirrors_sharding_pipeline():
    from repro.sharding.pipeline import bubble_fraction as jax_bubble
    for S, M in ((1, 4), (2, 4), (4, 1), (5, 13)):
        assert bubble_fraction(S, M) == jax_bubble(S, M)


def test_pipeline_recurrence_bottleneck_and_transfers():
    # unequal stages: steady state is bottleneck-paced
    cs = np.asarray([[2.0], [10.0], [3.0]])
    M = 6
    total = float(pipeline_pass_cycles(cs, M)[0])
    assert total >= M * 10.0
    assert total == pytest.approx(2.0 + 10.0 * M + 3.0)
    # link transfers only delay, never accelerate
    with_x = float(pipeline_pass_cycles(cs, M, np.asarray([[5.], [5.]]))[0])
    assert with_x > total


# ------------------------------------------------- partitioned server tables --

def test_single_stage_free_link_equals_cost_table():
    """S=1, tp=1, free link: the synthesized server table IS the
    unpartitioned `build_cost_tables` lattice (block sums are exact)."""
    base = _cost_tables().table("yi-9b", 128, 128)
    ps = partition_server_table(_stage_tables().table("yi-9b", 128, 128),
                                n_stages=1, link=FREE_LINK)
    for a, b in ((base.decode_cycles, ps.table.decode_cycles),
                 (base.decode_energy, ps.table.decode_energy),
                 (base.decode_macs, ps.table.decode_macs),
                 (base.prefill_cycles, ps.table.prefill_cycles),
                 (base.prefill_energy, ps.table.prefill_energy)):
        a, b = np.asarray(a), np.asarray(b)
        assert float(np.max(np.abs(a - b) / (np.abs(a) + 1.0))) < 1e-9
    assert ps.table.kv_bits_per_token == pytest.approx(
        base.kv_bits_per_token)
    assert ps.table.pe == base.pe
    assert ps.plan.bubble == 0.0


def test_stage_tables_fused_matches_numpy():
    """The ONE fused dse_eval_batched dispatch agrees with the float64
    per-stage reference loop (same bar as the traffic cost tables)."""
    st_np = _stage_tables(backend="numpy")
    st_pl = build_stage_tables(["yi-9b"], hw=((64, 64), (128, 128)),
                               tps=(1,), backend="pallas",
                               **LATTICES)
    a = st_np.table("yi-9b", 128, 128)
    b = st_pl.table("yi-9b", 128, 128)
    for x, y in ((a.dec_cycles, b.dec_cycles),
                 (a.dec_energy, b.dec_energy),
                 (a.pre_cycles, b.pre_cycles)):
        assert float(np.max(np.abs(x - y) / (np.abs(x) + 1.0))) <= 1e-5
    assert a.kinds == b.kinds


def test_partitioned_table_monotone_in_link_bandwidth():
    """Fatter links never slow a partitioned server (decode and prefill
    lattices are pointwise non-increasing in bits/cycle)."""
    st = _stage_tables().table("yi-9b", 128, 128)
    prev = None
    for bpc in (64.0, 256.0, 1024.0):
        ps = partition_server_table(
            st, n_stages=4, n_micro=4,
            link=LinkModel(bits_per_cycle=bpc, hop_cycles=200.0))
        cur = (np.asarray(ps.table.decode_cycles),
               np.asarray(ps.table.prefill_cycles))
        if prev is not None:
            assert (cur[0] <= prev[0] + 1e-9).all()
            assert (cur[1] <= prev[1] + 1e-9).all()
        prev = cur
    free = partition_server_table(st, n_stages=4, n_micro=4, link=FREE_LINK)
    assert (np.asarray(free.table.decode_cycles) <= prev[0] + 1e-9).all()


def test_pipelined_prefill_conserves_work():
    """Chunked prefill charges each chunk the INCREMENT of the cumulative
    prompt lattice: over a free link the pipelined server's prefill
    ENERGY equals the unpartitioned one exactly (microbatching one prompt
    cannot change its total work), and the makespan lands between the
    bottleneck stage's share and the serial total."""
    st = _stage_tables().table("yi-9b", 128, 128)
    t1 = partition_server_table(st, n_stages=1, link=FREE_LINK).table
    t2 = partition_server_table(st, n_stages=2, n_micro=4,
                                link=FREE_LINK).table
    e1 = np.asarray(t1.prefill_energy)
    e2 = np.asarray(t2.prefill_energy)
    assert float(np.max(np.abs(e1 - e2) / (np.abs(e1) + 1.0))) < 1e-9
    c1 = np.asarray(t1.prefill_cycles)
    c2 = np.asarray(t2.prefill_cycles)
    # pipelining overlaps stages: never slower than serial, never faster
    # than the bottleneck stage
    assert (c2 <= c1 * (1.0 + 1e-9)).all()
    assert (c2 >= c1 / 2.0 * (1.0 - 1e-9)).all()


def test_pipeline_recurrence_micro_axis_matches_broadcast():
    cs = np.asarray([[2.0], [5.0]])
    per_micro = np.broadcast_to(cs, (3, 2, 1))
    a = float(pipeline_pass_cycles(cs, 3)[0])
    b = float(pipeline_pass_cycles(per_micro, 3, micro_axis=True)[0])
    assert a == b
    with pytest.raises(ValueError):
        pipeline_pass_cycles(per_micro, 4, micro_axis=True)


def test_saturation_bracket_respects_bucket_distributions():
    """A bucket-length mix brackets off the histogram's weighted median,
    not the unused lognormal median fields."""
    buckets = TrafficModel(
        rate_qps=1.0, prompt_dist="buckets", prompt_buckets=(4096,),
        prompt_probs=(1.0,), output_dist="buckets",
        output_buckets=(1024,), output_probs=(1.0,))
    assert buckets.typical_prompt == 4096
    assert buckets.typical_output == 1024
    logn = TrafficModel(rate_qps=1.0, prompt_median=4096,
                        output_median=1024)
    assert logn.typical_prompt == 4096
    sim = SimConfig(slots=8)
    t = _danube()
    assert saturation_qps(t, buckets, sim) \
        == pytest.approx(saturation_qps(t, logn, sim))


def test_tp_server_energy_bounds():
    """A tp-server's step energy pays ALL ranks: at least the single-array
    energy (the work does not shrink), at most tp x it (full activation
    replication — the paper's multi-array tax), collectives excluded via
    the free link."""
    t1 = partition_server_table(_stage_tables("yi-9b", tp=1)
                                .table("yi-9b", 128, 128, 1),
                                link=FREE_LINK).table
    t4 = partition_server_table(_stage_tables("yi-9b", tp=4)
                                .table("yi-9b", 128, 128, 4),
                                link=FREE_LINK).table
    e1 = np.asarray(t1.decode_energy)
    e4 = np.asarray(t4.decode_energy)
    assert (e4 >= e1 * (1.0 - 1e-9)).all()
    assert (e4 <= 4.0 * e1 * (1.0 + 1e-9)).all()
    # and the split genuinely speeds the step up
    assert (np.asarray(t4.decode_cycles)
            < np.asarray(t1.decode_cycles)).all()
    assert t4.pe == 4 * t1.pe


def test_partition_plan_shape_and_kv_share():
    st = _stage_tables().table("yi-9b", 128, 128)
    ps = partition_server_table(st, n_stages=4, n_micro=8,
                                link=DEFAULT_LINK)
    assert ps.plan.bounds[0] == 0 and ps.plan.bounds[-1] == st.n_blocks
    assert ps.arrays == 4
    assert ps.table.pe == 4 * 128 * 128
    # the binding stage holds at most the whole cache, at least 1/S of it
    full = _cost_tables().table("yi-9b", 128, 128).kv_bits_per_token
    assert full / 4 <= ps.table.kv_bits_per_token <= full
    assert ps.plan.bubble == pytest.approx(bubble_fraction(4, 8))


# ------------------------------------------------------------- fleet replay --

@functools.lru_cache(maxsize=None)
def _danube_tables():
    return build_cost_tables(["h2o-danube-3-4b"], hw=((64, 64), (128, 128)),
                             backend="numpy", **LATTICES)


def _danube(hw=(64, 64)):
    return _danube_tables().table("h2o-danube-3-4b", *hw)


TRAFFIC = TrafficModel(rate_qps=1.0, prompt_median=128, output_median=32,
                       prompt_range=(16, 1024), output_range=(1, 256))


def test_single_server_fleet_equals_plain_simulate():
    trace = TRAFFIC.with_rate(2.0).sample(400, seed=3)
    cfg = FleetSimConfig(server=SimConfig(slots=8))
    fr = simulate_fleet(FleetTables(mixed=[_danube()]), trace, cfg)
    r = simulate(_danube(), trace, cfg.server)
    np.testing.assert_allclose(fr.ttft_s, r.ttft_s, rtol=0, atol=0)
    np.testing.assert_allclose(fr.tpot_s, r.tpot_s, rtol=0, atol=0)
    assert fr.energy_eq1 == pytest.approx(r.energy_eq1)
    assert fr.tokens_out == r.tokens_out


def test_fleet_goodput_monotone_in_server_count():
    """More identical servers never hurt: goodput under the SLO is
    non-decreasing in the array count at fixed offered load."""
    cfg = FleetSimConfig(server=SimConfig(slots=8))
    rate = 2.5 * saturation_qps(_danube(), TRAFFIC, cfg.server)
    trace = TRAFFIC.with_rate(rate).sample(600, seed=0, paired=True)
    slo = SLO(ttft_s=2.0, tpot_s=0.5)
    good = []
    for k in (1, 2, 4):
        fr = simulate_fleet(FleetTables(mixed=[_danube()] * k), trace, cfg)
        good.append(summarize(fr, slo)["goodput_qps"])
    assert good[0] <= good[1] <= good[2]
    assert good[2] > good[0]            # the extra arrays genuinely help


def test_fleet_goodput_monotone_in_link_bandwidth():
    """Pipelined servers on fatter links serve at least as well (same
    routed sub-traces, pointwise-cheaper steps)."""
    st = _stage_tables("h2o-danube-3-4b").table("h2o-danube-3-4b", 64, 64)
    cfg = FleetSimConfig(server=SimConfig(slots=8))
    slo = SLO(ttft_s=2.0, tpot_s=0.5)
    good, p99 = [], []
    for bpc in (32.0, 512.0):
        t = partition_server_table(st, n_stages=2, n_micro=4,
                                   link=LinkModel(bits_per_cycle=bpc)).table
        rate = 2.0 * saturation_qps(t, TRAFFIC, cfg.server)
        trace = TRAFFIC.with_rate(rate).sample(400, seed=1, paired=True)
        fr = simulate_fleet(FleetTables(mixed=[t, t]), trace, cfg)
        s = summarize(fr, slo)
        good.append(s["goodput_qps"])
        p99.append(s["tpot_p99_s"])
    assert good[0] <= good[1]
    assert p99[1] <= p99[0]


def test_disaggregated_fleet_ships_kv_over_the_link():
    trace = TRAFFIC.with_rate(4.0).sample(300, seed=2)
    pre, dec = _danube((128, 128)), _danube((64, 64))
    slow = FleetSimConfig(server=SimConfig(slots=8),
                          kv_link=LinkModel(bits_per_cycle=8.0))
    fast = FleetSimConfig(server=SimConfig(slots=8),
                          kv_link=LinkModel(bits_per_cycle=4096.0))
    fr_s = simulate_fleet(FleetTables(prefill=[pre], decode=[dec, dec]),
                          trace, slow)
    fr_f = simulate_fleet(FleetTables(prefill=[pre], decode=[dec, dec]),
                          trace, fast)
    assert fr_s.disaggregated and fr_s.link_seconds > fr_f.link_seconds > 0
    # energy prices the BITS shipped — identical traffic, identical cost,
    # regardless of how fast the wire drains it
    assert fr_s.link_energy == fr_f.link_energy > 0
    # shipping time is part of TTFT: a slower link pushes the aggregate up
    # (pointwise order can flip — a later decode arrival may catch a freer
    # batch wave — but the population cannot get faster)
    assert np.isfinite(fr_s.ttft_s).all() and np.isfinite(fr_f.ttft_s).all()
    assert float(np.mean(fr_s.ttft_s)) > float(np.mean(fr_f.ttft_s))
    assert float(np.percentile(fr_s.ttft_s, 99)) \
        > float(np.percentile(fr_f.ttft_s, 99))


def test_fleet_layout_validation():
    t = _danube()
    with pytest.raises(ValueError):
        FleetTables(mixed=[t], prefill=[t], decode=[t])
    with pytest.raises(ValueError):
        FleetTables(prefill=[t])
    with pytest.raises(ValueError):
        FleetTables()
    with pytest.raises(ValueError):
        FleetSimConfig(routing="random")


def test_jsq_routes_by_server_speed():
    """JSQ's backlog estimate sends more work to the faster server of a
    heterogeneous pool; round-robin stays blind to shape."""
    tables = [_danube((64, 64)), _danube((128, 128))]
    cfg = FleetSimConfig(routing="jsq", server=SimConfig(slots=8))
    rate = 3.0 * saturation_qps(tables[0], TRAFFIC, cfg.server)
    trace = TRAFFIC.with_rate(rate).sample(500, seed=4)
    parts = route_requests(trace, tables, cfg)
    assert len(parts[1]) > len(parts[0])
    rr = route_requests(trace, tables,
                        FleetSimConfig(server=SimConfig(slots=8)))
    assert abs(len(rr[0]) - len(rr[1])) <= 1


# ------------------------------------------------------- graph cut pricing --

def test_graph_cut_bits_hand_example():
    from repro.core.workloads import Gemm
    from repro.graph.ir import Graph, Node, Tensor
    g = Graph("toy")
    g.add(Node("x", "input", Tensor((4, 8))))                   # 256 bits
    g.add(Node("a", "gemm", Tensor((4, 4)), Gemm(4, 8, 4)), ("x",))
    g.add(Node("b", "gemm", Tensor((4, 2)), Gemm(4, 4, 2)), ("a",))
    g.add(Node("cat", "concat", Tensor((4, 6))), ("a", "b"))
    g.add(Node("c", "gemm", Tensor((4, 1)), Gemm(4, 6, 1)), ("cat",))
    g.add(Node("sink", "output", Tensor((0,))), ("c",))
    # a view edge prices its storage roots, once each
    assert g.edge_bits("cat", "c") == 4 * 4 * 8 + 4 * 2 * 8
    # edges into the output sink are free (state stays put)
    assert g.edge_bits("c", "sink") == 0.0
    # cut after {x, a}: only `a` crosses (consumed by b and, via the view,
    # by c — multicast once)
    assert g.cut_bits({"x", "a"}) == 4 * 4 * 8
    # cut after {x, a, b}: both roots cross via the view
    assert g.cut_bits({"x", "a", "b"}) == 4 * 4 * 8 + 4 * 2 * 8
    with pytest.raises(ValueError):
        g.edge_bits("x", "c")
    # edges are directed producer -> consumer; the reverse is an error,
    # not the consumer's output size
    with pytest.raises(ValueError):
        g.edge_bits("b", "a")


def test_lm_graph_boundary_cut_matches_stage_table_bits():
    """The residual-stream bits the stage tables charge at a pipeline
    boundary equal `Graph.cut_bits` on the full serving graph."""
    from repro.configs.base import reduced
    from repro.graph.builders import lm_graph
    cfg = reduced(get_config("yi-9b"))
    B = 4
    shape = ShapeConfig("d", 64, B, "decode")
    g = lm_graph(cfg, shape)
    # layer-0 nodes: the stream input, layer 0's own cache, and the ops up
    # to (incl.) the 3rd add — attn residual, gate merge, MLP residual
    inputs = [n.name for n in g.nodes if n.kind == "input"]
    left, adds = {inputs[0], inputs[1]}, 0
    for n in g.nodes:
        if n.kind == "input":
            continue
        left.add(n.name)
        if n.kind == "add":
            adds += 1
            if adds == 3:
                break
    cut = g.cut_bits(left)
    assert cut == B * cfg.d_model * 8.0
    # the stage tables charge exactly this at every decode boundary
    st = build_stage_tables(["yi-9b"], hw=((64, 64),), tps=(1,),
                            backend="numpy", slot_lattice=(B,),
                            kv_lattice=(64,), prompt_lattice=(16,))
    full = get_config("yi-9b")
    tab = st.table("yi-9b", 64, 64)
    assert tab.bnd_dec_bits[0, 0] == B * full.d_model * 8.0


# ------------------------------------------------------ paired CRN sampling --

def test_paired_sampling_gives_common_random_lengths():
    """Two models that differ only in their arrival process draw IDENTICAL
    prompt/output lengths under paired=True (common random numbers); the
    default sequential stream does not (mmpp consumes a different amount
    of entropy) and stays byte-stable for the golden fixtures."""
    pois = TrafficModel(rate_qps=5.0, arrival="poisson")
    mmpp = TrafficModel(rate_qps=5.0, arrival="mmpp")
    a = pois.sample(500, seed=7, paired=True)
    b = mmpp.sample(500, seed=7, paired=True)
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    np.testing.assert_array_equal(a.output_len, b.output_len)
    c = pois.sample(500, seed=7)
    d = mmpp.sample(500, seed=7)
    assert not np.array_equal(c.prompt_len, d.prompt_len)
    # the default path is the pre-existing single-stream draw
    rng = np.random.default_rng(7)
    arr = np.cumsum(rng.exponential(1.0 / 5.0, 500))
    np.testing.assert_allclose(c.arrival_s, arr)
    # rate changes leave paired lengths untouched (paired SLO probes)
    e = pois.with_rate(50.0).sample(500, seed=7, paired=True)
    np.testing.assert_array_equal(a.prompt_len, e.prompt_len)


# -------------------------------------------------------- composition DSE --

def test_enumerate_fleet_specs_iso_pe():
    budget = 16 * 128 * 128
    specs = enumerate_fleet_specs(budget, shapes=((64, 64), (128, 128)),
                                  stages=(1, 2), tps=(1, 2))
    assert len(specs) >= 3
    for s in specs:
        assert s.total_pes <= budget
        assert s.total_pes >= 0.9 * budget
    # a shape that cannot fill the budget is dropped
    none = enumerate_fleet_specs(100, shapes=((64, 64),))
    assert none == []


def test_fleet_capacity_sweep_ranks_compositions():
    """End to end: partition -> fused stage tables -> multi-server sim ->
    SLO bisection over a >= 3-composition space, then the robust winner."""
    arch = "h2o-danube-3-4b"
    budget = 4 * 64 * 64
    fleets = [
        FleetSpec("4x[64x64]", (PoolSpec(64, 64, 4),)),
        FleetSpec("2x[2st_64x64]", (PoolSpec(64, 64, 2, stages=2),)),
        FleetSpec("disagg_2+2", (PoolSpec(64, 64, 2, role="prefill"),
                                 PoolSpec(64, 64, 2, role="decode"))),
    ]
    slo = SLO(ttft_s=5.0, tpot_s=1.0)
    sweep = fleet_capacity_sweep(
        {arch: TRAFFIC}, slo, fleets, archs=[arch],
        sim=FleetSimConfig(server=SimConfig(slots=8)),
        n_requests=200, backend="numpy", lattices=LATTICES,
        pe_budget=budget)
    assert sweep.max_qps.shape == (1, 3)
    assert (sweep.max_qps >= 0).all() and sweep.max_qps.max() > 0
    assert np.isfinite(sweep.energy_per_token).all()
    best_spec, best_q = sweep.best(arch)
    assert best_q == sweep.max_qps.max()
    fl, F, mask, winner = robust_fleet_config(sweep)
    assert fl[winner] in fleets and mask[winner]
    assert F.shape == (3, 2)
    # weight validation mirrors the other robust_* variants
    with pytest.raises(ValueError):
        robust_fleet_config(sweep, weights={"nope": 1.0})
    # iso-PE discipline is enforced, not assumed
    with pytest.raises(ValueError):
        fleet_capacity_sweep({arch: TRAFFIC}, slo,
                             [FleetSpec("big", (PoolSpec(256, 256, 99),))],
                             archs=[arch], pe_budget=budget,
                             backend="numpy", lattices=LATTICES)


def test_fleet_bisection_monotone_in_slo_strictness():
    arch = "h2o-danube-3-4b"
    st = _stage_tables(arch)
    ft = FleetTables(mixed=[partition_server_table(
        st.table(arch, 64, 64), n_stages=1).table] * 2)
    cfg = FleetSimConfig(server=SimConfig(slots=8))
    loose, _ = fleet_max_sustainable_qps(ft, TRAFFIC, SLO(5.0, 1.0), cfg,
                                         n_requests=200)
    tight, _ = fleet_max_sustainable_qps(ft, TRAFFIC, SLO(0.5, 0.05), cfg,
                                         n_requests=200)
    assert tight <= loose
