"""The second reduction of a traced window, over the program's own spans:
on a small synthetic trace, and on traced CPU rehearsals of a sweep cell
and a capacity cell."""
import pytest

from bench import program_trace, trace
from bench.tests.conftest import SMALL

MS = 1_000_000


def test_program_spans_get_self_time_and_idle_time_exactly():
    ops = [("%k", 2 * MS, 3 * MS, 0), ("%k", 7 * MS, 8 * MS, 0)]
    bench = [("window", 0, 10 * MS), ("query", 1 * MS, 9 * MS),
             ("lower", 1 * MS, 2 * MS), ("dse_call", 2 * MS, 9 * MS)]
    program = [("sweep.put", 2 * MS, 2_500_000),
               ("sweep.enqueue", 2_500_000, 3 * MS),
               ("sweep.fetch", 3 * MS, 8_500_000),
               ("sweep.assemble", 8_500_000, 8_800_000)]
    r = program_trace.reduce(ops, bench, program, (0, 10 * MS))
    # the benchmark's own reduction does not see the program's spans
    assert r["bench"] == trace.reduce(ops, bench, (0, 10 * MS))
    assert r["bench"]["idle_s"] == pytest.approx(
        {"window": 2e-3, "lower": 1e-3, "dse_call": 5e-3})
    assert r["self_s"] == pytest.approx(
        {"sweep.put": 0.5e-3, "sweep.enqueue": 0.5e-3,
         "sweep.fetch": 5.5e-3, "sweep.assemble": 0.3e-3})
    assert r["program"]["idle_s"] == pytest.approx(
        {"none": 3.2e-3, "sweep.fetch": 4.5e-3, "sweep.assemble": 0.3e-3})
    # dse_call idles 5 ms, 4.8 of them inside the fetch and the assembly
    assert r["covered_idle"] == pytest.approx(
        {"window": 0.0, "lower": 0.0, "dse_call": 0.96})


def test_self_time_leaves_out_the_time_of_nested_spans():
    program = [("capacity_search", 0, 10 * MS),
               ("lockstep_round", 1 * MS, 9 * MS),
               ("search.sample", 1 * MS, 2 * MS),
               ("search.replay", 2 * MS, 6 * MS),
               ("search.score", 6 * MS, 8 * MS)]
    assert program_trace.self_seconds(program, (0, 10 * MS)) == \
        pytest.approx({"capacity_search": 2e-3, "lockstep_round": 1e-3,
                       "search.sample": 1e-3, "search.replay": 4e-3,
                       "search.score": 2e-3})
    # clipped to the window
    assert program_trace.self_seconds(program, (5 * MS, 7 * MS)) == \
        pytest.approx({"search.replay": 1e-3, "search.score": 1e-3})


@pytest.mark.parametrize("cell,bench_spans", [
    ("resnet152.grid", {"window", "query", "lower", "dse_call"}),
    ("olmoe-1b-7b.capacity_poisson",
     {"window", "query", "table_build", "search"})])
def test_traced_rehearsal_reports_every_step_metric(small_cell, cell,
                                                     bench_spans):
    c = small_cell(cell)
    r = program_trace.run_window(c, 2 ** 31 + 3, 0.3, True, True)
    assert r["correct"] and r["queries"] >= 1
    e2e = "sweep_points_per_s" if cell.startswith("resnet") \
        else "capacity_points_per_s"
    want = set(program_trace.SELF_MS[e2e]) | (
        {"fetch_idle_ms.sweep"} if e2e == "sweep_points_per_s"
        else {"replay_requests_per_s.capacity"})
    assert set(r["program"]) == want
    assert all(v is not None for v in r["program"].values()), r["program"]
    # the benchmark's breakdown names its own spans only
    assert {n for n, _ in r["idle_gaps"]} <= bench_spans
    assert set(r["covered_idle"]) <= bench_spans
    if e2e == "capacity_points_per_s":
        k = r["counters"]
        assert k["sim.replays"] == 0
        assert k["sim.requests"] == SMALL["n_requests"] * k["search.probes"]


def test_untraced_window_with_the_tracer_on_reports_no_breakdown(
        small_cell):
    r = program_trace.run_window(small_cell("resnet152.grid"), 9, 0.2,
                                 False, True)
    assert r["correct"] and r["tracing"] and not r["traced"]
    assert "program" not in r and set(r["metrics"]) == {
        "sweep_points_per_s", "sweep_p95_ms"}


def test_device_ops_are_moved_onto_the_host_clock_by_the_kernel_bounds():
    # each kernel runs between its enqueue's start and its fetch's end;
    # the trace shows the device 3 ms early
    early = 3 * MS
    program, ops = [], []
    for q in range(3):
        t = q * 10 * MS
        program += [("sweep.put", t, t + 1 * MS),
                    ("sweep.enqueue", t + 1 * MS, t + 2 * MS),
                    ("sweep.fetch", t + 2 * MS, t + 7 * MS)]
        # the kernel really runs [t + 1.5, t + 6.5] ms
        ops.append(("%dse_eval.1", t + 1_500_000 - early,
                    t + 6_500_000 - early, 0))
    window = (0, 30 * MS)
    # from t + 1 - (t - 1.5) to t + 7 - (t + 3.5): 2.5 to 3.5 ms
    assert program_trace.clock_offset(ops, program) == (2_500_000,
                                                        3_500_000)
    r = program_trace.reduce(ops, [("window", 0, 30 * MS)], program,
                             window)
    # shifted by 3 ms the kernel covers [t + 1.5, t + 6.5] exactly
    assert r["aligned"]["idle_s"] == pytest.approx(
        {"sweep.put": 3e-3, "sweep.enqueue": 1.5e-3, "sweep.fetch": 1.5e-3,
         "none": 9e-3})
    # unshifted, the kernel seems to start before its own dispatch, and
    # the fetch seems to idle 3.5 ms of its 5
    assert r["program"]["idle_s"]["sweep.fetch"] == pytest.approx(
        3 * 3.5e-3)
    # a kernel the spans cannot hold, or a missing one, gives no bounds
    assert program_trace.clock_offset(ops[:2], program) is None
    assert program_trace.clock_offset(
        [("%dse_eval.1", 0, 20 * MS, 0)] + ops[1:], program) is None
