"""The program's own spans in a benchmark window: where the host time of
a query goes, and which step of the program the device waits on.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> --tracing <list of 0|1>

With the program's wall-clock tracer on (`repro.obs.enable_tracing()`),
each span of the program also writes a JAX profiler annotation
`repro.<span>`. One window per entry of `--tracing` (each on its own
seed, `--seed` plus its place in the list) runs as the harness runs it,
with that tracer on (1) or off (0), and prints one JSON line: the
cell's end-to-end metrics, `correct`, and the program's counters. A
traced window (`--trace 1`) adds the benchmark's per-layer metrics,
read from its own spans alone as in `harness.run`, and a second
reduction of the same trace over the program's spans:

* `self_ms`: per query, the time in which each program span is the
  innermost one open;
* `program_idle_gaps`: device-idle seconds per innermost program span
  ("none" where none is open);
* `covered_idle`: per benchmark span, the share of the device-idle time
  inside it that falls inside a program span;
* `clock_offset_ms`: bounds on how far the device's timestamps lead
  the host's in the trace (below), and `program_idle_gaps_aligned`, the
  program reduction with the device's operations moved onto the host's
  clock by the middle of those bounds;
* `program`: the per-step metrics below, per query.

On the v5e the profiler's device timestamps can lead the host's by a
millisecond or more, a shift that differs from one profiler session to
the next. A sweep kernel can only run between the opening of its
`sweep.enqueue` and the closing of its `sweep.fetch`; over every query
of a window that bounds the shift from both sides. Idle time per
program span is read after the shift (`fetch_idle_ms.sweep` too): at the
sweeps' sub-millisecond steps the raw reduction puts idle time in the
wrong span.

The benchmark's own runs never run this; the harness neither turns the
program's tracer on nor reads its spans.
"""
import argparse
import glob
import json
import os
import shutil
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace  # noqa: E402

SPAN_PREFIX = "repro."
# the sweep kernels' device operations (`%dse_eval.N`,
# `%dse_eval_batched.N`), as `metrics/kernel_ns_per_elem.sweep.py` reads
KERNEL = "%dse_eval"

# per-step metrics: ms per query of a program span's self time, by the
# end-to-end metric of the cells they belong to
SELF_MS = {
    "sweep_points_per_s": {
        "put_ms.sweep": "sweep.put", "enqueue_ms.sweep": "sweep.enqueue",
        "fetch_ms.sweep": "sweep.fetch",
        "assemble_ms.sweep": "sweep.assemble"},
    "capacity_points_per_s": {
        "lattice_lower_ms.capacity": "tables.lower",
        "table_assemble_ms.capacity": "tables.assemble",
        "sample_ms.capacity": "search.sample",
        "replay_ms.capacity": "search.replay",
        "score_ms.capacity": "search.score"},
}


def load(log_dir):
    """The program's spans `(name, start, end)` of the newest trace, in
    ns on the profiler's clock, names without the prefix."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    return [(e.name[len(SPAN_PREFIX):], int(e.start_ns),
             int(e.start_ns) + int(e.duration_ns))
            for plane in ProfileData.from_file(paths[-1]).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def clock_offset(ops, program_spans):
    """Bounds `(lo, hi)` in ns on the shift that moves the device's
    operations onto the host's clock, from the k-th sweep kernel, the
    k-th `sweep.enqueue` and the k-th `sweep.fetch` of the trace; None
    where their counts differ or the bounds cross."""
    kern = sorted((s, e) for n, s, e, _ in ops if n.startswith(KERNEL))
    enq = sorted(s for n, s, _ in program_spans if n == "sweep.enqueue")
    fetch = sorted(e for n, _, e in program_spans if n == "sweep.fetch")
    if not kern or not len(kern) == len(enq) == len(fetch):
        return None
    lo = max(q - k[0] for q, k in zip(enq, kern))
    hi = min(f - k[1] for f, k in zip(fetch, kern))
    return (lo, hi) if lo <= hi else None


def self_seconds(spans, window):
    """Seconds inside `window` in which each span name is the innermost
    span open."""
    lo, hi = window
    out = defaultdict(float)
    for s, e, name in trace._segments(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += (e - s) * 1e-9
    return dict(out)


def reduce(ops, bench_spans, program_spans, window, devices=1):
    """The benchmark's reduction from its own spans (`bench`), the same
    reduction over the program's spans (`program`), self seconds per
    program span name (`self_s`) and, per benchmark span, the share of
    its device-idle time that lies inside a program span
    (`covered_idle`), all on the trace's own clocks; and the shift
    bounds of `clock_offset` with the program reduction after the
    middle shift (`offset`, `aligned`; None and the unshifted
    reduction where there are no bounds)."""
    bench = trace.reduce(ops, bench_spans, window, devices)
    both = trace.reduce(ops, bench_spans + [
        (SPAN_PREFIX + n, s, e) for n, s, e in program_spans],
        window, devices)
    covered = {n: 1.0 - both["idle_s"].get(n, 0.0) / v
               for n, v in bench["idle_s"].items() if v > 0}
    program = aligned = trace.reduce(ops, program_spans, window, devices)
    offset = clock_offset(ops, program_spans)
    if offset:
        mid = (offset[0] + offset[1]) // 2
        aligned = trace.reduce([(n, s + mid, e + mid, d)
                                for n, s, e, d in ops],
                               program_spans, window, devices)
    return {"bench": bench, "program": program,
            "self_s": self_seconds(program_spans, window),
            "covered_idle": covered, "offset": offset, "aligned": aligned}


def step_metrics(e2e, red, queries, counters):
    """The per-step metrics of a cell whose end-to-end metrics are
    `e2e`, per query, from a reduction `red` of `queries` queries."""
    out = {}
    ms = 1e3 / queries
    for m in e2e:
        for name, span in SELF_MS.get(m, {}).items():
            v = red["self_s"].get(span)
            out[name] = None if v is None else v * ms
    if "sweep_points_per_s" in e2e:
        out["fetch_idle_ms.sweep"] = \
            red["aligned"]["idle_s"].get("sweep.fetch", 0.0) * ms
    if "capacity_points_per_s" in e2e:
        replay_s = red["self_s"].get("search.replay")
        out["replay_requests_per_s.capacity"] = (
            counters.get("sim.requests", 0) / replay_s if replay_s
            else None)
    return out


def run_window(cell, seed, seconds, traced, tracing):
    """One window of `cell` with the program's tracer on or off; returns
    the result dict printed for it."""
    from repro import obs
    if tracing:
        obs.enable_tracing()
    try:
        w = cell.window(seed, seconds, traced)
    finally:
        obs.disable_tracing()
    cell.integrity(w)
    checks = cell.check(w["kept"])
    n = len(w["durations"])
    c = w["counters"]
    e2e = [m["name"] for m in harness.reported_metrics(
        cell.manifest, cell.workload, "end_to_end")]
    out = {"workload": cell.workload, "seed": seed, "traced": traced,
           "tracing": bool(tracing), "queries": n,
           "correct": w["failed"] == 0 and all(
               v["value"] <= v["limit"] for v in checks.values()),
           "counters": {k: c.get(k, 0) for k in (
               "search.probes", "search.lockstep_rounds", "sim.requests",
               "sim.replays", "kernels.sweep_dispatches",
               "kernels.fused_dispatches")}}
    red = None
    if traced:
        ops, bench_spans, n_dev = trace.load(w["log_dir"])
        program_spans = load(w["log_dir"])
        shutil.rmtree(w["log_dir"], ignore_errors=True)
        (win,) = [s for s in bench_spans if s[0] == "window"]
        red = reduce(ops, bench_spans, program_spans, (win[1], win[2]),
                     max(n_dev, 1))
        out["self_ms"] = {k: 1e3 * v / n
                          for k, v in sorted(red["self_s"].items())}
        out["program_idle_gaps"] = trace.top(red["program"]["idle_s"])
        out["clock_offset_ms"] = red["offset"] and [
            1e-6 * v for v in red["offset"]]
        out["program_idle_gaps_aligned"] = trace.top(
            red["aligned"]["idle_s"])
        out["covered_idle"] = red["covered_idle"]
        out["idle_gaps"] = trace.top(red["bench"]["idle_s"])
        out["program"] = step_metrics(e2e, red, n, c)
    view = harness.RunView(0.0, w["durations"], w["points"], w["elements"],
                           w["spans"], c, red and red["bench"])
    out["metrics"] = {}
    for section in ("end_to_end", "per_layer") if traced else \
            ("end_to_end",):
        for m in harness.reported_metrics(cell.manifest, cell.workload,
                                          section):
            if m["name"] == "setup_s":
                continue
            out["metrics"][m["name"]] = harness.load_module(
                os.path.join(harness.BENCH, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_")).read(view)
    out["span_ms"] = {k: view.span_mean_ms(k) for k in sorted(view.spans)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tracing", default="1",
                    help="comma-separated 0|1 per window: the program's "
                         "tracer off or on")
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    for i, on in enumerate(a.tracing.split(",")):
        print(json.dumps(run_window(cell, a.seed + i, a.seconds,
                                    bool(a.trace), on == "1")), flush=True)


if __name__ == "__main__":
    main()
