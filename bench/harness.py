"""One run of one benchmark cell: set-up, a closed-loop window, the
correctness check, and the result line.

Everything that belongs to a configuration, a traffic mix or a metric is
found by its name in `BENCHMARK.json`:

* `bench/configs/<config>.json` holds the configuration as it is run; its
  `lowering` names the plain reference lowering
  `bench/reference/lower_<lowering>.py`;
* `bench/traffic/<traffic>.json` holds the mix's parameters; its `kind`
  names the query kind `bench/queries/<kind>.py` that builds, runs and
  checks one query;
* `bench/metrics/<name>.py` reads one metric from a finished run: the
  end-to-end ones from an untraced run, the per-layer ones from a traced
  run.

The window is one client in a closed loop: each query starts when the
previous one has returned, until `seconds` of wall time have passed.
Rates and percentiles are over the queries' own durations.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from collections import defaultdict

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# jax.monitoring events of a backend compile and of lowering to MLIR
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_parts(manifest, workload):
    """(cell, config, mix, query kind, reference lowering) of a workload."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    cfg = load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    kind = load_module(os.path.join(BENCH, "queries", f"{mix['kind']}.py"),
                       f"bench_query_{mix['kind']}")
    lower = load_module(os.path.join(
        BENCH, "reference", f"lower_{cfg['lowering']}.py"),
        f"bench_lower_{cfg['lowering']}").lower
    return cell, cfg, mix, kind, lower


def reported_metrics(manifest, workload, section):
    """The metrics of `section` that this cell reports."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reports(m):
        return workload in m.get("workloads", [workload])

    if section == "end_to_end":
        return [m for m in manifest["end_to_end"] if reports(m)]
    return [m for m in manifest["per_layer"]
            if reports(m) and reports(e2e[m["moves"]])]


class Spans:
    """The benchmark's own host spans: durations per name, and, when the
    profiler runs, the same spans written into its trace."""

    def __init__(self, traced):
        self.traced = traced
        self.seconds = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name].append(time.perf_counter() - t0)


class RunView:
    """What a metric reader may read from a finished run: the queries'
    durations and counts, the benchmark's spans, the program's counter
    deltas over the window and, in a traced run, the trace reduction."""

    def __init__(self, setup_s, durations, points, elements, spans,
                 counters, trace):
        self.setup_s = setup_s
        self.durations = durations
        self.queries = len(durations)
        self.points = points
        self.elements = elements
        self.spans = spans.seconds
        self.counters = counters
        self.trace = trace

    def span_mean_ms(self, name):
        d = self.spans.get(name)
        return 1e3 * float(np.mean(d)) if d else None

    def idle_share(self):
        t = self.trace
        if not t or t["busy_s"] <= 0:
            return None
        return 1.0 - t["busy_s"] / t["window_s"]


def _device(require_tpu, chips):
    import jax
    devs = jax.devices()
    if require_tpu and (jax.default_backend() != "tpu" or len(devs) < chips):
        print(f"bench: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} {jax.default_backend()} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _compile_cache():
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Cell:
    """A cell after set-up: its parts, its program state warmed up, and
    the compile events counted since."""

    def __init__(self, workload, *, require_tpu=True, mix_override=None):
        self.workload = workload
        self.manifest = load_json(ROOT, "BENCHMARK.json")
        (self.cell, self.cfg, mix, self.kind,
         self.lower) = cell_parts(self.manifest, workload)
        self.mix = dict(mix, **(mix_override or {}))
        setup = {}
        t = time.perf_counter()
        import jax
        from repro.obs.metrics import metrics
        self.jax, self.metrics = jax, metrics
        setup["import_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.device = _device(require_tpu, self.cell["chips"])
        setup["backend_s"] = time.perf_counter() - t
        setup["cache_dir"] = _compile_cache()
        self.compiles = defaultdict(float)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        t = time.perf_counter()
        self.state = self.kind.prepare(self.cfg, self.mix, self.lower)
        setup["prepare_s"] = time.perf_counter() - t
        setup.update(self.state.get("setup", {}))
        t = time.perf_counter()
        nospan = Spans(False).span
        for params in self.kind.variants(self.state):
            self.kind.run(self.state, params, nospan)
        setup["warmup_s"] = time.perf_counter() - t
        setup["compiles"] = self.compile_count()
        setup["compile_s"] = sum(v for k, v in self.compiles.items()
                                 if k.endswith(":s"))
        self.setup = setup

    def _on_event(self, event, duration, **_kw):
        if event in COMPILE_EVENTS:
            self.compiles[event + ":n"] += 1
            self.compiles[event + ":s"] += duration

    def compile_count(self):
        return int(sum(v for k, v in self.compiles.items()
                       if k.endswith(":n")))

    def window(self, seed, seconds, traced=False, replace_kept=None):
        """One closed-loop window. Returns its durations, counts, kept
        answers, spans, counter deltas and (traced) profiler directory.
        `replace_kept(state, params)`, when given, stands in for every
        kept answer of the timed path (the control)."""
        kind, state = self.kind, self.state
        rng = np.random.default_rng([seed, 0])
        keep_rng = np.random.default_rng([seed, 1])
        sample = int(self.mix["check_sample"])
        w = {"kept": [], "spans": Spans(traced), "durations": [],
             "points": 0, "elements": 0, "failed": 0, "log_dir": None}
        before = self.metrics().snapshot()
        compiled = self.compile_count()
        if traced:
            w["log_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(w["log_dir"],
                                          profiler_options=opts)
        t_first = time.perf_counter()
        span = w["spans"].span
        queue = []
        with span("window"):
            while True:
                params = kind.draw(state, rng, queue)
                t0 = time.perf_counter()
                try:
                    with span("query"):
                        answer = kind.run(state, params, span)
                except Exception:
                    w["failed"] += 1
                    traceback.print_exc()
                    answer = None
                w["durations"].append(time.perf_counter() - t0)
                w["points"] += kind.points(state, params)
                w["elements"] += kind.elements(state, params)
                if answer is not None:
                    i = len(w["durations"]) - 1
                    j = i if i < sample else int(keep_rng.integers(i + 1))
                    if j < sample:
                        w["kept"][j:j + 1] = [
                            kind.keep(state, params, answer)
                            if replace_kept is None
                            else replace_kept(state, params)]
                if time.perf_counter() - t_first >= seconds:
                    break
        if traced:
            self.jax.profiler.stop_trace()
        after = self.metrics().snapshot()
        w["counters"] = {k: after.get(k, 0) - before.get(k, 0)
                         for k in after}
        w["compiles"] = self.compile_count() - compiled
        w["t_first"] = t_first
        return w

    def integrity(self, w):
        """Facts of the window that a sound run must show: no compile
        inside it, and one kernel dispatch per query; and, where the
        window searched, the replay engine that served its probes."""
        c = w["counters"]
        queries = len(w["durations"])
        dispatches = (c.get("kernels.sweep_dispatches", 0)
                      + c.get("kernels.fused_dispatches", 0))
        if w["compiles"]:
            raise SystemExit(f"bench: {w['compiles']} compile(s) inside "
                             "the measured window")
        if dispatches != queries - w["failed"]:
            raise SystemExit(f"bench: {dispatches} kernel dispatches for "
                             f"{queries - w['failed']} queries, expected "
                             "one each")
        facts = {"window_compiles": w["compiles"],
                 "dispatches_per_query": dispatches / max(queries, 1)}
        if c.get("search.probes", 0):
            # the scalar simulator counts one `sim.replays` per probe, the
            # packed engines count none; on a TPU the packed engine is the
            # native C one (the XLA lockstep engine refuses a TPU)
            facts["engine"] = "scalar" if c.get("sim.replays", 0) \
                else "packed"
        return facts

    def check(self, kept):
        """Each compared number beside its limit."""
        numbers = self.kind.check(self.state, kept)
        return {k: {"value": numbers[k], "limit": lim}
                for k, lim in self.mix["limits"].items()}


def run(workload, seed, seconds, traced, *, t_start=None, require_tpu=True,
        mix_override=None):
    """One run of `workload`; returns the result dict. `mix_override`
    replaces mix keys (small CPU rehearsals)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(workload, require_tpu=require_tpu, mix_override=mix_override)
    print("setup " + json.dumps(cell.setup), file=sys.stderr, flush=True)
    w = cell.window(seed, seconds, traced)
    facts = cell.integrity(w)
    device = dict(cell.device)
    stats = cell.jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    t_check = time.perf_counter()
    checks = cell.check(w["kept"])
    facts["check_s"] = time.perf_counter() - t_check
    durations = w["durations"]
    correct = w["failed"] == 0 and all(
        v["value"] <= v["limit"] for v in checks.values())
    result = {"correct": bool(correct), "attempted": len(durations),
              "failed": w["failed"]}
    red = None
    if traced:
        from bench import trace
        ops, host, n_dev = trace.load(w["log_dir"])
        shutil.rmtree(w["log_dir"], ignore_errors=True)
        win = [s for s in host if s[0] == "window"]
        if win:
            red = trace.reduce(ops, host, (win[0][1], win[0][2]),
                               max(n_dev, 1))
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
    view = RunView(w["t_first"] - t_start, durations, w["points"],
                   w["elements"], w["spans"], w["counters"], red)
    values = {}
    section = "per_layer" if traced else "end_to_end"
    for m in reported_metrics(cell.manifest, workload, section):
        v = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                        "bench_metric_" + m["name"].replace(".", "_")
                        ).read(view)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = device
    if red is not None:
        result["breakdown"] = {"device_ops": trace.top(red["op_s"]),
                               "idle_gaps": trace.top(red["idle_s"])}
    result["run"] = dict(facts, seed=seed, seconds=seconds,
                         window_s=sum(durations), points=w["points"],
                         query_ms=[1e3 * float(np.percentile(durations, p))
                                   for p in (0, 50, 100)],
                         setup=cell.setup)
    result["checks"] = checks
    return result


def emit(result):
    """Print the compared numbers as the last lines of standard error and
    the result as the last line of standard output."""
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
