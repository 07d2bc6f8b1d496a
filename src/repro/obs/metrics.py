"""Named counters and log-spaced histograms for the whole DSE stack.

The registry is the *accounting* half of the observability layer: where
the tracer answers "when did it happen", the registry answers "how many
times" — model evaluations, fused kernel dispatches, bisection probes,
lockstep rounds, simulator events, cost-table interpolations, spill
round trips. Counters turn docstring claims ("ONE fused dispatch",
"O(events) not O(tokens)", "zero model evals in the replay loop") into
numbers tests can assert on.

Always on, unlike the tracer, because every increment happens at CALL
granularity (once per sweep / replay / dispatch), never per simulated
event: the simulators accumulate plain local ints inside their hot loops
and publish them in one `add_many` when the replay returns, so the
registry costs nothing where time is measured.

Counter catalog (the names the stack emits; see README "Observability"):

    model.network_evals        analyze_network calls (closed-form evals)
    model.gemm_evals           layer-level closed-form evaluations
    kernels.sweep_dispatches   fused Pallas sweep kernel calls (dse_eval)
    kernels.fused_dispatches   batched-sweep kernel calls (dse_eval_batched)
    kernels.config_lane_dispatches  sweep-kernel calls on the config-lane
                               layout (every call of either kind)
    sweep.layer_rows           real layer rows those calls were given
    sweep.layer_rows_padded    layer rows the kernels walk after padding
                               (each table up to its last real row)
    sim.replays / sim.requests / sim.tokens_out
    sim.events                 discrete-event loop iterations (O(requests))
    sim.decode_steps           engine decode steps charged
    sim.table_lookups          cost-table interpolations (the O(1) lookups)
    sim.spill_steps            steps that paid a DRAM-spill stall
    sim.spill_cycles           total DRAM stall cycles charged
    fleet.replays / fleet.kv_ships
    slo.bisection_probes       scalar capacity-search probe replays
    search.lockstep_rounds     batched-bisection rounds (one packed replay)
    search.probes              lane-probes served by those rounds
    sample.mmpp_native         MMPP traces drawn by the compiled sampler
    sample.mmpp_fallback       MMPP traces drawn by the Python loop
    pareto.masks_sorted        two-objective Pareto masks, by sort and sweep
    pareto.masks_all_pairs     other Pareto masks, by n x n comparison
"""
from __future__ import annotations

import json
import math
from bisect import bisect_right
from typing import Dict, List, Optional, Sequence

__all__ = ["Histogram", "MetricsRegistry", "log_histogram", "metrics",
           "reset_metrics"]


class Histogram:
    """Log-spaced histogram: `buckets_per_decade` bins per factor of ten
    between `lo` and `hi`, plus an underflow and an overflow bin. Compact
    (a few dozen ints) yet percentile-capable — the shape percentiles
    alone cannot carry."""

    __slots__ = ("lo", "hi", "buckets_per_decade", "edges", "counts", "n",
                 "total", "vmin", "vmax")

    def __init__(self, lo: float = 1e-3, hi: float = 1e3,
                 buckets_per_decade: int = 4):
        if not (lo > 0.0 and hi > lo):
            raise ValueError("need 0 < lo < hi")
        self.lo = float(lo)
        self.hi = float(hi)
        self.buckets_per_decade = int(buckets_per_decade)
        n_edges = int(round(math.log10(hi / lo) * buckets_per_decade)) + 1
        self.edges = [lo * 10.0 ** (k / buckets_per_decade)
                      for k in range(n_edges)]
        # counts[0] = underflow (< lo); counts[-1] = overflow (>= hi)
        self.counts = [0] * (len(self.edges) + 1)
        self.n = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, value: float, count: int = 1) -> None:
        v = float(value)
        self.counts[bisect_right(self.edges, v)] += count
        self.n += count
        self.total += v * count
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def observe_many(self, values: Sequence[float]) -> None:
        """Bulk observe; uses numpy when given an array (the slo.summarize
        path observes thousands of latency samples at once)."""
        try:
            import numpy as np
        except ImportError:                              # pragma: no cover
            for v in values:
                self.observe(v)
            return
        x = np.asarray(values, np.float64)
        x = x[np.isfinite(x)]
        if x.size == 0:
            return
        idx = np.searchsorted(self.edges, x, side="right")
        for i, c in zip(*np.unique(idx, return_counts=True)):
            self.counts[int(i)] += int(c)
        self.n += int(x.size)
        self.total += float(x.sum())
        self.vmin = min(self.vmin, float(x.min()))
        self.vmax = max(self.vmax, float(x.max()))

    def merge(self, other: "Histogram") -> "Histogram":
        """Bucket-wise sum of another histogram into this one (in place).

        Requires an identical bucket configuration — merging differently
        shaped histograms would silently mis-bin, so it raises instead.
        This is how `fleet/sim.py` aggregates per-server TTFT/TPOT
        distributions fleet-wide without re-observing raw samples."""
        if (self.lo, self.hi, self.buckets_per_decade) != (
                other.lo, other.hi, other.buckets_per_decade):
            raise ValueError(
                "bucket config mismatch: "
                f"(lo={self.lo}, hi={self.hi}, "
                f"bpd={self.buckets_per_decade}) vs "
                f"(lo={other.lo}, hi={other.hi}, "
                f"bpd={other.buckets_per_decade})")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.n += other.n
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        return self

    def quantile(self, q: float, interp: bool = False) -> float:
        """Approximate quantile from the bucket CDF.

        The default (`interp=False`, unchanged behavior) returns the upper
        edge of the bucket holding the q-th sample — a conservative bound
        whose error is the full bucket width. `interp=True` places the
        quantile linearly WITHIN that bucket by its share of the bucket's
        mass, shrinking the error well below the bucket ratio on smooth
        distributions (property-tested against `numpy.percentile`). The
        open-ended underflow/overflow buckets interpolate between the
        observed extreme (`vmin`/`vmax`) and the nearest finite edge."""
        if self.n == 0:
            return math.nan
        target = q * self.n
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target and c:
                if not interp:
                    if i == 0:
                        return self.edges[0]
                    if i >= len(self.edges):
                        return self.vmax
                    return self.edges[i]
                if i == 0:
                    lo_e = min(self.vmin, self.edges[0])
                    hi_e = self.edges[0]
                elif i >= len(self.edges):
                    lo_e = self.edges[-1]
                    hi_e = max(self.vmax, self.edges[-1])
                else:
                    lo_e = self.edges[i - 1]
                    hi_e = self.edges[i]
                frac = (target - (acc - c)) / c
                return lo_e + (hi_e - lo_e) * min(max(frac, 0.0), 1.0)
        return self.vmax

    def to_dict(self) -> Dict:
        """JSON-ready, deterministic (plain ints/floats only)."""
        return {
            "lo": self.lo, "hi": self.hi,
            "buckets_per_decade": self.buckets_per_decade,
            "counts": list(self.counts),
            "n": self.n,
            "mean": (self.total / self.n) if self.n else None,
            "min": self.vmin if self.n else None,
            "max": self.vmax if self.n else None,
        }


def log_histogram(values: Sequence[float], lo: float = 1e-3,
                  hi: float = 1e3, buckets_per_decade: int = 4) -> Dict:
    """One-shot helper: histogram a sample vector into a compact dict
    (the latency-distribution records `traffic.slo.summarize` attaches)."""
    h = Histogram(lo=lo, hi=hi, buckets_per_decade=buckets_per_decade)
    h.observe_many(values)
    return h.to_dict()


class MetricsRegistry:
    """Flat name -> counter / histogram store with snapshot/delta support
    (tests snapshot before an operation and assert on the delta)."""

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}

    # ----------------------------------------------------------- counters --
    def inc(self, name: str, value: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def add_many(self, updates: Dict[str, float]) -> None:
        """Publish a batch of counter increments in one call — the hot-loop
        contract: simulators accumulate local ints, then add_many once."""
        c = self.counters
        for name, value in updates.items():
            c[name] = c.get(name, 0.0) + value

    def get(self, name: str, default: float = 0.0) -> float:
        return self.counters.get(name, default)

    # --------------------------------------------------------- histograms --
    def _get_or_create(self, name: str, lo, hi, buckets_per_decade
                       ) -> Histogram:
        """Shared observe/hist resolution. Bound arguments left at their
        `None` defaults mean "whatever the histogram already uses" (or the
        standard 1e-3..1e3 x 4 when creating); EXPLICIT bounds that
        conflict with an existing histogram's config raise instead of
        being silently ignored — a windowed percentile landing in a
        mis-bucketed histogram would merge garbage."""
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(
                lo=1e-3 if lo is None else lo,
                hi=1e3 if hi is None else hi,
                buckets_per_decade=(4 if buckets_per_decade is None
                                    else buckets_per_decade))
            return h
        for label, want, have in (("lo", lo, h.lo), ("hi", hi, h.hi),
                                  ("buckets_per_decade", buckets_per_decade,
                                   h.buckets_per_decade)):
            if want is not None and want != have:
                raise ValueError(
                    f"histogram {name!r} already exists with "
                    f"{label}={have}, conflicting with requested "
                    f"{label}={want}")
        return h

    def observe(self, name: str, value: float, lo: Optional[float] = None,
                hi: Optional[float] = None,
                buckets_per_decade: Optional[int] = None) -> None:
        self._get_or_create(name, lo, hi, buckets_per_decade).observe(value)

    def hist(self, name: str, lo: Optional[float] = None,
             hi: Optional[float] = None,
             buckets_per_decade: Optional[int] = None) -> Histogram:
        """Get-or-create the named histogram (for bulk `observe_many` —
        the attribution paths observe whole per-request columns at once)."""
        return self._get_or_create(name, lo, hi, buckets_per_decade)

    # ---------------------------------------------------- snapshot / delta --
    def snapshot(self) -> Dict[str, float]:
        return dict(self.counters)

    def delta(self, before: Dict[str, float]) -> Dict[str, float]:
        """Counter movement since `before` (zero-delta names omitted)."""
        out = {}
        for name, v in self.counters.items():
            d = v - before.get(name, 0.0)
            if d:
                out[name] = d
        return out

    # ----------------------------------------------------------- reporting --
    def summarize(self) -> Dict:
        """Deterministic JSON-ready report: sorted counter totals + every
        histogram's compact dict."""
        return {
            "counters": {k: self.counters[k]
                         for k in sorted(self.counters)},
            "histograms": {k: self.histograms[k].to_dict()
                           for k in sorted(self.histograms)},
        }

    def to_json(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.summarize(), indent=indent, sort_keys=True)

    def reset(self) -> None:
        self.counters.clear()
        self.histograms.clear()


_METRICS = MetricsRegistry()


def metrics() -> MetricsRegistry:
    """The process-wide registry every subsystem publishes into."""
    return _METRICS


def reset_metrics() -> MetricsRegistry:
    """Clear the process-wide registry (tests isolate with snapshot/delta
    instead where possible; reset is for benchmark stages)."""
    _METRICS.reset()
    return _METRICS
