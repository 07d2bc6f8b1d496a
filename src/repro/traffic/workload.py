"""Serving-traffic workload models: arrival processes + length mixes.

The scenario matrix (repro.scenarios) freezes the serving mix into static
(phase, batch, seq) cells; production serving is a *process* — requests
arrive over time, queue, and leave at different lengths. This module
generates the request traces the discrete-event simulator (traffic/sim.py)
replays:

  * arrival processes — ``poisson`` (memoryless steady load), ``mmpp``
    (2-state Markov-modulated Poisson: bursty load with exponential
    sojourns between a low-rate and a high-rate regime, the classic
    burstiness model), and exact ``trace`` replay of recorded arrival
    times;
  * length distributions — ``lognormal`` prompt/output lengths (the
    standard fit for production LM traffic) and ``buckets`` (an empirical
    histogram over discrete lengths).

Everything draws from an explicit ``np.random.Generator`` seeded by the
caller, so a (model, n, seed) triple always produces the same trace —
golden fixtures and the SLO bisection both depend on that determinism.

The MMPP sampler is a loop over sojourns, and a low-rate probe of the
capacity bisection spans tens of thousands of them. Where the system C
compiler links it against the installed numpy's ``libnpyrandom.a``, the
loop runs in C (`traffic.native`) on the Generator's own bit generator,
calling numpy's own exponential and poisson and the uniform's
``next_double`` in the Python loop's order: the same bytes, draw for
draw, and the same Generator state afterwards, so the length draws that
follow are unchanged too. Otherwise the Python loop runs. The counters
``sample.mmpp_native`` / ``sample.mmpp_fallback`` say which did.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.obs.metrics import metrics as _obs_metrics

ARRIVALS = ("poisson", "mmpp", "trace", "scheduled")
LENGTHS = ("lognormal", "buckets", "const")


# ------------------------------------------------- non-stationary schedules --

@dataclasses.dataclass(frozen=True)
class RateSchedule:
    """A deterministic time-varying arrival-rate profile λ(t) for
    ``arrival="scheduled"`` traffic — the non-stationary load the windowed
    telemetry layer (obs/windowed.py) exists to observe.

    The profile is a PRODUCT of multiplicative shapes on a base rate::

        λ(t) = base_qps · seg(t) · (1 + A·sin(2π(t − φ)/P)) · burst(t)

      * ``segments``  — piecewise multipliers ``(start_s, mult)``: each
        applies from its start until the next segment's start (1.0 before
        the first) — staged ramps / step changes;
      * the sinusoid  — the diurnal curve: amplitude ``A ∈ [0, 1)``
        around the base (never touching zero, so the profile stays
        invertible), period ``P`` and phase ``φ`` in seconds;
      * ``bursts``    — overlays ``(start_s, duration_s, mult)``: flash
        crowds / incident retries multiplying the rate inside the window.

    Because every shape is multiplicative, ``scaled(f)`` — multiply
    ``base_qps`` by ``f`` — rescales the WHOLE profile while preserving
    its shape exactly, which is what `TrafficModel.with_rate` needs for
    the SLO capacity bisection to probe scheduled traffic honestly
    (mirroring the recorded-trace time-dilation fix).

    Sampling is by inversion of the integrated rate Λ(t): n unit-mean
    exponential gaps accumulate to target masses, and a trapezoid
    integral of λ on a uniform grid (resolution `_grid_dt`, a pure
    function of the shapes) maps mass back to time — a seeded
    (schedule, n, seed) triple is byte-stable, the golden-fixture
    contract."""
    base_qps: float = 1.0
    diurnal_amplitude: float = 0.0
    diurnal_period_s: float = 86400.0
    diurnal_phase_s: float = 0.0
    segments: Tuple[Tuple[float, float], ...] = ()
    bursts: Tuple[Tuple[float, float, float], ...] = ()

    def __post_init__(self):
        if self.base_qps <= 0.0:
            raise ValueError(f"base_qps must be positive, got "
                             f"{self.base_qps}")
        if not 0.0 <= self.diurnal_amplitude < 1.0:
            raise ValueError("diurnal_amplitude must be in [0, 1): an "
                             "amplitude of 1 zeroes the rate and the "
                             "profile stops being invertible")
        if self.diurnal_period_s <= 0.0:
            raise ValueError("diurnal_period_s must be positive")
        starts = [s for s, _ in self.segments]
        if starts != sorted(starts):
            raise ValueError("segments must be sorted by start_s")
        if any(m <= 0.0 for _, m in self.segments):
            raise ValueError("segment multipliers must be positive")
        if any(d <= 0.0 or m <= 0.0 for _, d, m in self.bursts):
            raise ValueError("burst durations and multipliers must be "
                             "positive")

    def rate(self, t) -> np.ndarray:
        """Vectorized instantaneous rate λ(t) in requests/second."""
        t = np.asarray(t, np.float64)
        r = np.full(t.shape, self.base_qps)
        if self.segments:
            starts = np.asarray([s for s, _ in self.segments], np.float64)
            mults = np.asarray([1.0] + [m for _, m in self.segments],
                               np.float64)
            r = r * mults[np.searchsorted(starts, t, side="right")]
        if self.diurnal_amplitude:
            r = r * (1.0 + self.diurnal_amplitude
                     * np.sin(2.0 * np.pi * (t - self.diurnal_phase_s)
                              / self.diurnal_period_s))
        for start, dur, mult in self.bursts:
            r = r * np.where((t >= start) & (t < start + dur), mult, 1.0)
        return r

    def scaled(self, factor: float) -> "RateSchedule":
        """The whole profile multiplied by `factor` — shape-preserving
        (diurnal curve, segments and bursts keep their relative heights
        and their ABSOLUTE positions in time)."""
        if factor <= 0.0:
            raise ValueError(f"factor must be positive, got {factor}")
        return dataclasses.replace(self, base_qps=self.base_qps * factor)

    def mean_qps(self, horizon_s: float) -> float:
        """Trapezoid mean of λ over [0, horizon_s]."""
        if horizon_s <= 0.0:
            raise ValueError("horizon_s must be positive")
        dt = min(self._grid_dt(), horizon_s / 16.0)
        grid = np.linspace(0.0, horizon_s,
                           int(np.ceil(horizon_s / dt)) + 1)
        r = self.rate(grid)
        return float(np.sum(0.5 * (r[1:] + r[:-1])
                            * np.diff(grid))) / horizon_s

    def _grid_dt(self) -> float:
        """Integration-grid resolution: fine enough to resolve the
        sharpest shape present (a pure function of the schedule, so
        sampling stays deterministic)."""
        cand = [self.diurnal_period_s / 16.0]
        if self.diurnal_amplitude:
            cand.append(self.diurnal_period_s / 512.0)
        if self.bursts:
            cand.append(min(d for _, d, _ in self.bursts) / 16.0)
        starts = [s for s, _ in self.segments if s > 0.0]
        if starts:
            gaps = np.diff([0.0] + starts)
            pos = gaps[gaps > 0.0]
            if pos.size:
                cand.append(float(pos.min()) / 16.0)
        return max(min(cand), 1e-6)

    def arrivals(self, n: int, rng: np.random.Generator) -> np.ndarray:
        """(n,) sorted arrival times of a non-homogeneous Poisson process
        with intensity λ(t), by inversion: unit-rate exponential gaps
        accumulate to target masses E_k, and t_k = Λ⁻¹(E_k) via linear
        interpolation of the trapezoid-integrated rate."""
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        targets = np.cumsum(rng.exponential(1.0, n))
        dt = self._grid_dt()
        # open the integration horizon until the integrated mass covers
        # the last target (doubling; multipliers are positive, so Λ is
        # strictly increasing and this terminates)
        t_end = max(float(targets[-1]) / self.base_qps, dt)
        while True:
            grid = np.linspace(0.0, t_end,
                               int(np.ceil(t_end / dt)) + 1)
            r = self.rate(grid)
            cum = np.concatenate(
                [[0.0], np.cumsum(0.5 * (r[1:] + r[:-1]) * np.diff(grid))])
            if cum[-1] >= targets[-1]:
                break
            t_end *= 2.0
        return np.interp(targets, cum, grid)


# ------------------------------------------------------- arrival processes --

def poisson_arrivals(rate_qps: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """(n,) sorted arrival times of a Poisson process at `rate_qps`."""
    if rate_qps <= 0.0:
        raise ValueError(f"rate_qps must be positive, got {rate_qps}")
    return np.cumsum(rng.exponential(1.0 / rate_qps, n))


def mmpp_arrivals(rate_lo: float, rate_hi: float, n: int,
                  rng: np.random.Generator, mean_sojourn_s: float = 10.0
                  ) -> np.ndarray:
    """(n,) arrival times of a 2-state Markov-modulated Poisson process.

    The modulating chain alternates between a low-rate and a high-rate
    state with exponential sojourns of mean `mean_sojourn_s`; within a
    sojourn arrivals are Poisson at the state's rate (uniform order
    statistics over the sojourn). Index-of-dispersion > 1 — burstier than
    any single Poisson at the same mean rate.

    The compiled sampler (`traffic.native.mmpp_sampler`) runs the sojourn
    loop when it built, else `_mmpp_arrivals_py` does; both give the same
    bytes and leave `rng` in the same state.
    """
    if not (0.0 < rate_lo <= rate_hi):
        raise ValueError(f"need 0 < rate_lo <= rate_hi, got "
                         f"({rate_lo}, {rate_hi})")
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    from repro.traffic import native      # native imports this module
    lib = native.mmpp_sampler()
    if lib is None:
        _obs_metrics().inc("sample.mmpp_fallback")
        return _mmpp_arrivals_py(rate_lo, rate_hi, n, rng, mean_sojourn_s)
    _obs_metrics().inc("sample.mmpp_native")
    return native.mmpp_arrivals(lib, rate_lo, rate_hi, n, rng,
                                mean_sojourn_s)


def _mmpp_arrivals_py(rate_lo: float, rate_hi: float, n: int,
                      rng: np.random.Generator, mean_sojourn_s: float = 10.0
                      ) -> np.ndarray:
    """The sojourn loop in Python: one iteration per sojourn, each a
    scalar exponential and poisson draw, then the sojourn's uniforms."""
    if not (0.0 < rate_lo <= rate_hi):
        raise ValueError(f"need 0 < rate_lo <= rate_hi, got "
                         f"({rate_lo}, {rate_hi})")
    out = []
    t, hi, total = 0.0, False, 0
    while total < n:
        dwell = rng.exponential(mean_sojourn_s)
        rate = rate_hi if hi else rate_lo
        k = int(rng.poisson(rate * dwell))
        need = n - total
        if k > need:
            # the trace ends inside this sojourn: draw only the `need`
            # arrivals still wanted, over a window shrunk so the state's
            # LOCAL rate is preserved (k arrivals per dwell ~ need
            # arrivals per dwell*need/k) — never materialize the billions
            # of samples an extreme-rate probe would otherwise imply.
            out.append(t + np.sort(rng.uniform(0.0, dwell * need / k,
                                               need)))
            total = n
        elif k:
            out.append(t + np.sort(rng.uniform(0.0, dwell, k)))
            total += k
        t += dwell
        hi = not hi
    return np.concatenate(out)[:n]


# ------------------------------------------------------ length distributions --

def lognormal_lengths(median: float, sigma: float, lo: int, hi: int, n: int,
                      rng: np.random.Generator) -> np.ndarray:
    """(n,) int32 lengths ~ round(LogNormal(ln median, sigma)), clipped to
    [lo, hi] (lo >= 1: zero-length prompts/outputs are not a request)."""
    if lo < 1 or hi < lo:
        raise ValueError(f"need 1 <= lo <= hi, got ({lo}, {hi})")
    x = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(x), lo, hi).astype(np.int32)


def bucket_lengths(buckets: Sequence[int], probs: Sequence[float], n: int,
                   rng: np.random.Generator) -> np.ndarray:
    """(n,) int32 lengths drawn from an empirical histogram."""
    buckets = np.asarray(buckets, np.int32)
    probs = np.asarray(probs, np.float64)
    if buckets.ndim != 1 or probs.shape != buckets.shape:
        raise ValueError("buckets and probs must be equal-length 1-d")
    if (probs < 0).any() or probs.sum() <= 0:
        raise ValueError("probs must be non-negative with positive sum")
    return rng.choice(buckets, size=n, p=probs / probs.sum())


# ------------------------------------------------------------ trace object --

@dataclasses.dataclass
class RequestTrace:
    """A concrete replayable request stream (the simulator input).

    The optional shared-prefix axis marks requests whose prompt BEGINS
    with a template shared across requests (system prompts, few-shot
    headers): `prefix_id[i] >= 0` names the template population and
    `prefix_len[i]` counts its tokens, already INCLUDED in
    `prompt_len[i]`. `-1`/`0` mean an unshared prompt. The axis is pure
    annotation — a simulator that ignores it replays the exact same
    work, which is what keeps the no-reuse goldens byte-identical.

    The optional tenant axis (`tenant_id[i] >= 0` names a priority
    class) is annotation in the same sense: the engine replays identical
    work, and the windowed telemetry layer (obs/windowed.py) splits
    per-window QPS/goodput accounting by class."""
    arrival_s: np.ndarray       # (n,) float64, sorted
    prompt_len: np.ndarray      # (n,) int32, >= 1
    output_len: np.ndarray      # (n,) int32, >= 1 decode steps per request
    prefix_id: Optional[np.ndarray] = None    # (n,) int32, -1 = unshared
    prefix_len: Optional[np.ndarray] = None   # (n,) int32, part of prompt
    tenant_id: Optional[np.ndarray] = None    # (n,) int32 priority class

    def __post_init__(self):
        n = len(self.arrival_s)
        if len(self.prompt_len) != n or len(self.output_len) != n:
            raise ValueError("trace arrays must share one length")
        if self.tenant_id is not None and len(self.tenant_id) != n:
            raise ValueError("trace arrays must share one length")
        if n and (np.diff(self.arrival_s) < 0).any():
            raise ValueError("arrival_s must be sorted")
        if n and (int(self.prompt_len.min()) < 1
                  or int(self.output_len.min()) < 1):
            raise ValueError("prompt_len/output_len must be >= 1")
        if (self.prefix_id is None) != (self.prefix_len is None):
            raise ValueError("prefix_id and prefix_len come together")
        if self.prefix_id is not None:
            if len(self.prefix_id) != n or len(self.prefix_len) != n:
                raise ValueError("trace arrays must share one length")
            if n and int(self.prefix_len.min()) < 0:
                raise ValueError("prefix_len must be >= 0")
            # the prefix is a PART of the prompt, and at least one
            # non-template token must remain to prefill on a cache hit
            if n and (self.prefix_len >= self.prompt_len).any():
                raise ValueError("prefix_len must be < prompt_len")

    def __len__(self) -> int:
        return len(self.arrival_s)

    @property
    def offered_qps(self) -> float:
        """Mean offered request rate of the trace."""
        span = float(self.arrival_s[-1] - self.arrival_s[0])
        return len(self) / span if span > 0 else float("inf")

    @property
    def total_tokens(self) -> int:
        return int(self.prompt_len.sum() + self.output_len.sum())


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """A named, seedable traffic generator: arrival process x length mix.

    ``sample(n, seed)`` is a pure function of (self, n, seed). ``rate_qps``
    scales the arrival process (for mmpp it is the MEAN rate; the lo/hi
    regime rates keep their ratio), which is what the SLO capacity
    bisection (traffic/slo.py) sweeps.
    """
    arrival: str = "poisson"            # poisson | mmpp | trace
    rate_qps: float = 1.0
    burst_ratio: float = 4.0            # mmpp: rate_hi / rate_lo
    mean_sojourn_s: float = 10.0        # mmpp regime dwell
    trace_arrival_s: Optional[Tuple[float, ...]] = None   # arrival="trace"
    # prompt lengths
    prompt_dist: str = "lognormal"      # lognormal | buckets | const
    prompt_median: float = 512.0
    prompt_sigma: float = 0.8
    prompt_range: Tuple[int, int] = (16, 4096)
    prompt_buckets: Optional[Tuple[int, ...]] = None
    prompt_probs: Optional[Tuple[float, ...]] = None
    # output lengths (decode steps per request)
    output_dist: str = "lognormal"
    output_median: float = 128.0
    output_sigma: float = 0.7
    output_range: Tuple[int, int] = (1, 2048)
    output_buckets: Optional[Tuple[int, ...]] = None
    output_probs: Optional[Tuple[float, ...]] = None
    # shared-prefix populations (system prompts / few-shot templates):
    # population k PREPENDS `prefix_lens[k]` template tokens to a
    # `prefix_probs[k]` share of requests (the sampled prompt length is
    # the request's unique part). Remaining mass is unshared. None (the
    # default) disables the axis and changes no draw.
    prefix_lens: Optional[Tuple[int, ...]] = None
    prefix_probs: Optional[Tuple[float, ...]] = None
    # non-stationary scheduled arrivals (arrival="scheduled"): the
    # RateSchedule IS the rate — `rate_qps` mirrors `schedule.base_qps`
    # via with_rate and is otherwise ignored by sample(). None (the
    # default) leaves every other arrival kind byte-identical.
    schedule: Optional[RateSchedule] = None
    # per-tenant priority classes: request i draws class k with
    # probability tenant_probs[k] from its OWN child stream ([seed, 4] —
    # disjoint from arrivals/lengths/prefixes, so enabling the axis
    # changes no other draw). Pure annotation; the windowed telemetry
    # layer splits accounting by class. Names default to "t0", "t1", ...
    tenant_probs: Optional[Tuple[float, ...]] = None
    tenant_names: Optional[Tuple[str, ...]] = None

    def with_rate(self, rate_qps: float) -> "TrafficModel":
        """Rescale the arrival process to `rate_qps`. For synthetic
        arrivals (poisson/mmpp) only the rate field changes; recorded
        traces rescale their timestamps by the rate ratio (time-dilating
        the recording, the standard trace-replay load knob) — leaving
        them untouched would make every rate probe of the SLO bisection
        replay identical arrivals. Scheduled traffic rescales its WHOLE
        profile shape-preservingly (`RateSchedule.scaled`, anchored at
        `schedule.base_qps`) for the same reason: a probe that changed
        only `rate_qps` would replay the exact same diurnal arrivals and
        the capacity bisection would never move."""
        rate_qps = float(rate_qps)
        if rate_qps <= 0.0:
            raise ValueError(f"rate_qps must be positive, got {rate_qps}")
        if self.arrival == "scheduled" and self.schedule is not None:
            if rate_qps == self.schedule.base_qps:
                return dataclasses.replace(self, rate_qps=rate_qps)
            return dataclasses.replace(
                self, rate_qps=rate_qps,
                schedule=self.schedule.scaled(
                    rate_qps / self.schedule.base_qps))
        if self.arrival == "trace" and self.trace_arrival_s is not None \
                and rate_qps != self.rate_qps:
            if self.rate_qps <= 0.0:
                raise ValueError("cannot rescale a trace with nonpositive "
                                 f"rate_qps {self.rate_qps}")
            scale = self.rate_qps / rate_qps
            return dataclasses.replace(
                self, rate_qps=rate_qps,
                trace_arrival_s=tuple(t * scale
                                      for t in self.trace_arrival_s))
        return dataclasses.replace(self, rate_qps=rate_qps)

    def _typical(self, which: str) -> float:
        dist = getattr(self, f"{which}_dist")
        if dist == "buckets":
            b = np.asarray(getattr(self, f"{which}_buckets"), np.float64)
            p = np.asarray(getattr(self, f"{which}_probs"), np.float64)
            order = np.argsort(b)
            cum = np.cumsum(p[order] / p.sum())
            # upper-median convention (side="right"): the smallest bucket
            # with cumulative mass STRICTLY above 0.5. side="left" is
            # off by one bucket when the mass hits exactly 0.5 — two
            # equal buckets would report the lower one as "typical".
            return float(b[order][np.searchsorted(cum, 0.5, side="right")])
        return float(getattr(self, f"{which}_median"))

    @property
    def typical_prompt(self) -> float:
        """Median prompt length UNDER THE ACTIVE distribution — for
        `buckets` the probability-weighted median of the histogram, not
        the (unused) `prompt_median` field. The saturation estimate that
        brackets the SLO bisection reads this, so bucket mixes get a
        meaningful bracket too. Shared-prefix populations add their
        expected template length (the prefix is part of the prompt)."""
        base = self._typical("prompt")
        if self.prefix_lens is not None:
            base += float(sum(l * p for l, p in zip(self.prefix_lens,
                                                    self.prefix_probs)))
        return base

    @property
    def typical_output(self) -> float:
        return self._typical("output")

    def _lengths(self, which: str, n: int, rng) -> np.ndarray:
        dist = getattr(self, f"{which}_dist")
        if dist == "lognormal":
            lo, hi = getattr(self, f"{which}_range")
            return lognormal_lengths(getattr(self, f"{which}_median"),
                                     getattr(self, f"{which}_sigma"),
                                     lo, hi, n, rng)
        if dist == "buckets":
            return bucket_lengths(getattr(self, f"{which}_buckets"),
                                  getattr(self, f"{which}_probs"), n, rng)
        if dist == "const":
            k = int(getattr(self, f"{which}_median"))
            return np.full(n, k, np.int32)
        raise ValueError(f"unknown {which}_dist {dist!r} (have {LENGTHS})")

    def sample(self, n: int, seed: int = 0, *,
               paired: bool = False) -> RequestTrace:
        """Draw a trace. With ``paired=False`` (the default, and the
        byte-stable contract the golden fixtures pin) one generator
        feeds arrivals then lengths in sequence. With ``paired=True``
        the arrival process and each length mix draw from INDEPENDENT
        child streams of `seed` — common random numbers: two models that
        differ only in their arrival process (a heterogeneous per-arch
        mix) or rate (the SLO bisection's probes) see the exact same
        prompt/output length draws, so fleet-vs-single-array and
        arch-vs-arch comparisons are paired rather than confounded by
        how much entropy the arrival sampler happened to consume."""
        if paired:
            rng, rng_p, rng_o = (np.random.default_rng([seed, k])
                                 for k in range(3))
        else:
            rng = rng_p = rng_o = np.random.default_rng(seed)
        if self.arrival == "poisson":
            arr = poisson_arrivals(self.rate_qps, n, rng)
        elif self.arrival == "mmpp":
            # lo/hi around the mean rate: mean = (lo + hi) / 2 with equal
            # sojourns, so lo = 2 mean / (1 + ratio)
            lo = 2.0 * self.rate_qps / (1.0 + self.burst_ratio)
            arr = mmpp_arrivals(lo, lo * self.burst_ratio, n, rng,
                                mean_sojourn_s=self.mean_sojourn_s)
        elif self.arrival == "trace":
            if self.trace_arrival_s is None:
                raise ValueError("arrival='trace' needs trace_arrival_s")
            arr = np.asarray(self.trace_arrival_s, np.float64)[:n]
            if len(arr) < n:
                raise ValueError(f"trace has {len(arr)} arrivals < n={n}")
        elif self.arrival == "scheduled":
            if self.schedule is None:
                raise ValueError("arrival='scheduled' needs a RateSchedule")
            arr = self.schedule.arrivals(n, rng)
        else:
            raise ValueError(
                f"unknown arrival {self.arrival!r} (have {ARRIVALS})")
        plen = self._lengths("prompt", n, rng_p)
        pfx_id, pfx_len = self._prefixes(n, seed)
        if pfx_len is not None:
            plen = (plen + pfx_len).astype(np.int32)
        return RequestTrace(arrival_s=np.asarray(arr, np.float64),
                            prompt_len=plen,
                            output_len=self._lengths("output", n, rng_o),
                            prefix_id=pfx_id, prefix_len=pfx_len,
                            tenant_id=self._tenants(n, seed))

    def _prefixes(self, n: int, seed: int):
        """Seeded shared-prefix assignment, or (None, None) when the axis
        is off. Draws from its OWN child stream (`[seed, 3]`, disjoint
        from the arrival/length streams in both the sequential and the
        paired layout), so enabling sharing changes neither the arrival
        nor the base-length draws — and probes at different rates see the
        same template assignment (common random numbers)."""
        if self.prefix_lens is None:
            return None, None
        lens = np.asarray(self.prefix_lens, np.int64)
        probs = np.asarray(self.prefix_probs, np.float64)
        if lens.ndim != 1 or probs.shape != lens.shape or len(lens) == 0:
            raise ValueError("prefix_lens and prefix_probs must be "
                             "equal-length non-empty 1-d")
        if (lens < 1).any():
            raise ValueError("prefix_lens must be >= 1")
        total = float(probs.sum())
        if (probs < 0).any() or total > 1.0 + 1e-12:
            raise ValueError("prefix_probs must be non-negative with "
                             "sum <= 1 (remaining mass is unshared)")
        rng = np.random.default_rng([seed, 3])
        p = np.append(probs, max(1.0 - total, 0.0))
        idx = rng.choice(len(lens) + 1, size=n, p=p / p.sum())
        shared = idx < len(lens)
        pfx_len = np.where(shared, np.append(lens, 0)[idx], 0)
        pfx_id = np.where(shared, idx, -1)
        return pfx_id.astype(np.int32), pfx_len.astype(np.int32)

    def _tenants(self, n: int, seed: int) -> Optional[np.ndarray]:
        """Seeded per-tenant class assignment, or None when the axis is
        off. Draws from its OWN child stream (`[seed, 4]`, disjoint from
        every other draw), so attaching tenants changes neither the
        arrival nor the length nor the prefix streams."""
        if self.tenant_probs is None:
            return None
        probs = np.asarray(self.tenant_probs, np.float64)
        if probs.ndim != 1 or len(probs) == 0:
            raise ValueError("tenant_probs must be a non-empty 1-d tuple")
        if (probs < 0).any() or probs.sum() <= 0:
            raise ValueError("tenant_probs must be non-negative with "
                             "positive sum")
        if self.tenant_names is not None \
                and len(self.tenant_names) != len(probs):
            raise ValueError("tenant_names must match tenant_probs")
        rng = np.random.default_rng([seed, 4])
        return rng.choice(len(probs), size=n,
                          p=probs / probs.sum()).astype(np.int32)

    @property
    def tenant_labels(self) -> Optional[Tuple[str, ...]]:
        """Display names of the tenant classes ("t0", "t1", ... when
        `tenant_names` is unset); None when the axis is off."""
        if self.tenant_probs is None:
            return None
        if self.tenant_names is not None:
            return tuple(self.tenant_names)
        return tuple(f"t{k}" for k in range(len(self.tenant_probs)))


@dataclasses.dataclass(frozen=True)
class KVReuseConfig:
    """The cross-request KV-reuse scenario knob, bundling the traffic
    axis (what share of requests draw a shared template, how long) with
    the engine axis (how much prefix cache the server keeps). The DSE
    sweeps (`core.dse.slo_capacity_sweep`/`fleet_capacity_sweep`) accept
    one of these as `cache_hit`; `share=0.0` is the exact no-reuse
    baseline (no field of traffic or sim changes)."""
    share: float = 0.5          # request share drawing a shared prefix
    prefix_len: int = 512       # template length (tokens)
    n_prefixes: int = 4         # distinct template populations
    cache_mib: float = 256.0    # server prefix-cache capacity (MiB of KV)

    def __post_init__(self):
        if not 0.0 <= self.share <= 1.0:
            raise ValueError(f"share must be in [0, 1], got {self.share}")
        if self.prefix_len < 1 or self.n_prefixes < 1:
            raise ValueError("prefix_len and n_prefixes must be >= 1")
        if self.cache_mib <= 0.0:
            raise ValueError("cache_mib must be positive")

    def apply(self, tm: TrafficModel) -> TrafficModel:
        """`tm` with this knob's shared-prefix populations attached
        (equal shares across `n_prefixes` templates); identity at
        share=0."""
        if self.share == 0.0:
            return tm
        if tm.prefix_lens is not None:
            raise ValueError("traffic model already carries shared-prefix "
                             "populations; applying a KVReuseConfig on "
                             "top would silently overwrite them")
        return dataclasses.replace(
            tm,
            prefix_lens=(int(self.prefix_len),) * self.n_prefixes,
            prefix_probs=(float(self.share) / self.n_prefixes,)
            * self.n_prefixes)
