"""Device-resident DSE search: fused capacity bisection, on-device NSGA-2,
and a gradient design-point refiner.

The sequential sweeps (`core.dse.slo_capacity_sweep`,
`fleet_capacity_sweep`) answer "what load does each design point sustain?"
by running an independent scalar bisection per point: every probe is one
host replay, and a full 10-arch x DEFAULT_HW lattice costs hundreds of
them back to back. This module restructures that search around ONE
vectorized probe per bisection round:

  * `_BisectLane` transcribes `traffic.slo.bisect_max_qps` probe-for-probe
    into an explicit state machine, so every design point ("lane")
    advances its own bracket while all lanes share a single batched
    replay. The probe SEQUENCE each lane sees is identical to the scalar
    search, and the replays themselves are bit-identical
    (`traffic.native`), so the resulting max-QPS tables match the
    sequential sweep bit for bit.
  * `_TraceFactory` amortizes trace sampling: Poisson probes at different
    rates reuse one cached set of exponential/length draws and rebuild
    only the arrival cumsum (draw-for-draw what
    `TrafficModel.with_rate(q).sample(n, seed)` produces). Arrival
    processes that consume rate-dependent entropy (mmpp) take the full
    sampler per probe; for mmpp its sojourn loop runs in C on the
    Generator's own bit stream (`traffic.native`).
  * `_ServerBatch` picks the replay engine for a batch of fixed tables:
    the native C replay (`traffic.native.NativeBatch`, one call per
    round) when a compiler is present, and the scalar simulator, its
    reference, otherwise. Both produce identical numbers.

`nsga2_device` and `refine_design_point` move the other two search loops
of the DSE onto the device: a fixed-shape NSGA-2 whose jnp generation
loop matches a numpy oracle bitwise, and a `jax.grad` refiner over the
relaxed (continuous-tiling) cost model whose proposals are always
re-verified with the exact closed form.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.metrics import metrics as _obs_metrics
from repro.obs.trace import tracer as _obs_tracer
from repro.traffic.sim import SimConfig, SimResult, simulate
from repro.traffic.slo import (QPS_CAP, SLO, meets_slo, saturation_qps,
                               summarize)
from repro.traffic.workload import RequestTrace, TrafficModel

__all__ = [
    "batched_bisect", "batched_max_sustainable_qps",
    "batched_fleet_max_sustainable_qps", "nsga2_device",
    "refine_design_point",
]


# ------------------------------------------------- lockstep bisection -------

class _BisectLane:
    """One lane of the lockstep capacity search: an explicit state machine
    transcribing `traffic.slo.bisect_max_qps` probe-for-probe. `qps` is
    the rate this lane wants probed next; `feed(ok, result)` consumes the
    probe outcome and advances the bracket. Lanes finish at different
    rounds; a finished lane simply stops requesting probes."""

    __slots__ = ("hi", "lo", "best", "best_res", "iters", "it", "grown",
                 "saturated", "phase", "qps", "q_out", "res_out")

    def __init__(self, hi: float, iters: int):
        self.iters = int(iters)
        self.hi = float(hi)
        self.lo = self.hi / 1024.0
        self.grown = False
        self.saturated = False
        self.best = 0.0
        self.best_res = None
        self.it = 0
        self.q_out = None
        self.res_out = None
        self.phase = "init_lo"
        self.qps = self.lo

    @property
    def done(self) -> bool:
        return self.phase == "done"

    def _finish(self, q: float, res) -> None:
        self.q_out = min(q, QPS_CAP)
        self.res_out = res
        self.phase = "done"

    def _start_bisect(self) -> None:
        self.best = self.lo
        self.best_res = None
        self.it = 0
        if self.iters <= 0:
            self._final_or_finish()
        else:
            self.phase = "bisect"
            self.qps = 0.5 * (self.lo + self.hi)

    def _final_or_finish(self) -> None:
        # scalar tail: re-probe `best` only when no passing mid was seen
        if self.best_res is None:
            self.phase = "final"
            self.qps = self.best
        else:
            self._finish(self.best, self.best_res)

    def feed(self, ok: bool, res) -> None:
        if self.phase == "init_lo":
            if not ok:
                self.saturated = False
                self.q_out = 0.0
                self.res_out = res
                self.phase = "done"
            else:
                self.phase = "open"
                self.qps = self.hi
        elif self.phase == "open":
            if ok:
                self.lo, self.hi = self.hi, 2.0 * self.hi
                if self.hi > QPS_CAP:
                    if self.grown:
                        self.saturated = True
                        self._start_bisect()
                        return
                    self.grown = True
                self.qps = self.hi
            else:
                self.saturated = False
                self._start_bisect()
        elif self.phase == "bisect":
            mid = self.qps
            if ok:
                self.lo = mid
                self.best = mid
                self.best_res = res
            else:
                self.hi = mid
            self.it += 1
            if self.it < self.iters:
                self.qps = 0.5 * (self.lo + self.hi)
            else:
                self._final_or_finish()
        elif self.phase == "final":
            self._finish(self.best, res)
        else:                                            # pragma: no cover
            raise RuntimeError(f"feed() on finished lane ({self.phase})")


def batched_bisect(probe_batch: Callable, brackets: Sequence[float],
                   iters: int = 9) -> Tuple[List[Tuple], int]:
    """Advance every lane's `bisect_max_qps` in lockstep.

    `probe_batch([(lane, qps), ...])` must return `[(ok, result), ...]`
    in the same order — one vectorized replay round. Returns
    (`[(max_qps, result, saturated_at_bracket)] per lane`, rounds)."""
    lanes = [_BisectLane(h, iters) for h in brackets]
    rounds = 0
    n_probes = 0
    tr = _obs_tracer()
    while True:
        reqs = [(i, ln.qps) for i, ln in enumerate(lanes) if not ln.done]
        if not reqs:
            break
        with tr.span("lockstep_round", "bisect", round=rounds,
                     lanes=len(reqs)):
            outs = probe_batch(reqs)
        for (i, _q), (ok, res) in zip(reqs, outs):
            lanes[i].feed(ok, res)
        rounds += 1
        n_probes += len(reqs)
    _obs_metrics().add_many({"search.lockstep_rounds": rounds,
                             "search.probes": n_probes})
    return [(ln.q_out, ln.res_out, ln.saturated) for ln in lanes], rounds


# --------------------------------------------------- probe trace factory ----

class _TraceFactory:
    """Cached probe-trace generation. For Poisson arrivals the exponential
    inter-arrival draws and both length vectors are rate-independent
    (`rng.exponential(s, n)` is draw-for-draw `s * standard_exponential(n)`),
    so probes at different rates reuse one cached draw and rebuild only
    the arrival cumsum — bitwise what
    `TrafficModel.with_rate(q).sample(n, seed, paired=...)` returns.
    Arrival processes that consume rate-dependent entropy and recorded
    traces take the full sampler every probe: `rng.poisson(rate * dwell)`
    consumes a rate-dependent number of draws, so no mmpp draw carries
    over between rates. The mmpp sampler's sojourn loop runs in C where
    `traffic.native.mmpp_sampler` built (same bytes, draw for draw), so
    the bisection's low first rates, whose traces span tens of thousands
    of sojourns, no longer pay a Python iteration per sojourn."""

    def __init__(self):
        self._cache: Dict = {}

    def trace(self, tm: TrafficModel, qps: float, n: int, seed: int,
              paired: bool) -> RequestTrace:
        if (tm.arrival != "poisson" or tm.prefix_lens is not None
                or tm.tenant_probs is not None):
            # prefix-bearing and tenant-bearing models take the full
            # sampler so the cached fast path never silently drops the
            # shared-prefix or tenant axis (scheduled arrivals land here
            # too via the arrival check)
            return tm.with_rate(qps).sample(n, seed, paired=paired)
        key = (dataclasses.replace(tm, rate_qps=1.0), n, seed, paired)
        ent = self._cache.get(key)
        if ent is None:
            if paired:
                rng, rng_p, rng_o = (np.random.default_rng([seed, k])
                                     for k in range(3))
            else:
                rng = rng_p = rng_o = np.random.default_rng(seed)
            ent = (rng.standard_exponential(n),
                   tm._lengths("prompt", n, rng_p),
                   tm._lengths("output", n, rng_o))
            self._cache[key] = ent
        std, plen, olen = ent
        if qps <= 0.0:
            raise ValueError(f"rate_qps must be positive, got {qps}")
        return RequestTrace(arrival_s=np.cumsum(std * (1.0 / qps)),
                            prompt_len=plen, output_len=olen)


# ------------------------------------------------------- packed executor ----

class _ServerBatch:
    """Fixed-lane probe executor: one server (cost table) per lane, one
    shared `SimConfig`. Each round takes `{lane: trace}` jobs for the
    lanes that want a probe.

    Backend selection (`auto`): the runtime-compiled C replay
    (`traffic.native`) when a compiler is present and the config fits its
    limits, else the scalar simulator (an explicit "native" raises
    instead). Both are bit-identical to `traffic.sim.simulate` per lane."""

    def __init__(self, tables: Sequence, cfg: SimConfig, n_max: int,
                 backend: str = "auto"):
        self.tables = list(tables)
        self.cfg = cfg
        self.backend = self._resolve(backend)
        if self.backend == "native":
            from repro.traffic.native import NativeBatch
            self._batch = NativeBatch(self.tables, cfg, n_max)

    def _resolve(self, backend: str) -> str:
        if backend == "scalar":
            return "scalar"
        if backend not in ("auto", "native"):
            raise ValueError(f"unknown backend {backend!r} "
                             "(have auto|native|scalar)")
        tr = self.cfg.tracer
        if tr is not None and tr.enabled:
            return "scalar"                # the packed engine emits no
                                           # events; traced replays take
                                           # the instrumented scalar path
        if self.cfg.policy != "prefill_first":
            return "scalar"                # the packed engine only does
                                           # prefill_first
        if self.cfg.prefix_cache_mib is not None or self.cfg.spec is not None:
            return "scalar"                # KV-reuse / speculative replays
                                           # run the scalar event loop
        if self.cfg.windows is not None:
            return "scalar"                # the packed engine keeps no
                                           # windowed telemetry
        shapes = {(len(t.slot_lattice), len(t.kv_lattice),
                   len(t.prompt_lattice)) for t in self.tables}
        if len(shapes) != 1:
            return "scalar"                # one lattice shape per batch
        from repro.traffic import native
        if native.available() and self.cfg.slots <= 64:
            return "native"
        if backend == "native":
            raise RuntimeError(
                "native backend requested but unavailable "
                "(no C compiler, or slots > 64)")
        return "scalar"

    def run_round(self, jobs: Dict[int, RequestTrace]
                  ) -> Dict[int, SimResult]:
        """Replay one round (span `search.replay`: packing and the packed
        engine, or the scalar simulator) and, on the packed engine, build
        the per-lane results (span `search.score`). The packed engine
        counts the round's requests in `sim.requests`, as the scalar
        simulator does per replay (but no `sim.replays`)."""
        t0 = time.perf_counter()
        tr_obs = _obs_tracer()
        if self.backend == "scalar":
            with tr_obs.span("search.replay", "bisect"):
                return {i: simulate(self.tables[i], tr, self.cfg)
                        for i, tr in jobs.items()}
        with tr_obs.span("search.replay", "bisect"):
            res = self._batch.replay(jobs)
        wall = time.perf_counter() - t0
        _obs_metrics().add_many(
            {"sim.requests": sum(len(tr) for tr in jobs.values())})
        from repro.traffic.native import _to_result
        with tr_obs.span("search.score", "bisect"):
            return {i: _to_result(self.tables[i], tr, self.cfg, res, i,
                                  wall)
                    for i, tr in jobs.items()}


# ------------------------------------------- batched capacity searches ------

def batched_max_sustainable_qps(
        tables: Sequence, traffics: Sequence[TrafficModel], slo: SLO,
        sim: SimConfig = SimConfig(), n_requests: int = 2000, seed: int = 0,
        iters: int = 9, backend: str = "auto",
        stats: Optional[Dict] = None) -> List[Tuple[float, Dict]]:
    """`traffic.slo.max_sustainable_qps` for MANY (table, traffic) design
    points at once: all lanes bisect in lockstep, one packed replay per
    round. Returns `[(max_qps, summary)]` per lane, bit-identical to the
    scalar search (same probe sequences, same replays, same summaries)."""
    tables = list(tables)
    traffics = list(traffics)
    if len(tables) != len(traffics):
        raise ValueError("need one traffic model per table")
    ex = _ServerBatch(tables, sim, n_requests, backend=backend)
    tf = _TraceFactory()
    n_probes = 0
    tr = _obs_tracer()

    def probe_batch(reqs):
        nonlocal n_probes
        n_probes += len(reqs)
        with tr.span("search.sample", "bisect"):
            jobs = {i: tf.trace(traffics[i], q, n_requests, seed, False)
                    for i, q in reqs}
        res = ex.run_round(jobs)
        with tr.span("search.score", "bisect"):
            return [(meets_slo(res[i], slo), res[i]) for i, _ in reqs]

    brackets = [2.0 * saturation_qps(t, tm, sim)
                for t, tm in zip(tables, traffics)]
    out, rounds = batched_bisect(probe_batch, brackets, iters)
    if stats is not None:
        stats.update(backend=ex.backend, rounds=rounds, probes=n_probes,
                     lanes=len(tables))
    final = []
    for q, res, sat in out:
        s = summarize(res, slo)
        s["saturated_at_bracket"] = sat
        final.append((q, s))
    return final


def batched_fleet_max_sustainable_qps(
        fleets: Sequence, traffics: Sequence[TrafficModel], slo: SLO,
        cfgs: Sequence, n_requests: int = 1200, seed: int = 0,
        iters: int = 9, paired: bool = True, backend: str = "auto",
        stats: Optional[Dict] = None) -> List[Tuple[float, Dict]]:
    """`fleet.sim.fleet_max_sustainable_qps` for MANY (fleet, traffic,
    config) lanes at once. Routing and result assembly run the SAME host
    code as the scalar fleet replay (`fleet.sim._disagg_prepare` /
    `_assemble_*`); only the per-server replays are batched — one packed
    engine over the union of every lane's decode-capable servers."""
    from repro.fleet.sim import (_DecodeOnlyTable, _assemble_disagg,
                                 _assemble_mixed, _disagg_prepare,
                                 _sub_trace, fleet_saturation_qps,
                                 route_requests, simulate_fleet)
    fleets = list(fleets)
    traffics = list(traffics)
    cfgs = list(cfgs)
    if not (len(fleets) == len(traffics) == len(cfgs)):
        raise ValueError("need one traffic model and config per fleet")
    tf = _TraceFactory()
    n_probes = 0

    # one global server-lane space over all fleets (packed once)
    lane_tables: List = []
    base: List[int] = []
    dec_tables: List[Optional[List]] = []
    for fl in fleets:
        base.append(len(lane_tables))
        if fl.disaggregated:
            dt = [_DecodeOnlyTable(t) for t in fl.decode]
            dec_tables.append(dt)
            lane_tables.extend(dt)
        else:
            dec_tables.append(None)
            lane_tables.extend(fl.mixed)

    uniform = all(c.server == cfgs[0].server for c in cfgs)
    if uniform:
        ex = _ServerBatch(lane_tables, cfgs[0].server, n_requests,
                          backend=backend)

        def probe_batch(reqs):
            nonlocal n_probes
            n_probes += len(reqs)
            t0 = time.perf_counter()
            ctx, jobs = {}, {}
            for f, q in reqs:
                trace = tf.trace(traffics[f], q, n_requests, seed, paired)
                if fleets[f].disaggregated:
                    prep = _disagg_prepare(fleets[f], trace, cfgs[f],
                                           dec_tables=dec_tables[f])
                    parts, sub = prep["dparts"], prep["dec_trace"]
                else:
                    prep = None
                    parts = route_requests(trace, fleets[f].mixed, cfgs[f])
                    sub = trace
                ctx[f] = (trace, prep, parts)
                for s, idx in enumerate(parts):
                    if len(idx):
                        jobs[base[f] + s] = _sub_trace(sub, idx)
            res = ex.run_round(jobs)
            out = []
            for f, _q in reqs:
                trace, prep, parts = ctx[f]
                results = [res.get(base[f] + s) for s in range(len(parts))]
                if prep is None:
                    fr = _assemble_mixed(fleets[f], trace, cfgs[f], parts,
                                         results, t0)
                else:
                    fr = _assemble_disagg(fleets[f], trace, cfgs[f], prep,
                                          results, t0)
                out.append((meets_slo(fr, slo), fr))
            return out
    else:
        # heterogeneous per-lane server configs: per-lane scalar replay
        # (still one lockstep bisection — fewer sampler calls, same math)
        def probe_batch(reqs):
            nonlocal n_probes
            n_probes += len(reqs)
            out = []
            for f, q in reqs:
                trace = tf.trace(traffics[f], q, n_requests, seed, paired)
                fr = simulate_fleet(fleets[f], trace, cfgs[f])
                out.append((meets_slo(fr, slo), fr))
            return out

    brackets = [2.0 * fleet_saturation_qps(fl, tm, c)
                for fl, tm, c in zip(fleets, traffics, cfgs)]
    out, rounds = batched_bisect(probe_batch, brackets, iters)
    if stats is not None:
        stats.update(backend=ex.backend if uniform else "scalar",
                     rounds=rounds, probes=n_probes, lanes=len(fleets),
                     server_lanes=len(lane_tables))
    final = []
    for f, (q, res, sat) in enumerate(out):
        s = summarize(res, slo)
        s["saturated_at_bracket"] = sat
        s["n_servers"] = fleets[f].n_servers
        s["disaggregated"] = fleets[f].disaggregated
        final.append((q, s))
    return final


# ------------------------------------------------------ on-device NSGA-2 ----
#
# The fixed-shape variant of `core.pareto.nsga2`: no dedup/refill (their
# shapes depend on the data, which jit cannot express), stable sorts
# everywhere, all randomness pre-drawn on the host, and the genome
# evaluation is a gather from a precomputed EXACT objective table over the
# quantized (h, w) grid — gathers are bit-exact on every backend, so the
# jnp generation loop and the numpy oracle agree bit for bit.

def _fnds_fixed(xp, F):
    """Fixed-iteration front ranks (0 = best); unassigned impossible after
    n peels. Integer arithmetic only — exact on both backends."""
    n = F.shape[0]
    dom = ((F[:, None, :] <= F[None, :, :]).all(-1)
           & (F[:, None, :] < F[None, :, :]).any(-1))     # i dominates j
    n_dom = dom.sum(0).astype(np.int64)
    big = np.int64(1) << 40

    def peel(r, ranks, n_dom):
        front = (n_dom == 0) & (ranks == n)
        ranks = xp.where(front, r, ranks)
        n_dom = n_dom - (dom & front[:, None]).sum(0)
        n_dom = xp.where(ranks < n, big, n_dom)
        return ranks, n_dom

    if xp is np:
        ranks = np.full(n, n, np.int64)
        for r in range(n):
            ranks, n_dom = peel(np.int64(r), ranks, n_dom)
        return ranks
    from jax import lax
    ranks0 = xp.full(n, n, xp.int64)
    ranks, _ = lax.fori_loop(
        0, n, lambda r, st: peel(r.astype(xp.int64), *st),
        (ranks0, xp.asarray(n_dom)))
    return ranks


def _crowd_fixed(xp, F):
    """Crowding distance with STABLE per-objective argsorts (the one
    place `core.pareto.crowding_distance` leaves tie order unspecified)."""
    n, k = F.shape
    if xp is np:
        d = np.zeros(n)
        for j in range(k):
            order = np.argsort(F[:, j], kind="stable")
            Fs = F[order, j]
            fmin, fmax = Fs[0], Fs[-1]
            d[order[0]] = d[order[-1]] = np.inf
            if n > 2 and fmax > fmin:
                d[order[1:-1]] += (Fs[2:] - Fs[:-2]) / (fmax - fmin)
        return d
    d = xp.zeros(n)
    for j in range(k):
        order = xp.argsort(F[:, j], stable=True)
        Fs = F[order, j]
        fmin, fmax = Fs[0], Fs[-1]
        d = d.at[order[0]].set(xp.inf)
        d = d.at[order[-1]].set(xp.inf)
        if n > 2:
            contrib = xp.where(fmax > fmin,
                               (Fs[2:] - Fs[:-2]) / (fmax - fmin), 0.0)
            d = d.at[order[1:-1]].add(contrib)
    return d


def _rank_crowd_order(xp, ranks, crowd):
    """`np.lexsort((-crowd, ranks))` as two stable passes (jnp has no
    lexsort; two-pass stable argsort is the same total order)."""
    if xp is np:
        order = np.argsort(-crowd, kind="stable")
        return order[np.argsort(ranks[order], kind="stable")]
    order = xp.argsort(-crowd, stable=True)
    return order[xp.argsort(ranks[order], stable=True)]


def _draw_nsga2_randoms(seed: int, pop: int, gens: int, quantum: float,
                        lo, hi) -> Dict[str, np.ndarray]:
    """All randomness of a fixed-shape NSGA-2 run, drawn once on the host
    so both backends consume the identical stream."""
    rng = np.random.default_rng(seed)
    rnd = {"init": rng.uniform(lo, hi, size=(pop, 2)),
           "tour": np.empty((gens, pop, 2), np.int64),
           "perm": np.empty((gens, pop), np.int64),
           "alpha": np.empty((gens, pop, 1)),
           "mut": np.empty((gens, pop, 2)),
           "do_mut": np.empty((gens, pop, 2))}
    for g in range(gens):
        rnd["tour"][g] = rng.integers(0, pop, size=(pop, 2))
        rnd["perm"][g] = rng.permutation(pop)
        rnd["alpha"][g] = rng.uniform(size=(pop, 1))
        rnd["mut"][g] = rng.normal(0, quantum * 2, size=(pop, 2))
        rnd["do_mut"][g] = (rng.uniform(size=(pop, 2)) < 0.2)
    return rnd


def _generation(xp, P, FP, tour, perm, alpha, mut, do_mut, snap, lookup,
                pop, mul):
    """One elitist NSGA-2 generation, written once for both backends.
    `mul(a, b)` is a fusion-proof product on the jnp side (a plain one on
    numpy); `snap`/`lookup` quantize genomes and gather their exact
    objectives."""
    ranks = _fnds_fixed(xp, FP)
    crowd = _crowd_fixed(xp, FP)
    i0, i1 = tour[:, 0], tour[:, 1]
    better = xp.where((ranks[i0] < ranks[i1])
                      | ((ranks[i0] == ranks[i1])
                         & (crowd[i0] > crowd[i1])), i0, i1)
    parents = P[better]
    partners = parents[perm]
    children = mul(alpha, parents) + mul(1.0 - alpha, partners)
    children = snap(children + mul(do_mut, mut))
    FC = lookup(children)
    allP = xp.concatenate([P, children])
    allF = xp.concatenate([FP, FC])
    order = _rank_crowd_order(xp, _fnds_fixed(xp, allF),
                              _crowd_fixed(xp, allF))[:pop]
    return allP[order], allF[order]


def nsga2_device(eval_fn, bounds, *, pop: int = 64, gens: int = 40,
                 seed: int = 0, quantum: int = 8, warm_start=None,
                 backend: str = "jnp"):
    """Fixed-shape NSGA-2 whose whole evolution runs on-device in ONE jit
    dispatch (`backend="jnp"`), with a numpy twin (`backend="numpy"`) that
    consumes the identical pre-drawn randomness — the bitwise test oracle.

    `eval_fn` ((m, 2) int genomes -> (m, k) minimized objectives) is
    called ONCE, on the full quantized (h, w) grid implied by
    `bounds`/`quantum`; generations then evaluate genomes by table
    gather, which is exact on every backend. Differences vs
    `core.pareto.nsga2`: no dedup/refill (data-dependent shapes don't
    jit) and stable sort order throughout — same algorithm family, not
    the same stream of iterates. Returns (genomes, objectives) of the
    final population's Pareto set, like `nsga2`."""
    if backend not in ("jnp", "numpy"):
        raise ValueError(f"unknown backend {backend!r} (have jnp|numpy)")
    (hl, hh), (wl, wh) = bounds
    qf = float(quantum)
    lo = np.asarray([hl, wl], np.float64)
    hi = np.asarray([hh, wh], np.float64)

    def snap_np(x):
        return np.clip(np.round(x / qf) * qf, lo, hi)

    # exact objective table over every reachable quantized genome
    h_vals = np.unique(snap_np(np.stack(
        [np.arange(hl, hh + 1, dtype=np.float64)] * 2, 1))[:, 0])
    w_vals = np.unique(snap_np(np.stack(
        [np.arange(wl, wh + 1, dtype=np.float64)] * 2, 1))[:, 1])
    grid = np.stack(np.meshgrid(h_vals, w_vals, indexing="ij"),
                    -1).reshape(-1, 2)
    table = np.asarray(eval_fn(grid.astype(int)), np.float64)
    n_w = len(w_vals)

    rnd = _draw_nsga2_randoms(seed, pop, gens, qf, lo, hi)
    P0 = snap_np(rnd["init"])
    if warm_start is not None:
        ws = snap_np(np.asarray(warm_start, np.float64))[:pop]
        P0[:len(ws)] = ws

    if backend == "numpy":
        def lookup(P):
            idx = (np.searchsorted(h_vals, P[:, 0]) * n_w
                   + np.searchsorted(w_vals, P[:, 1]))
            return table[idx]

        P, FP = P0, lookup(P0)
        for g in range(gens):
            P, FP = _generation(
                np, P, FP, rnd["tour"][g], rnd["perm"][g], rnd["alpha"][g],
                rnd["mut"][g], rnd["do_mut"][g].astype(np.float64),
                snap_np, lookup, pop, lambda a, b: a * b)
    else:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax import enable_x64

        with enable_x64():
            jlo, jhi = jnp.asarray(lo), jnp.asarray(hi)
            jh, jw = jnp.asarray(h_vals), jnp.asarray(w_vals)
            jtab = jnp.asarray(table)

            @jax.jit
            def evolve(P0, tour, perm, alpha, mut, do_mut, zero, q):
                # `zero` is a runtime 0.0 and `q` a runtime quantum:
                # opaque to XLA, so products can't be contracted into
                # fmas and the /q can't become a reciprocal multiply —
                # the elementwise stream matches numpy op for op.
                def mul(a, b):
                    return a * b + zero

                def snap(x):
                    return jnp.clip(jnp.round(x / q) * q, jlo, jhi)

                def lookup(P):
                    idx = (jnp.searchsorted(jh, P[:, 0]) * n_w
                           + jnp.searchsorted(jw, P[:, 1]))
                    return jtab[idx]

                def gen(g, st):
                    P, FP = st
                    pick = lambda a: lax.dynamic_index_in_dim(
                        a, g, 0, keepdims=False)
                    return _generation(
                        jnp, P, FP, pick(tour), pick(perm), pick(alpha),
                        pick(mut), pick(do_mut), snap, lookup, pop, mul)

                return lax.fori_loop(0, gens, gen, (P0, lookup(P0)))

            P, FP = evolve(
                jnp.asarray(P0), jnp.asarray(rnd["tour"]),
                jnp.asarray(rnd["perm"]), jnp.asarray(rnd["alpha"]),
                jnp.asarray(rnd["mut"]),
                jnp.asarray(rnd["do_mut"].astype(np.float64)),
                jnp.float64(0.0), jnp.float64(qf))
            P, FP = np.asarray(P), np.asarray(FP)

    from repro.core.pareto import pareto_mask
    final = pareto_mask(FP)
    return P[final].astype(int), FP[final]


# ------------------------------------------------- gradient refiner ---------

def refine_design_point(workloads, seed_point, *,
                        objectives=("energy", "cycles"),
                        steps: int = 48, lr: float = 8.0, quantum: int = 8,
                        bounds=((16, 256), (16, 256)),
                        model_kw: Optional[dict] = None):
    """Gradient-refine a design point against the relaxed cost model.

    `jax.grad` descends the continuous-tiling relaxation of the closed
    forms (`kernels.dse_eval.relaxed_objectives`) from `seed_point`,
    normalizing each objective by its seed value so multi-objective /
    multi-model losses are scale-balanced. The WHOLE trajectory runs in
    one jitted `lax.fori_loop` — a single device dispatch regardless of
    `steps`. Every visited point is then snapped to the `quantum` grid,
    deduplicated, and re-evaluated with the EXACT numpy closed forms
    (`core.systolic.analyze_network`); the seed itself is always in that
    candidate set, so the accepted point can never be worse than the
    unrefined seed under exact evaluation. Relaxed numbers only steer —
    the reported objective is always exact.

    `workloads` is one layer list or a dict name -> layer list (the
    multi-model case sums the per-model normalized objectives — the
    Fig. 5 robust-configuration loss). Returns a dict with the accepted
    (h, w), exact objective scalars/vectors for seed and refined point,
    and search accounting (`device_dispatches` is 1 by construction).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import enable_x64

    from repro.core import systolic

    from repro.kernels.dse_eval import relaxed_objectives

    named = dict(workloads) if isinstance(workloads, dict) \
        else {"model": list(workloads)}
    model_kw = dict(model_kw or {})
    fns = {n: relaxed_objectives(wl, objectives, **model_kw)
           for n, wl in named.items()}

    (hl, hh), (wlo, wh) = bounds
    x0 = np.asarray(seed_point, np.float64)
    if x0.shape != (2,):
        raise ValueError(f"seed_point must be (h, w), got {seed_point!r}")

    with enable_x64():
        lo = jnp.asarray([hl, wlo], jnp.float64)
        hi = jnp.asarray([hh, wh], jnp.float64)

        @jax.jit
        def descend(x, lr_):
            denoms = {n: jnp.abs(f(x)) + 1e-30 for n, f in fns.items()}

            def loss(y):
                t = 0.0
                for n, f in fns.items():
                    t = t + jnp.sum(f(y) / denoms[n])
                return t

            g = jax.grad(loss)

            def step(i, st):
                y, traj = st
                gv = g(y)
                gv = gv / (jnp.linalg.norm(gv) + 1e-30)
                y = jnp.clip(y - lr_ * gv, lo, hi)
                return y, traj.at[i + 1].set(y)

            traj0 = jnp.zeros((steps + 1, 2), jnp.float64).at[0].set(x)
            return lax.fori_loop(0, steps, step, (x, traj0))[1]

        traj = np.asarray(descend(jnp.asarray(x0), jnp.float64(lr)))

    # Snap every visited point to the design grid; the RAW seed is always
    # a candidate, so "never worse than the seed" holds by construction.
    snapped = np.clip(np.round(traj / quantum) * quantum,
                      [hl, wlo], [hh, wh])
    cands = np.unique(np.concatenate([x0[None], snapped], axis=0), axis=0)
    seed_idx = int(np.where((cands == x0).all(axis=1))[0][0])

    h = cands[:, 0]
    w = cands[:, 1]
    exact = {}
    scal = np.zeros(len(cands))
    for n, wl in named.items():
        m = systolic.analyze_network(list(wl), h, w, **model_kw)
        F = np.stack(
            [np.broadcast_to(np.asarray(
                {"energy": m.energy, "cycles": m.cycles,
                 "utilization": -m.utilization}[o], np.float64), h.shape)
             for o in objectives], axis=1)
        exact[n] = F
        scal += (F / np.maximum(np.abs(F[seed_idx]), 1e-30)).sum(axis=1)
    best = int(np.argmin(scal))

    def _num(v):
        return int(v) if float(v).is_integer() else float(v)

    return {
        "h": _num(cands[best, 0]), "w": _num(cands[best, 1]),
        "seed": (_num(x0[0]), _num(x0[1])),
        "objective": float(scal[best]),
        "seed_objective": float(scal[seed_idx]),
        "improved": bool(scal[best] < scal[seed_idx]),
        "objectives": {n: {o: float(exact[n][best, i])
                           for i, o in enumerate(objectives)}
                       for n in named},
        "seed_objectives": {n: {o: float(exact[n][seed_idx, i])
                                for i, o in enumerate(objectives)}
                            for n in named},
        "candidates_evaluated": int(len(cands)),
        "exact_evals": int(len(cands) * len(named)),
        "device_dispatches": 1,
        "steps": int(steps),
    }
