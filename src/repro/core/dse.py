"""Design-space exploration driver (the paper's §4/§5 experiments).

* grid_sweep: all (h, w) in [16..256 step 8]^2 (961 configs) for a network's
  workloads — vectorized in one shot over the whole grid (Fig. 2/4 heatmaps).
  `backend="numpy"` (float64, exact) or `backend="pallas"` (the fused sweep
  kernel from kernels/dse_eval.py; Mosaic on TPU, interpret mode elsewhere).
* precision_sweep: the bitwidth design space — (h, w, act_bits, weight_bits)
  points with bit-normalized energy / bits-per-cycle UB bandwidth
  (ArrayFlex-style configurable-precision arrays).
* pareto_grid / pareto_nsga2: frontier of (cycles vs energy) and
  (cycles vs -utilization) (Fig. 3).
* robust_config: averaged min-max-normalized (energy, cycles) across a model
  mix, Pareto over configurations (Fig. 5): `robust_serving_config` of one
  `scenario_sweep` over the models.
* equal_pe_sweep: extreme aspect ratios at constant PE count (Fig. 6,
  Samajdar et al. comparison), on either backend.
* capacity_sweep: the connectivity-aware (h, w, ub_kib) design space — the
  per-config closed forms run on the numpy/pallas grid backends over
  `graph.flatten()`, and the graph's liveness profile (repro.graph) adds
  finite-UB spill energy per capacity point.
* scenario_sweep: the serving-scenario dimension — every scenario's padded
  layer table packed into one (S, L, 5) tensor and dispatched to the fused
  batched Pallas kernel in a SINGLE call over (scenario, h, w), instead of
  a Python loop of per-scenario sweeps (see repro.scenarios for the
  config x phase x batch x seq_len matrix).
* robust_serving_config: Fig. 5's min-max normalization generalized to a
  (weighted) serving mix over a ScenarioSweepResult.
* slo_capacity_sweep: the traffic dimension — max sustainable QPS under a
  (p99 TTFT, p99 TPOT) SLO per (arch, h, w), bisected on the
  discrete-event serving simulator (repro.traffic) whose cost tables come
  from one fused batched Pallas dispatch.
* robust_traffic_config: Fig. 5 weighted by a heterogeneous traffic mix
  over (energy/token, 1/max_qps), with the normalized winner.
* fleet_capacity_sweep: the fleet-composition dimension — enumerate pools
  of (possibly differently shaped) arrays holding pipeline/tensor-
  partitioned model instances under an iso-PE budget, score each
  composition's max QPS under the SLO on the multi-server simulator
  (repro.fleet): partition -> fused stage tables -> fleet replay -> SLO
  bisection, per architecture of a traffic mix.
* robust_fleet_config: Fig. 5's normalization over fleet compositions,
  weighted by the traffic mix, with the normalized winner.
"""
from __future__ import annotations

import dataclasses
import inspect
import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import systolic
from repro.core.model_core import Precision
from repro.core.pareto import nsga2, pareto_mask
from repro.core.workloads import Workload
from repro.obs.trace import tracer as _obs_tracer

GRID_LO, GRID_HI, GRID_STEP = 16, 256, 8


def grid_axes():
    return np.arange(GRID_LO, GRID_HI + 1, GRID_STEP)


@dataclasses.dataclass
class SweepResult:
    hs: np.ndarray          # (G,)
    ws: np.ndarray          # (G,)
    H: np.ndarray           # (G, G) grid (height on axis 0)
    W: np.ndarray
    cycles: np.ndarray      # (G, G)
    energy: np.ndarray
    utilization: np.ndarray
    m_ub: np.ndarray
    m_inter_pe: np.ndarray
    m_aa: np.ndarray
    ub_bw_bits: Optional[np.ndarray] = None   # (G, G) bits/cycle

    def flat(self):
        return {k: getattr(self, k).reshape(-1)
                for k in ("cycles", "energy", "utilization")}


def _grid_sweep_numpy(workloads, hs, ws, H, W, **model_kw):
    m = systolic.analyze_network(list(workloads), H.astype(np.float64),
                                 W.astype(np.float64), **model_kw)
    # some counters (e.g. m_ub without act_reread) are config-independent
    # and come back 0-d; broadcast so every field honors the (G, G) grid
    # contract on both backends.
    grid = lambda x: np.broadcast_to(np.asarray(x, np.float64),
                                     H.shape).copy()
    return SweepResult(hs=hs, ws=ws, H=H, W=W, cycles=grid(m.cycles),
                       energy=grid(m.energy),
                       utilization=grid(m.utilization),
                       m_ub=grid(m.m_ub),
                       m_inter_pe=grid(m.m_inter_pe),
                       m_aa=grid(m.m_aa),
                       ub_bw_bits=grid(m.ub_bandwidth_bits))


def _pallas_eval_configs(workloads, cfgs, **model_kw):
    """Evaluate an arbitrary (C, 2) config list on the fused Pallas sweep
    kernel, returning a dict of per-config metric columns.

    `kernels.ops` pads the config list to the kernel's tile and slices
    the result back; off-TPU the kernel runs in interpret mode. Traces
    `sweep.put` (the layer table), `sweep.fetch` (waiting for the device
    and the copy back) and `sweep.assemble` (the columns).
    """
    from repro.kernels import ops
    from repro.kernels.dse_eval import OUT_COLS

    tr = _obs_tracer()
    with tr.span("sweep.put", "dse"):
        layers = np.asarray(
            [(m, k, n, g, r) for (m, k, n, g, r) in workloads], np.float32)
    out = ops.sweep(cfgs, layers, **model_kw)
    with tr.span("sweep.fetch", "dse"):
        out = np.asarray(out)
    with tr.span("sweep.assemble", "dse"):
        return {k: out[:, j] for j, k in enumerate(OUT_COLS)}


def _grid_sweep_pallas(workloads, hs, ws, H, W, **model_kw):
    """Dispatch the whole grid to the fused Pallas sweep kernel."""
    cfgs = np.stack([H.reshape(-1), W.reshape(-1)], axis=1)
    col = {k: v.reshape(H.shape) for k, v in _pallas_eval_configs(
        workloads, cfgs, **model_kw).items()}
    return SweepResult(hs=hs, ws=ws, H=H, W=W, cycles=col["cycles"],
                       energy=col["energy"],
                       utilization=col["utilization"], m_ub=col["m_ub"],
                       m_inter_pe=col["m_inter_pe"], m_aa=col["m_aa"],
                       ub_bw_bits=col["ub_bandwidth_bits"])


def grid_sweep(workloads: Sequence[Workload], hs=None, ws=None,
               backend: str = "numpy", **model_kw) -> SweepResult:
    hs = grid_axes() if hs is None else np.asarray(hs)
    ws = grid_axes() if ws is None else np.asarray(ws)
    H, W = np.meshgrid(hs, ws, indexing="ij")
    if backend == "numpy":
        return _grid_sweep_numpy(workloads, hs, ws, H, W, **model_kw)
    if backend == "pallas":
        return _grid_sweep_pallas(workloads, hs, ws, H, W, **model_kw)
    raise ValueError(f"unknown backend {backend!r} (numpy|pallas)")


def precision_sweep(workloads: Sequence[Workload],
                    bit_widths: Sequence[int] = (4, 8, 16),
                    hs=None, ws=None, out_bits: int = None,
                    backend: str = "numpy", **model_kw) -> List[dict]:
    """Sweep the (h, w, act_bits, weight_bits) design space.

    For every (act_bits, weight_bits) pair the full (h, w) grid is evaluated
    with bit-normalized energy and bits/cycle UB bandwidth; `out_bits`
    defaults to max(act_bits, weight_bits) (accumulate at the wider operand
    width). Returns one record per precision point with the best-energy
    configuration and its bandwidth demand.
    """
    records = []
    for ab, wb in itertools.product(bit_widths, bit_widths):
        prec = Precision(act_bits=ab, weight_bits=wb,
                         out_bits=out_bits if out_bits else max(ab, wb))
        s = grid_sweep(workloads, hs=hs, ws=ws, backend=backend,
                       precision=prec, **model_kw)
        i, j = np.unravel_index(np.argmin(s.energy), s.energy.shape)
        records.append({
            "act_bits": ab, "weight_bits": wb,
            "out_bits": prec.out_bits,
            "best_h": int(s.hs[i]), "best_w": int(s.ws[j]),
            "min_energy": float(s.energy[i, j]),
            "cycles_at_best": float(s.cycles[i, j]),
            "util_at_best": float(s.utilization[i, j]),
            "ub_bw_bits_at_best": float(s.ub_bw_bits[i, j]),
            "sweep": s,
        })
    return records


def pareto_grid(sweep: SweepResult, objectives=("energy", "cycles")):
    """Exact Pareto set over the sweep grid. Returns (configs, F, mask)."""
    cols = []
    for o in objectives:
        v = getattr(sweep, o).reshape(-1).astype(np.float64)
        if o == "utilization":
            v = -v
        cols.append(v)
    F = np.stack(cols, axis=1)
    mask = pareto_mask(F)
    configs = np.stack([sweep.H.reshape(-1), sweep.W.reshape(-1)], axis=1)
    return configs[mask], F[mask], mask


# keyword arguments consumed by pareto.nsga2 itself (derived from its
# signature so the split can't drift); anything else passed to pareto_nsga2
# is a model option and must reach analyze_network.
_NSGA2_KEYS = frozenset(
    p.name for p in inspect.signature(nsga2).parameters.values()
    if p.kind == p.KEYWORD_ONLY)


def pareto_nsga2(workloads, objectives=("energy", "cycles"),
                 model_kw: Optional[dict] = None, engine: str = "numpy",
                 **kw):
    """NSGA-II frontier with full model-option support.

    Optimizer knobs (`pop`, `gens`, `seed`, `quantum`, `warm_start`) go to
    `nsga2`; every other keyword — `precision=`, `dataflow=`,
    `act_reread=`, ... — is threaded through to `analyze_network`, so the
    evolved frontier reflects the same accounting as the exact grid.
    `model_kw` may also be passed explicitly.

    `warm_start="grid"` seeds the initial population with the EXACT grid
    Pareto points (one grid sweep + `pareto_grid`), so the evolved
    frontier starts at — and can only improve on — the exact one.
    `engine="device"` runs the fixed-shape on-device NSGA-2
    (`core.search.nsga2_device`, one jit dispatch for the whole
    evolution) instead of the per-generation numpy loop."""
    model_kw = dict(model_kw or {})
    for k in list(kw):
        if k not in _NSGA2_KEYS and k != "warm_start":
            model_kw[k] = kw.pop(k)

    def eval_fn(pop):
        h = pop[:, 0].astype(np.float64)
        w = pop[:, 1].astype(np.float64)
        m = systolic.analyze_network(list(workloads), h, w, **model_kw)
        cols = []
        for o in objectives:
            v = {"energy": m.energy, "cycles": m.cycles,
                 "utilization": -m.utilization}[o]
            cols.append(np.asarray(v, np.float64))
        return np.stack(cols, axis=1)

    if isinstance(kw.get("warm_start"), str):
        if kw["warm_start"] != "grid":
            raise ValueError(f"unknown warm_start {kw['warm_start']!r} "
                             "(have 'grid' or an (m, 2) genome array)")
        sweep = grid_sweep(list(workloads), backend="numpy", **model_kw)
        kw["warm_start"] = pareto_grid(sweep, objectives)[0]

    bounds = ((GRID_LO, GRID_HI), (GRID_LO, GRID_HI))
    if engine == "device":
        from repro.core.search import nsga2_device
        return nsga2_device(eval_fn, bounds, **kw)
    if engine != "numpy":
        raise ValueError(f"unknown engine {engine!r} (have numpy|device)")
    return nsga2(eval_fn, bounds, **kw)


def _normalize(x):
    lo, hi = x.min(), x.max()
    return (x - lo) / (hi - lo) if hi > lo else np.zeros_like(x)


def robust_config(model_workloads: Dict[str, Sequence[Workload]],
                  weights: Optional[Dict[str, float]] = None,
                  backend: str = "numpy", **kw):
    """Fig. 5: the (weighted) average of min-max-normalized (energy,
    cycles) per model, then the Pareto set over the grid. Returns
    (configs, F, mask) as `robust_serving_config` does.

    The models are swept together by `scenario_sweep`: `backend="pallas"`
    in ONE fused dispatch of the batched kernel over (model, h, w),
    `backend="numpy"` in float64, one model at a time. `kw` (`hs`, `ws`,
    model options) goes to `scenario_sweep`."""
    return robust_serving_config(
        scenario_sweep(model_workloads, backend=backend, fused=True, **kw),
        weights)


def equal_pe_sweep(model_workloads: Dict[str, Sequence[Workload]],
                   total_pes: int = 16384, backend: str = "numpy",
                   **model_kw):
    """Fig. 6: aspect-ratio sweep at constant PE count (Samajdar-style):
    h x w with h*w = total_pes, h in powers of two. `backend` selects the
    numpy float64 path or the fused Pallas sweep kernel, like grid_sweep."""
    hs = []
    h = 2
    while h <= total_pes // 2:
        if total_pes % h == 0:
            hs.append(h)
        h *= 2
    hs = np.asarray(hs)
    ws = total_pes // hs
    out = {}
    for name, wls in model_workloads.items():
        if backend == "numpy":
            m = systolic.analyze_network(list(wls), hs.astype(np.float64),
                                         ws.astype(np.float64), **model_kw)
            energy, cycles, util = (np.asarray(m.energy),
                                    np.asarray(m.cycles),
                                    np.asarray(m.utilization))
        elif backend == "pallas":
            col = _pallas_eval_configs(wls, np.stack([hs, ws], axis=1),
                                       **model_kw)
            energy, cycles, util = (col["energy"], col["cycles"],
                                    col["utilization"])
        else:
            raise ValueError(f"unknown backend {backend!r} (numpy|pallas)")
        out[name] = {
            "h": hs, "w": ws,
            "energy": _normalize(energy),
            "cycles": _normalize(cycles),
            "utilization": util,
        }
    return out


# ---------------------------------------------------- serving-scenario DSE --

_SWEEP_KEYS = ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
               "m_aa", "ub_bw_bits")


def pad_layer_sets(workload_lists: Sequence[Sequence[Workload]]):
    """Pack ragged per-scenario workload lists into one (S, Lmax, 5) float32
    tensor, padding with `kernels.dse_eval.PAD_LAYER` rows."""
    from repro.kernels.dse_eval import PAD_LAYER
    L = max(len(wls) for wls in workload_lists)
    out = np.empty((len(workload_lists), L, 5), np.float32)
    for i, wls in enumerate(workload_lists):
        rows = [tuple(map(float, wl)) for wl in wls]
        rows += [PAD_LAYER] * (L - len(rows))
        out[i] = np.asarray(rows, np.float32)
    return out


@dataclasses.dataclass
class ScenarioSweepResult:
    """Per-scenario (h, w) grids stacked along a leading scenario axis."""
    names: List[str]
    hs: np.ndarray          # (G,)
    ws: np.ndarray
    H: np.ndarray           # (G, G)
    W: np.ndarray
    cycles: np.ndarray      # (S, G, G)
    energy: np.ndarray
    utilization: np.ndarray
    m_ub: np.ndarray
    m_inter_pe: np.ndarray
    m_aa: np.ndarray
    ub_bw_bits: np.ndarray

    def index(self, name: str) -> int:
        return self.names.index(name)

    def result(self, name: str) -> SweepResult:
        """One scenario's grids as a plain SweepResult."""
        i = self.index(name)
        return SweepResult(hs=self.hs, ws=self.ws, H=self.H, W=self.W,
                           **{k: getattr(self, k)[i] for k in _SWEEP_KEYS})

    def best_energy(self, name: str):
        """(h, w, energy) of the min-energy design point of one scenario."""
        e = self.energy[self.index(name)]
        i, j = np.unravel_index(np.argmin(e), e.shape)
        return int(self.hs[i]), int(self.ws[j]), float(e[i, j])


def scenario_sweep(named_workloads, hs=None,
                   ws=None, backend: str = "pallas", fused: bool = True,
                   cache_hit: float = 0.0, spec_decode=None,
                   **model_kw) -> ScenarioSweepResult:
    """Sweep the whole scenario matrix over the (h, w) grid.

    `backend="pallas"` with `fused=True` (the default) pads every
    scenario's layer list into one batched (S, L, 5) tensor and makes a
    SINGLE fused kernel dispatch over (scenario, h, w); `fused=False` is
    the per-scenario dispatch loop kept as the speedup baseline.
    `backend="numpy"` is the float64 reference (always a per-scenario
    loop; exact, used by the equivalence tests).

    `named_workloads` is either the lowered {name: workload list} dict or
    a `scenarios.matrix.Scenario` list. The KV-serving knobs — `cache_hit`
    (fraction of each prefill prompt served from the cross-request prefix
    cache) and `spec_decode` (a `traffic.cost_table.SpecDecodeConfig`;
    decode cells lower as k-draft + verify rounds) — re-lower the cells
    via `scenarios.matrix.kv_named_workloads`, so they require the
    Scenario list, not a pre-lowered dict."""
    if cache_hit or spec_decode is not None:
        from repro.scenarios.matrix import kv_named_workloads
        if isinstance(named_workloads, dict):
            raise ValueError(
                "scenario_sweep: cache_hit/spec_decode re-lower the "
                "scenario cells — pass the Scenario list "
                "(serving_matrix(...)), not a pre-lowered dict")
        named_workloads = kv_named_workloads(named_workloads, cache_hit,
                                             spec_decode)
    elif not isinstance(named_workloads, dict):
        from repro.scenarios.matrix import named_workloads as _lower
        named_workloads = _lower(named_workloads)
    hs = grid_axes() if hs is None else np.asarray(hs)
    ws = grid_axes() if ws is None else np.asarray(ws)
    H, W = np.meshgrid(hs, ws, indexing="ij")
    names = list(named_workloads)
    shape = (len(names),) + H.shape

    _span = _obs_tracer().span("scenario_sweep", "dse", backend=backend,
                               fused=bool(fused), scenarios=len(names),
                               configs=int(H.size))
    with _span:
        return _scenario_sweep_body(named_workloads, names, hs, ws, H, W,
                                    shape, backend, fused, model_kw)


def _scenario_sweep_body(named_workloads, names, hs, ws, H, W, shape,
                         backend, fused, model_kw):
    if backend == "numpy":
        grids = {k: np.empty(shape, np.float64) for k in _SWEEP_KEYS}
        for i, name in enumerate(names):
            s = _grid_sweep_numpy(named_workloads[name], hs, ws, H, W,
                                  **model_kw)
            for k in _SWEEP_KEYS:
                grids[k][i] = getattr(s, k)
    elif backend == "pallas" and not fused:
        grids = {k: np.empty(shape, np.float64) for k in _SWEEP_KEYS}
        cfgs = np.stack([H.reshape(-1), W.reshape(-1)], axis=1)
        for i, name in enumerate(names):
            col = _pallas_eval_configs(named_workloads[name], cfgs,
                                       **model_kw)
            col["ub_bw_bits"] = col.pop("ub_bandwidth_bits")
            for k in _SWEEP_KEYS:
                grids[k][i] = col[k].reshape(H.shape)
    elif backend == "pallas":
        from repro.kernels import ops
        from repro.kernels.dse_eval import OUT_COLS

        tr = _obs_tracer()
        with tr.span("sweep.put", "dse"):
            layer_sets = pad_layer_sets([named_workloads[n] for n in names])
            cfgs = np.stack([H.reshape(-1), W.reshape(-1)], axis=1)
        out = ops.sweep_batched(cfgs, layer_sets, **model_kw)
        with tr.span("sweep.fetch", "dse"):
            out = np.asarray(out)
        with tr.span("sweep.assemble", "dse"):
            cols = {k: out[:, :, j] for j, k in enumerate(OUT_COLS)}
            cols["ub_bw_bits"] = cols.pop("ub_bandwidth_bits")
            grids = {k: cols[k].reshape(shape).astype(np.float64)
                     for k in _SWEEP_KEYS}
    else:
        raise ValueError(f"unknown backend {backend!r} (numpy|pallas)")

    return ScenarioSweepResult(names=names, hs=hs, ws=ws, H=H, W=W, **grids)


def robust_serving_config(sweep: ScenarioSweepResult,
                          weights: Optional[Dict[str, float]] = None):
    """Fig. 5 generalized to a serving mix: the (weighted) average of
    min-max-normalized (energy, cycles) per SCENARIO — phase x batch x
    seq_len cells, or whole models (`robust_config`) — then the Pareto
    set over the grid. Returns (configs, F, mask).

    `weights` maps scenario name -> traffic share; None means uniform.
    When a dict is given it must be COMPLETE over the swept scenarios
    (unknown names raise): a scenario's share may be 0.0 (no traffic),
    but it must be said explicitly — silently dropping unnamed cells
    would turn a typo into a different mix. Traces `robust.normalize`
    (the normalization and the Pareto mask)."""
    if weights is not None:
        unknown = set(weights) - set(sweep.names)
        missing = set(sweep.names) - set(weights)
        if unknown or missing:
            raise ValueError(
                "robust_serving_config: weights must cover the swept "
                f"scenarios exactly (unknown: {sorted(unknown)[:3]}, "
                f"missing: {sorted(missing)[:3]})")
    with _obs_tracer().span("robust.normalize", "dse"):
        wsum = 0.0
        e_acc = np.zeros_like(sweep.H, np.float64)
        c_acc = np.zeros_like(sweep.H, np.float64)
        for i, name in enumerate(sweep.names):
            wt = 1.0 if weights is None else float(weights[name])
            if wt == 0.0:
                continue
            e_acc += wt * _normalize(sweep.energy[i])
            c_acc += wt * _normalize(sweep.cycles[i])
            wsum += wt
        if wsum == 0.0:
            raise ValueError(
                "robust_serving_config: all scenario weights zero")
        F = np.stack([(e_acc / wsum).reshape(-1),
                      (c_acc / wsum).reshape(-1)], axis=1)
        mask = pareto_mask(F)
    configs = np.stack([sweep.H.reshape(-1), sweep.W.reshape(-1)], axis=1)
    return configs, F, mask


# ------------------------------------------------------ capacity-aware DSE --

# Default UB capacities (KiB): spans "everything spills" to "nothing does"
# for the 224x224 CNN zoo, whose liveness peaks sit between ~0.3 and ~6 MiB.
UB_KIBS = (128, 256, 512, 1024, 2048, 4096, 8192)


@dataclasses.dataclass
class CapacitySweepResult:
    """(h, w, ub_kib) design space for one network graph.

    The closed-form grid (`base`) is capacity-independent; the liveness
    profile of the graph's schedule determines a per-capacity spill term,
    so `energy_total[u, i, j] = base.energy[i, j] + spill_energy[u]`."""
    base: SweepResult
    order: str
    peak_bits: float               # schedule's peak UB occupancy
    ub_kibs: np.ndarray            # (U,)
    spill_bits: np.ndarray         # (U,) DRAM round-trip traffic
    spill_energy: np.ndarray       # (U,) Eq. 1-relative
    energy_total: np.ndarray       # (U, G, G)
    # capacity_sweep(breakdown=True): one grid-shaped CostBreakdown per
    # capacity point, conserving against `energy_total[u]` elementwise.
    breakdowns: Optional[List] = None

    def best(self, u: int):
        """(h, w, energy_total) of the best design point at capacity u."""
        i, j = np.unravel_index(np.argmin(self.energy_total[u]),
                                self.energy_total[u].shape)
        return (int(self.base.hs[i]), int(self.base.ws[j]),
                float(self.energy_total[u, i, j]))


def capacity_sweep(graph, ub_kibs: Sequence[float] = UB_KIBS, hs=None,
                   ws=None, order: str = "dfs", backend: str = "numpy",
                   breakdown: bool = False,
                   **model_kw) -> CapacitySweepResult:
    """Sweep the (h, w, ub_kib) design space for a network graph.

    The per-config part reuses the grid backends (numpy float64 or the
    fused Pallas kernel) over `graph.flatten()` — bit-identical to the flat
    workload list — while the graph's liveness profile under the chosen
    schedule `order` ("dfs" | "bfs") converts each finite capacity into
    spill/refetch energy (see repro.graph.occupancy).

    `breakdown=True` additionally attaches one grid-shaped
    `obs.attribution.CostBreakdown` per capacity point (compute /
    ub_stream / fill_drain from the closed forms, dram_spill from the
    liveness profile), each conserving against `energy_total[u]`. The
    component grids come from the exact numpy closed forms, so
    conservation at 1e-9 is guaranteed for `backend="numpy"`."""
    from repro.core.model_core import dram_spill_energy
    from repro.graph.occupancy import spill_bits
    from repro.graph.schedule import occupancy_profile

    base = grid_sweep(graph.flatten(), hs=hs, ws=ws, backend=backend,
                      **model_kw)
    prof = occupancy_profile(graph, order=order)
    ubs = np.asarray(list(ub_kibs), np.float64)
    sp = np.asarray([spill_bits(prof, u * 1024.0 * 8.0) for u in ubs])
    se = np.asarray([dram_spill_energy(s) for s in sp])
    energy_total = base.energy[None, :, :] + se[:, None, None]
    bds = None
    if breakdown:
        from repro.obs.attribution import CostBreakdown, network_breakdown
        H, W = np.meshgrid(base.hs.astype(np.float64),
                           base.ws.astype(np.float64), indexing="ij")
        net = network_breakdown(graph.flatten(), H, W, **model_kw)
        bds = []
        for u in range(len(ubs)):
            bds.append(CostBreakdown(
                total_cycles=net.total_cycles,
                total_energy=energy_total[u],
                cycles=dict(net.cycles),
                energy={**net.energy,
                        "dram_spill": se[u] + net.total_energy * 0.0},
                macs=dict(net.macs),
                words={**net.words, "dram_spill": sp[u] / 8.0},
                label=f"capacity:{order}:ub{int(ubs[u])}KiB",
                meta={"time_unit": "cycles", "ub_kib": float(ubs[u]),
                      "order": order}))
    return CapacitySweepResult(
        base=base, order=order, peak_bits=prof.peak_bits, ub_kibs=ubs,
        spill_bits=sp, spill_energy=se,
        energy_total=energy_total, breakdowns=bds)


# ------------------------------------------------------ SLO-aware traffic DSE --

@dataclasses.dataclass
class SLOSweepResult:
    """Max sustainable QPS under an SLO per (arch, h, w) design point.

    `max_qps[a, c]` is the bisected capacity of config c serving arch a's
    traffic; `energy_per_token[a, c]` is the Eq. 1-relative energy rate at
    that operating point (the pair the robust-traffic normalization
    consumes). `summaries[a][c]` keeps the full percentile/goodput record
    of the winning probe."""
    archs: List[str]
    hw: np.ndarray                  # (C, 2) int
    slo: "object"
    max_qps: np.ndarray             # (A, C)
    energy_per_token: np.ndarray    # (A, C)
    goodput_qps: np.ndarray         # (A, C)
    summaries: List[List[dict]]
    # the batched search's `backend` (replay engine), `rounds`, `probes`
    # and `lanes`; None after a sequential search
    search_stats: Optional[Dict] = None

    def best(self, arch: str):
        """(h, w, max_qps) of the highest-capacity config for one arch."""
        a = self.archs.index(arch)
        c = int(np.argmax(self.max_qps[a]))
        return (int(self.hw[c, 0]), int(self.hw[c, 1]),
                float(self.max_qps[a, c]))


def _kv_scenario(per_arch: Dict, sim, cache_hit, spec_decode):
    """Apply the KV-reuse / speculative-decode scenario knobs to a
    per-arch traffic dict + a `traffic.sim.SimConfig`.

    `cache_hit` is a `traffic.workload.KVReuseConfig` or a float
    shorthand (the shared-template probability at the defaults); it adds
    the shared-prefix axis to every traffic model and turns the
    simulator's prefix-cache tier on. `spec_decode` is a
    `traffic.cost_table.SpecDecodeConfig` and arms the draft/verify
    engine (the cost tables must carry the matching lattices). Returns
    the adjusted (per_arch, sim, kv_config_or_None)."""
    from repro.traffic.workload import KVReuseConfig
    kv = None
    if cache_hit is not None:
        kv = cache_hit if isinstance(cache_hit, KVReuseConfig) \
            else KVReuseConfig(share=float(cache_hit))
        per_arch = {a: kv.apply(tm) for a, tm in per_arch.items()}
        if kv.share > 0.0:
            sim = dataclasses.replace(sim, prefix_cache_mib=kv.cache_mib)
    if spec_decode is not None:
        sim = dataclasses.replace(sim, spec=spec_decode)
    return per_arch, sim, kv


def _windowed_slo_cfg(windows, slo):
    """Fill a WindowConfig's SLO targets from the sweep's SLO when the
    caller left them unset (the common case: one source of truth)."""
    if windows.slo_ttft_s is None:
        return dataclasses.replace(windows, slo_ttft_s=slo.ttft_s,
                                   slo_tpot_s=slo.tpot_s)
    return windows


def _annotate_windowed(qps, summaries, wcfg, monitor, replay):
    """Burn-rate-aware capacity annotation: ONE windowed replay at each
    point's bisected capacity (`replay(a, c, qps, wcfg)` returns a result
    carrying `.windowed`), scored by `worst_window_goodput` and an
    `SLOMonitor`. The flag this exists for is `peak_burn_flagged`: the
    replay meets the day-average SLO objective (whole-run bad fraction
    within the monitor's budget) yet FIRES a burn-rate alert — a
    composition that looks fine on the mean and falls over at peak.
    Points bisected to zero get `"windowed": None`."""
    from repro.obs.windowed import SLOMonitor, worst_window_goodput
    mon = SLOMonitor() if monitor is None else monitor
    A, C = qps.shape
    for a in range(A):
        for c in range(C):
            q = float(qps[a, c])
            if q <= 0.0:
                summaries[a][c]["windowed"] = None
                continue
            s = replay(a, c, q, wcfg).windowed
            m = mon.evaluate(s)
            done = float(s.completions.sum())
            day_bad = (float(s.completions.sum() - s.good.sum()) / done
                       if done > 0 else 0.0)
            day_ok = day_bad <= mon.budget
            ww = worst_window_goodput(s)
            summaries[a][c]["windowed"] = {
                "window_s": s.cfg.window_s,
                "worst_window_goodput_qps": ww["goodput_qps"],
                "worst_window_good_frac": ww["good_frac"],
                "worst_window_t0_s": ww["t0_s"],
                "burn_alerts_fired": m.fired,
                "n_alerts": len(m.alerts),
                "budget_consumed": m.final_budget_consumed,
                "day_bad_frac": day_bad,
                "day_average_ok": day_ok,
                "peak_burn_flagged": day_ok and m.fired,
            }


def slo_capacity_sweep(traffic, slo, archs: Optional[Sequence[str]] = None,
                       hw=None, sim=None, n_requests: int = 1200,
                       seed: int = 0, backend: str = "pallas",
                       tables=None, search: str = "auto",
                       cache_hit=None, spec_decode=None,
                       windows=None, monitor=None,
                       **model_kw) -> SLOSweepResult:
    """The SLO-aware capacity design space: which (h, w) sustains how much
    traffic for each architecture.

    `traffic` is one TrafficModel or a per-arch dict (heterogeneous arrival
    mixes); `slo` a traffic.SLO; `sim` a traffic.SimConfig. All cost
    tables are built in ONE fused batched Pallas dispatch (or passed in
    via `tables`), then each (arch, h, w) point is bisected for its max
    sustainable QPS on the discrete-event simulator — the Systimator-style
    "meets the deadline at rate X" answer rather than a scalar ranking.

    `search` picks the bisection engine: "sequential" runs one scalar
    bisection per point; "auto"/"batched" advance every point in lockstep
    with one packed multi-lane replay per round (`core.search`). The two
    paths are bit-identical — same probe sequences, same replays — the
    batched one just runs an order of magnitude faster.

    `cache_hit` / `spec_decode` are the KV-serving scenario knobs
    (`_kv_scenario`): shared-prefix traffic + the prefix-cache tier, and
    draft/verify speculative decoding (when set, the cost tables are
    built with the extra draft/verify lattices — prebuilt `tables` must
    already carry them).

    `windows` (an `obs.windowed.WindowConfig`; SLO targets default to
    `slo`'s) adds burn-rate-aware scoring: after the bisection, each
    point is replayed ONCE at its capacity with windowed telemetry on and
    its summary gains a `"windowed"` dict — worst-window goodput plus the
    `SLOMonitor` verdict (`monitor` overrides the default rules/budget),
    flagging points that pass the day-average SLO but burn budget at
    peak (`peak_burn_flagged`). The bisection itself is untouched.
    """
    from repro.configs.base import list_archs
    from repro.core.search import batched_max_sustainable_qps
    from repro.traffic.cost_table import DEFAULT_HW, build_cost_tables
    from repro.traffic.sim import SimConfig
    from repro.traffic.slo import max_sustainable_qps

    if search not in ("auto", "batched", "sequential"):
        raise ValueError(f"unknown search {search!r} "
                         "(have auto|batched|sequential)")
    archs = list(list_archs()) if archs is None else list(archs)
    hw = list(DEFAULT_HW) if hw is None else [tuple(map(int, p)) for p in hw]
    sim = SimConfig() if sim is None else sim
    _tr = _obs_tracer()
    if tables is None:
        with _tr.span("cost_tables", "dse", archs=len(archs),
                      configs=len(hw)):
            tables = build_cost_tables(archs, hw, backend=backend,
                                       spec=spec_decode, **model_kw)
    per_arch = traffic if isinstance(traffic, dict) else \
        {a: traffic for a in archs}
    missing = set(archs) - set(per_arch)
    if missing:
        raise ValueError(f"slo_capacity_sweep: no traffic model for "
                         f"{sorted(missing)[:3]}")
    per_arch, sim, _ = _kv_scenario(per_arch, sim, cache_hit, spec_decode)

    A, C = len(archs), len(hw)
    qps = np.zeros((A, C))
    ept = np.zeros((A, C))
    good = np.zeros((A, C))
    summaries: List[List[dict]] = []
    stats = None
    with _tr.span("capacity_search", "dse", search=search, lanes=A * C):
        if search == "sequential":
            points = [
                [max_sustainable_qps(tables.table(arch, h, w),
                                     per_arch[arch], slo, sim=sim,
                                     n_requests=n_requests,
                                     seed=seed) for h, w in hw]
                for arch in archs]
        else:
            stats = {}
            flat = batched_max_sustainable_qps(
                [tables.table(arch, h, w) for arch in archs for h, w in hw],
                [per_arch[arch] for arch in archs for _ in hw],
                slo, sim=sim, n_requests=n_requests, seed=seed, stats=stats)
            points = [flat[a * C:(a + 1) * C] for a in range(A)]
    for a in range(A):
        row = []
        for c in range(C):
            q, summ = points[a][c]
            qps[a, c] = q
            ept[a, c] = summ["energy_per_token"]
            good[a, c] = summ.get("goodput_qps", 0.0)
            row.append(summ)
        summaries.append(row)
    if windows is not None:
        from repro.traffic.sim import simulate
        wcfg = _windowed_slo_cfg(windows, slo)

        def replay(a, c, q, wc):
            h, w_ = hw[c]
            return simulate(
                tables.table(archs[a], h, w_),
                per_arch[archs[a]].with_rate(q).sample(n_requests, seed),
                dataclasses.replace(sim, windows=wc))

        with _tr.span("windowed_score", "dse", lanes=A * C):
            _annotate_windowed(qps, summaries, wcfg, monitor, replay)
    return SLOSweepResult(archs=archs, hw=np.asarray(hw, np.int64),
                          slo=slo, max_qps=qps, energy_per_token=ept,
                          goodput_qps=good, summaries=summaries,
                          search_stats=stats)


def _robust_mix_frontier(archs, max_qps, energy_per_token,
                         weights: Optional[Dict[str, float]], label: str):
    """Shared Fig. 5 machinery of the robust_*_config variants: per arch,
    min-max normalize (energy/token, 1/max_qps) over the candidate axis
    — capacity is a benefit, so it is inverted (guarding dead candidates)
    to make both objectives costs — average with the mix weights, Pareto,
    and pick the normalized winner. Explicit `weights` must cover `archs`
    exactly (a 0.0 share is allowed but must be said).
    Returns (F, mask, winner_idx)."""
    if weights is not None:
        unknown = set(weights) - set(archs)
        missing = set(archs) - set(weights)
        if unknown or missing:
            raise ValueError(
                f"{label}: weights must cover the swept archs exactly "
                f"(unknown: {sorted(unknown)[:3]}, "
                f"missing: {sorted(missing)[:3]})")
    n = max_qps.shape[1]
    e_acc = np.zeros(n, np.float64)
    q_acc = np.zeros(n, np.float64)
    wsum = 0.0
    for a, arch in enumerate(archs):
        wt = 1.0 if weights is None else float(weights[arch])
        if wt == 0.0:
            continue
        inv_qps = 1.0 / np.maximum(max_qps[a], 1e-12)
        e_acc += wt * _normalize(energy_per_token[a])
        q_acc += wt * _normalize(inv_qps)
        wsum += wt
    if wsum == 0.0:
        raise ValueError(f"{label}: all mix weights zero")
    F = np.stack([e_acc / wsum, q_acc / wsum], axis=1)
    mask = pareto_mask(F)
    frontier = np.flatnonzero(mask)
    winner = int(frontier[np.argmin(F[mask].sum(axis=1))])
    return F, mask, winner


def robust_traffic_config(sweep: SLOSweepResult,
                          weights: Optional[Dict[str, float]] = None):
    """Fig. 5's robustness normalization, traffic edition: min-max
    normalize (energy_per_token, 1/max_qps) per ARCH over the config list,
    average with the traffic mix weights, Pareto — then the normalized
    winner (argmin of the weighted sum on the frontier).

    Like `robust_serving_config`, an explicit `weights` dict must cover
    the swept archs exactly (a 0.0 share is allowed but must be said).
    Returns (hw, F, mask, winner_idx)."""
    F, mask, winner = _robust_mix_frontier(
        sweep.archs, sweep.max_qps, sweep.energy_per_token, weights,
        "robust_traffic_config")
    return sweep.hw, F, mask, winner


# ------------------------------------------------- winner explanation (obs) --

@dataclasses.dataclass
class WinnerExplanation:
    """WHY the robust-traffic winner wins: per-candidate cost attribution
    at a common operating point, plus winner-vs-rival delta tables.

    `breakdowns[0]` is the winner, then one entry per rival, each a
    traffic-mix-weighted PER-TOKEN `obs.attribution.CostBreakdown`
    (every entry conserves — components sum to totals at 1e-9).
    `deltas[j]` is ``winner.delta(rivals[j])`` (negative = the winner is
    cheaper on that component) and `dominant[j]` names the component
    with the largest absolute delta per kind — the axis that actually
    pays for the flip."""
    hw: np.ndarray                  # (C, 2) candidate configs
    winner: int                     # index into hw
    rivals: List[int]               # indices into hw
    breakdowns: List[object]        # [winner, *rivals] CostBreakdowns
    deltas: List[Dict]              # winner.delta(rival) per rival
    dominant: List[Dict[str, str]]  # per rival: kind -> component name
    rates_qps: Dict[str, float]     # per-arch replay probe rate

    def to_dict(self) -> Dict:
        """Deterministic JSON-ready form (sorted keys downstream)."""
        return {
            "winner": {"h": int(self.hw[self.winner, 0]),
                       "w": int(self.hw[self.winner, 1])},
            "rivals": [{"h": int(self.hw[r, 0]), "w": int(self.hw[r, 1])}
                       for r in self.rivals],
            "breakdowns": [b.to_dict() for b in self.breakdowns],
            "deltas": self.deltas,
            "dominant": self.dominant,
            "rates_qps": {a: float(q)
                          for a, q in sorted(self.rates_qps.items())},
        }


def explain_winner(sweep: SLOSweepResult, traffic, tables,
                   weights: Optional[Dict[str, float]] = None,
                   rivals: Optional[Sequence[int]] = None, sim=None,
                   n_requests: int = 600, seed: int = 0,
                   cache_hit=None, spec_decode=None) -> WinnerExplanation:
    """Explain the `robust_traffic_config` winner with cost attribution.

    Re-runs the winner and its frontier rivals (or an explicit `rivals`
    index list) through the serving simulator with `breakdown=True` at a
    COMMON per-arch probe rate — the largest rate every swept config
    sustains (min over positive `max_qps`, falling back to 1 QPS), so the
    replays see identical arrivals and the component deltas isolate the
    hardware, not the load. Per-arch breakdowns are scaled to
    energy/cycles PER TOKEN and averaged with the traffic-mix weights
    (same convention as the Fig. 5 normalization), then differenced:
    which of compute / queueing / dram_spill / kv_refetch /
    draft_overhead pays for the win.

    `traffic` / `tables` / `cache_hit` / `spec_decode` must match the
    `slo_capacity_sweep` call that produced `sweep` — the explanation
    replays the same scenario, just instrumented."""
    from repro.traffic.sim import SimConfig, simulate

    hw, F, mask, winner = robust_traffic_config(sweep, weights)
    if rivals is None:
        rivals = [int(i) for i in np.flatnonzero(mask) if int(i) != winner]
    rivals = [int(r) for r in rivals]
    archs = sweep.archs
    sim = SimConfig() if sim is None else sim
    per_arch = traffic if isinstance(traffic, dict) else \
        {a: traffic for a in archs}
    per_arch, sim, _ = _kv_scenario(per_arch, sim, cache_hit, spec_decode)
    sim = dataclasses.replace(sim, breakdown=True)

    rates: Dict[str, float] = {}
    for a, arch in enumerate(archs):
        pos = sweep.max_qps[a][sweep.max_qps[a] > 0.0]
        rates[arch] = float(pos.min()) if pos.size else 1.0

    breakdowns = []
    for c in [winner] + rivals:
        h, w = int(hw[c, 0]), int(hw[c, 1])
        acc = None
        for arch in archs:
            wt = 1.0 if weights is None else float(weights[arch])
            if wt == 0.0:
                continue
            trace = per_arch[arch].with_rate(rates[arch]) \
                .sample(n_requests, seed=seed)
            r = simulate(tables.table(arch, h, w), trace, sim)
            b = r.breakdown.scaled(wt / max(r.tokens_out, 1))
            acc = b if acc is None else acc.add(b)
        if acc is None:
            raise ValueError("explain_winner: all mix weights zero")
        acc.label = f"{h}x{w}"
        breakdowns.append(acc.check_conservation())
    deltas = [breakdowns[0].delta(b) for b in breakdowns[1:]]
    dominant = [{kind: (max(d[kind], key=lambda k: abs(d[kind][k]))
                        if d[kind] else "")
                 for kind in ("cycles", "energy")} for d in deltas]
    return WinnerExplanation(hw=hw, winner=winner, rivals=rivals,
                             breakdowns=breakdowns, deltas=deltas,
                             dominant=dominant, rates_qps=rates)


# ---------------------------------------------------- fleet-composition DSE --

@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """One homogeneous pool of fleet servers: `n_servers` replicas, each a
    model instance partitioned over `stages x tp` arrays of shape h x w.
    `role` is "mixed" (the server runs both phases) or "prefill"/"decode"
    (disaggregated serving on differently-shaped arrays)."""
    h: int
    w: int
    n_servers: int
    stages: int = 1
    tp: int = 1
    role: str = "mixed"

    def __post_init__(self):
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown pool role {self.role!r}")
        if min(self.n_servers, self.stages, self.tp) < 1:
            raise ValueError("n_servers, stages and tp must be >= 1")

    @property
    def arrays_per_server(self) -> int:
        return self.stages * self.tp

    @property
    def pes(self) -> int:
        return self.n_servers * self.arrays_per_server * self.h * self.w


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """A fleet composition: pools + routing + pipeline microbatching."""
    name: str
    pools: Tuple[PoolSpec, ...]
    routing: str = "round_robin"
    n_microbatches: int = 4

    @property
    def total_pes(self) -> int:
        return sum(p.pes for p in self.pools)

    @property
    def disaggregated(self) -> bool:
        return any(p.role == "prefill" for p in self.pools)


def enumerate_fleet_specs(pe_budget: int,
                          shapes: Sequence = ((64, 64), (128, 128),
                                              (256, 256)),
                          stages: Sequence[int] = (1, 2, 4),
                          tps: Sequence[int] = (1,),
                          min_fill: float = 0.9,
                          routing: str = "round_robin",
                          n_microbatches: int = 4) -> List[FleetSpec]:
    """Monolithic fleet compositions under an iso-PE budget: for every
    (shape, stages, tp) the largest replica count that fits, kept when it
    uses at least `min_fill` of the budget (a composition that strands
    PEs is not an iso-PE comparison). Disaggregated compositions are
    deployment choices, not grid points — build them explicitly with
    `PoolSpec(role="prefill"/"decode")`."""
    out: List[FleetSpec] = []
    for (h, w) in shapes:
        for s in stages:
            for tp in tps:
                per = int(h) * int(w) * s * tp
                n = pe_budget // per
                if n < 1 or n * per < min_fill * pe_budget:
                    continue
                out.append(FleetSpec(
                    name=f"{n}x[{s}st{('x%dtp' % tp) if tp > 1 else ''}"
                         f"_{h}x{w}]",
                    pools=(PoolSpec(int(h), int(w), n, stages=s, tp=tp),),
                    routing=routing, n_microbatches=n_microbatches))
    return out


class _SpecStageTables:
    """Adapter serving a plain spec-enabled `CostTableSet` through the
    stage-table interface: speculative fleets are restricted to
    single-array servers (stages=1, tp=1), whose tables need no
    partitioning — `resolve_fleet` passes them through so the
    draft/verify lattices survive to the per-server simulator."""
    passthrough = True

    def __init__(self, tables):
        self._tables = tables

    def table(self, arch: str, h: int, w: int, tp: int = 1):
        if tp != 1:
            raise ValueError("speculative fleets are tp=1")
        return self._tables.table(arch, h, w)


def resolve_fleet(stage_tables, arch: str, fleet: FleetSpec, link=None):
    """Materialize a FleetSpec into runnable per-server cost tables
    (`fleet.sim.FleetTables`) + the pipeline plans behind them."""
    from repro.fleet.interconnect import DEFAULT_LINK
    from repro.fleet.partition import partition_server_table
    from repro.fleet.sim import FleetTables
    link = DEFAULT_LINK if link is None else link
    pools: Dict[str, list] = {"mixed": [], "prefill": [], "decode": []}
    plans, cache = [], {}
    passthrough = getattr(stage_tables, "passthrough", False)
    for pool in fleet.pools:
        if passthrough:
            pools[pool.role] += [stage_tables.table(
                arch, pool.h, pool.w, pool.tp)] * pool.n_servers
            continue
        key = (pool.h, pool.w, pool.tp, pool.stages)
        if key not in cache:
            cache[key] = partition_server_table(
                stage_tables.table(arch, pool.h, pool.w, pool.tp),
                n_stages=pool.stages, n_micro=fleet.n_microbatches,
                link=link)
        pools[pool.role] += [cache[key].table] * pool.n_servers
        plans.append(cache[key].plan)
    return FleetTables(mixed=pools["mixed"], prefill=pools["prefill"],
                       decode=pools["decode"]), plans


@dataclasses.dataclass
class FleetSweepResult:
    """Max sustainable QPS under an SLO per (arch, fleet composition)."""
    archs: List[str]
    fleets: List[FleetSpec]
    slo: "object"
    max_qps: np.ndarray             # (A, F)
    energy_per_token: np.ndarray    # (A, F)
    goodput_qps: np.ndarray         # (A, F)
    summaries: List[List[dict]]
    plans: List[List[list]]         # [arch][fleet] -> pipeline plans

    def best(self, arch: str):
        """(FleetSpec, max_qps) of the highest-capacity composition."""
        a = self.archs.index(arch)
        f = int(np.argmax(self.max_qps[a]))
        return self.fleets[f], float(self.max_qps[a, f])


def fleet_capacity_sweep(traffic, slo, fleets: Sequence[FleetSpec],
                         archs: Optional[Sequence[str]] = None,
                         sim=None, link=None, n_requests: int = 800,
                         seed: int = 0, backend: str = "pallas",
                         stage_tables=None, lattices: Optional[dict] = None,
                         pe_budget: Optional[int] = None,
                         search: str = "auto",
                         cache_hit=None, spec_decode=None,
                         windows=None, monitor=None,
                         **model_kw) -> FleetSweepResult:
    """The fleet-composition design space, end to end: every fleet's
    servers are partitioned (DP pipeline splits + tensor splits) over
    stage tables built in ONE fused batched dispatch across all archs,
    shapes and tp degrees, then each (arch, fleet) point is bisected for
    its max sustainable QPS on the multi-server discrete-event simulator.

    `traffic` is one TrafficModel or a per-arch dict (heterogeneous
    mixes; probes draw component-paired traces so compositions compare on
    common random numbers); `sim` a fleet.FleetSimConfig whose routing is
    overridden per FleetSpec; `link` the inter-array LinkModel (pipeline
    boundaries, TP collectives and disaggregated KV shipping);
    `pe_budget`, when given, rejects compositions over budget (iso-PE
    discipline enforced, not assumed). `search` picks the bisection
    engine exactly as in `slo_capacity_sweep` ("auto"/"batched": one
    lockstep bisection over every (arch, fleet) lane with the per-server
    replays packed into one multi-lane engine; bit-identical to
    "sequential"). `windows` / `monitor` add the same burn-rate-aware
    post-bisection scoring as `slo_capacity_sweep` — one windowed fleet
    replay per point at its capacity, summaries annotated with
    worst-window goodput and the `peak_burn_flagged` verdict."""
    from repro.configs.base import list_archs
    from repro.core.search import batched_fleet_max_sustainable_qps
    from repro.fleet.interconnect import DEFAULT_LINK
    from repro.fleet.partition import build_stage_tables
    from repro.fleet.sim import (FleetSimConfig, fleet_max_sustainable_qps)

    if search not in ("auto", "batched", "sequential"):
        raise ValueError(f"unknown search {search!r} "
                         "(have auto|batched|sequential)")
    archs = list(list_archs()) if archs is None else list(archs)
    fleets = list(fleets)
    if not fleets:
        raise ValueError("fleet_capacity_sweep: no fleet compositions")
    if pe_budget is not None:
        over = [f.name for f in fleets if f.total_pes > pe_budget]
        if over:
            raise ValueError(f"fleet_capacity_sweep: over PE budget "
                             f"{pe_budget}: {over[:3]}")
    sim = FleetSimConfig() if sim is None else sim
    link = DEFAULT_LINK if link is None else link
    per_arch = traffic if isinstance(traffic, dict) else \
        {a: traffic for a in archs}
    missing = set(archs) - set(per_arch)
    if missing:
        raise ValueError(f"fleet_capacity_sweep: no traffic model for "
                         f"{sorted(missing)[:3]}")
    per_arch, server_cfg, _ = _kv_scenario(per_arch, sim.server,
                                           cache_hit, spec_decode)
    if server_cfg is not sim.server:
        sim = dataclasses.replace(sim, server=server_cfg)
    if spec_decode is not None:
        # Speculative decode needs the draft/verify lattices, which the
        # pipeline-partitioned stage tables do not carry: restrict to
        # single-array servers (stages=1, tp=1) and resolve those pools
        # straight from spec-enabled plain cost tables.
        bad = [f.name for f in fleets
               if any(p.stages != 1 or p.tp != 1 for p in f.pools)]
        if bad:
            raise ValueError(
                "fleet_capacity_sweep: spec_decode requires single-array "
                f"servers (stages=1, tp=1); offending fleets: {bad[:3]}")

    _tr = _obs_tracer()
    if spec_decode is not None and stage_tables is None:
        from repro.traffic.cost_table import build_cost_tables
        hw = sorted({(p.h, p.w) for f in fleets for p in f.pools})
        with _tr.span("cost_tables", "dse", archs=len(archs),
                      configs=len(hw)):
            spec_tables = build_cost_tables(archs, hw, backend=backend,
                                            spec=spec_decode,
                                            **(lattices or {}),
                                            **model_kw)
        stage_tables = _SpecStageTables(spec_tables)
    elif stage_tables is None:
        hw = sorted({(p.h, p.w) for f in fleets for p in f.pools})
        tps = sorted({p.tp for f in fleets for p in f.pools})
        with _tr.span("stage_tables", "dse", archs=len(archs),
                      configs=len(hw), tps=len(tps)):
            stage_tables = build_stage_tables(archs, hw=hw, tps=tps,
                                              backend=backend,
                                              **(lattices or {}),
                                              **model_kw)

    A, F = len(archs), len(fleets)
    qps = np.zeros((A, F))
    ept = np.zeros((A, F))
    good = np.zeros((A, F))
    summaries: List[List[dict]] = []
    plans: List[List[list]] = []
    with _tr.span("resolve_fleets", "dse", archs=A, fleets=F):
        resolved = [[resolve_fleet(stage_tables, arch, fleet, link)
                     for fleet in fleets] for arch in archs]
    lane_cfgs = [dataclasses.replace(sim, routing=fleet.routing)
                 for fleet in fleets]
    with _tr.span("capacity_search", "dse", search=search, lanes=A * F):
        if search == "sequential":
            points = [
                [fleet_max_sustainable_qps(resolved[a][f][0],
                                           per_arch[arch], slo,
                                           cfg=lane_cfgs[f],
                                           n_requests=n_requests,
                                           seed=seed)
                 for f in range(F)]
                for a, arch in enumerate(archs)]
        else:
            flat = batched_fleet_max_sustainable_qps(
                [resolved[a][f][0] for a in range(A) for f in range(F)],
                [per_arch[arch] for arch in archs for _ in fleets],
                slo, [lane_cfgs[f] for _ in archs for f in range(F)],
                n_requests=n_requests, seed=seed)
            points = [flat[a * F:(a + 1) * F] for a in range(A)]
    for a in range(A):
        row, prow = [], []
        for f in range(F):
            q, summ = points[a][f]
            qps[a, f] = q
            ept[a, f] = summ["energy_per_token"]
            good[a, f] = summ.get("goodput_qps", 0.0)
            row.append(summ)
            prow.append(resolved[a][f][1])
        summaries.append(row)
        plans.append(prow)
    if windows is not None:
        from repro.fleet.sim import simulate_fleet
        wcfg = _windowed_slo_cfg(windows, slo)

        def replay(a, f, q, wc):
            lane = lane_cfgs[f]
            lane = dataclasses.replace(
                lane, server=dataclasses.replace(lane.server, windows=wc))
            return simulate_fleet(
                resolved[a][f][0],
                per_arch[archs[a]].with_rate(q).sample(n_requests, seed,
                                                       paired=True),
                lane)

        with _tr.span("windowed_score", "dse", lanes=A * F):
            _annotate_windowed(qps, summaries, wcfg, monitor, replay)
    return FleetSweepResult(archs=archs, fleets=fleets, slo=slo,
                            max_qps=qps, energy_per_token=ept,
                            goodput_qps=good, summaries=summaries,
                            plans=plans)


def robust_fleet_config(sweep: FleetSweepResult,
                        weights: Optional[Dict[str, float]] = None):
    """Fig. 5's robustness normalization over fleet compositions: min-max
    normalize (energy_per_token, 1/max_qps) per ARCH across the
    composition list, average with the traffic-mix weights, Pareto, then
    the normalized winner. Like the other robust_* variants an explicit
    `weights` dict must cover the swept archs exactly.
    Returns (fleets, F, mask, winner_idx)."""
    F, mask, winner = _robust_mix_frontier(
        sweep.archs, sweep.max_qps, sweep.energy_per_token, weights,
        "robust_fleet_config")
    return sweep.fleets, F, mask, winner
