"""Per-step serving cost lattices: the simulator's O(1) lookup tables.

The discrete-event simulator needs the cost of one engine step — a decode
step over `active` slots whose KV spans average `kv`, or a prefill over a
`prompt`-length request — millions of times per replay. Evaluating the
analytic model per step would dwarf the event loop, so the whole lattice

    decode:  (active-slot count) x (KV-span bucket)
    prefill: (prompt-length bucket)

is precomputed for every (arch, h, w) design point in ONE fused
`dse_eval_batched` Pallas dispatch: each lattice point lowers to a padded
layer table via `extract_workloads` (decode at batch=active/seq=kv,
prefill at batch=1/seq=prompt — exactly the scenario-matrix lowering), the
tables stack into one (S, L, 5) tensor via `core.dse.pad_layer_sets`, and
the shared (h, w) config list sweeps against all of them in a single
kernel call. The simulator's inner loop then only does bilinear/linear
interpolation over the lattice — zero model evaluations.

Interpolation contract (property-tested in tests/test_traffic.py): exact
at lattice points, piecewise-linear between them, clamped outside, and
monotone along the KV/slot axes whenever the underlying lattice is (the
closed forms are non-decreasing in batch and attention span).
"""
from __future__ import annotations

import dataclasses
from bisect import bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ShapeConfig, get_config, list_archs
from repro.core.lm_workloads import extract_workloads
from repro.obs.trace import tracer as _obs_tracer

# Default design points for capacity planning: square sizes spanning the
# paper's grid plus the tall/wide aspect extremes that Fig. 6 shows can
# win on skinny decode GEMMs.
DEFAULT_HW: Tuple[Tuple[int, int], ...] = (
    (32, 32), (64, 64), (128, 128), (256, 256),
    (64, 128), (128, 64), (64, 256), (256, 64))

DEFAULT_SLOT_LATTICE: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
DEFAULT_KV_LATTICE: Tuple[int, ...] = (64, 128, 256, 512, 1024, 2048, 4096)
DEFAULT_PROMPT_LATTICE: Tuple[int, ...] = (16, 64, 128, 256, 512, 1024,
                                           2048, 4096)


def kv_bits_per_token(cfg, act_bits: float = 8.0) -> float:
    """Bits of KV-cache residency one decoded token adds across all
    attention layers (K and V; grouped-query heads). SSM/recurrent layers
    carry constant state — they add nothing per token (the xLSTM family
    reports 0.0)."""
    if cfg.family == "ssm":
        return 0.0
    n_attn = sum(1 for i in range(cfg.num_layers) if cfg.is_attn_layer(i))
    return 2.0 * n_attn * cfg.num_kv_heads * cfg.resolved_head_dim * act_bits


def _interp_axis(lattice: List[float], x: float) -> Tuple[int, float]:
    """Clamped linear-interpolation coordinates: (left index, fraction)."""
    if x <= lattice[0]:
        return 0, 0.0
    if x >= lattice[-1]:
        return len(lattice) - 2, 1.0
    i = bisect_right(lattice, x) - 1
    return i, (x - lattice[i]) / (lattice[i + 1] - lattice[i])


@dataclasses.dataclass(frozen=True)
class SpecDecodeConfig:
    """Draft/verify speculative decoding as a cost-table axis.

    One decode ROUND runs `k` draft-model steps then ONE target-model
    verify step over all `k + 1` candidate positions (each speculated
    token is a GEMM row, so verify lowers as decode at batch
    `slots * (k + 1)`). Acceptance follows the standard leading-run
    model: among the k drafts, the round emits `1 + run` tokens where
    `run` is the leading run of iid Bernoulli(`acceptance`) successes —
    between 1 and k+1 tokens per round. `seed` drives the acceptance
    draws (`spec_round_counts`), so a replay is deterministic."""
    draft_arch: str
    k: int = 4
    acceptance: float = 0.7
    seed: int = 0

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if not 0.0 <= self.acceptance <= 1.0:
            raise ValueError(
                f"acceptance must be in [0, 1], got {self.acceptance}")


def spec_round_counts(output_len, k: int, acceptance: float,
                      seed: int = 0) -> np.ndarray:
    """(n,) draft/verify rounds to emit each request's `output_len`
    tokens under the leading-run acceptance model — a pure function of
    (output_len, k, acceptance, seed), drawn from a dedicated child
    stream so it shares no entropy with trace sampling. Exact token
    accounting: request i's accepted-beyond-baseline tokens are
    `output_len[i] - rounds[i]` (every round emits its verify token plus
    the accepted draft run), which is what the `sim.accepted_tokens`
    counter reconciles against."""
    olen = np.asarray(output_len, np.int64)
    if olen.ndim != 1:
        raise ValueError("output_len must be 1-d")
    rng = np.random.default_rng([int(seed), 0x5bec])
    remaining = olen.copy()
    rounds = np.zeros(len(olen), np.int64)
    alive = remaining > 0
    while alive.any():
        u = rng.random((int(alive.sum()), k))
        run = (u < acceptance).cumprod(axis=1).sum(axis=1)  # in [0, k]
        remaining[alive] -= np.minimum(run + 1, remaining[alive])
        rounds[alive] += 1
        alive = remaining > 0
    return rounds


@dataclasses.dataclass
class CostTable:
    """Per-step cost lattice of ONE (arch, h, w) design point.

    All lookups are scalar-in/scalar-out pure-Python (bisect + affine
    blend) — they are the simulator's hot path and must not touch numpy
    per call."""
    arch: str
    h: int
    w: int
    clockless: bool = True              # costs are cycles / Eq. 1 units
    slot_lattice: List[float] = dataclasses.field(default_factory=list)
    kv_lattice: List[float] = dataclasses.field(default_factory=list)
    prompt_lattice: List[float] = dataclasses.field(default_factory=list)
    # decode lattices, indexed [slot][kv]
    decode_cycles: List[List[float]] = dataclasses.field(default_factory=list)
    decode_energy: List[List[float]] = dataclasses.field(default_factory=list)
    decode_macs: List[List[float]] = dataclasses.field(default_factory=list)
    # prefill lattices, indexed [prompt]
    prefill_cycles: List[float] = dataclasses.field(default_factory=list)
    prefill_energy: List[float] = dataclasses.field(default_factory=list)
    kv_bits_per_token: float = 0.0
    pe: float = 0.0                     # h * w (utilization normalizer)
    # speculative-decode lattices (empty unless built with spec=...):
    # draft_* is the DRAFT arch's decode step on this same (h, w) array;
    # verify_* is the target arch's decode step at batch slot*(k+1) —
    # both indexed [slot][kv] on the shared lattices above.
    spec_k: int = 0                     # 0 => no spec lattices
    draft_arch: str = ""
    draft_cycles: List[List[float]] = dataclasses.field(default_factory=list)
    draft_energy: List[List[float]] = dataclasses.field(default_factory=list)
    draft_macs: List[List[float]] = dataclasses.field(default_factory=list)
    verify_cycles: List[List[float]] = dataclasses.field(
        default_factory=list)
    verify_energy: List[List[float]] = dataclasses.field(
        default_factory=list)
    verify_macs: List[List[float]] = dataclasses.field(default_factory=list)
    # pipeline-parallel bubble fraction of the stage schedule this table
    # was synthesized from (fleet/partition.partition_server_table); 0 for
    # unpartitioned tables. The fleet attribution splits each server's
    # compute time by it — the charged totals never read it.
    pipeline_bubble: float = 0.0

    # ------------------------------------------------------------- lookups --
    def _bilerp(self, grid: List[List[float]], active: float,
                kv: float) -> float:
        i, fa = _interp_axis(self.slot_lattice, active)
        j, fk = _interp_axis(self.kv_lattice, kv)
        lo = grid[i][j] + fk * (grid[i][j + 1] - grid[i][j])
        hi = grid[i + 1][j] + fk * (grid[i + 1][j + 1] - grid[i + 1][j])
        return lo + fa * (hi - lo)

    def decode_step(self, active: float, kv: float) -> float:
        """Cycles of one decode step: bilinear over (slots, kv span)."""
        return self._bilerp(self.decode_cycles, active, kv)

    def decode_step_energy(self, active: float, kv: float) -> float:
        return self._bilerp(self.decode_energy, active, kv)

    def decode_step_macs(self, active: float, kv: float) -> float:
        return self._bilerp(self.decode_macs, active, kv)

    def prefill(self, prompt_len: float) -> Tuple[float, float]:
        """(cycles, energy) of a batch-1 prefill over `prompt_len` tokens."""
        i, f = _interp_axis(self.prompt_lattice, prompt_len)
        c = self.prefill_cycles
        e = self.prefill_energy
        return (c[i] + f * (c[i + 1] - c[i]),
                e[i] + f * (e[i + 1] - e[i]))

    # ------------------------------------------- speculative-decode lookups --
    @property
    def has_spec(self) -> bool:
        return self.spec_k > 0 and bool(self.draft_cycles)

    def draft_step(self, active: float, kv: float) -> float:
        """Cycles of ONE draft-model decode step at `active` slots."""
        return self._bilerp(self.draft_cycles, active, kv)

    def draft_step_energy(self, active: float, kv: float) -> float:
        return self._bilerp(self.draft_energy, active, kv)

    def draft_step_macs(self, active: float, kv: float) -> float:
        return self._bilerp(self.draft_macs, active, kv)

    def verify_step(self, active: float, kv: float) -> float:
        """Cycles of ONE target-model verify step over `active` slots'
        k+1 candidate positions (lowered at batch `active * (k + 1)`;
        the slot axis is still addressed by `active`)."""
        return self._bilerp(self.verify_cycles, active, kv)

    def verify_step_energy(self, active: float, kv: float) -> float:
        return self._bilerp(self.verify_energy, active, kv)

    def verify_step_macs(self, active: float, kv: float) -> float:
        return self._bilerp(self.verify_macs, active, kv)


@dataclasses.dataclass
class CostTableSet:
    """All (arch, h, w) tables from one build, plus build provenance."""
    tables: Dict[Tuple[str, int, int], CostTable]
    archs: List[str]
    hw: List[Tuple[int, int]]
    n_scenarios: int                 # lattice points lowered (all archs)
    n_configs: int                   # design points swept
    backend: str
    build_seconds: float = 0.0

    def table(self, arch: str, h: int, w: int) -> CostTable:
        return self.tables[(arch, int(h), int(w))]

    def __len__(self) -> int:
        return len(self.tables)


def _lattice_shapes(slot_lattice, kv_lattice, prompt_lattice):
    """The ShapeConfig lowering of every lattice point of one arch, decode
    points first (row-major over (slot, kv)), then prefill points."""
    shapes = [ShapeConfig(f"d{b}x{s}", int(s), int(b), "decode")
              for b in slot_lattice for s in kv_lattice]
    shapes += [ShapeConfig(f"p{p}", int(p), 1, "prefill")
               for p in prompt_lattice]
    return shapes


def build_cost_tables(archs: Optional[Sequence[str]] = None,
                      hw: Sequence[Tuple[int, int]] = DEFAULT_HW,
                      slot_lattice: Sequence[int] = DEFAULT_SLOT_LATTICE,
                      kv_lattice: Sequence[int] = DEFAULT_KV_LATTICE,
                      prompt_lattice: Sequence[int] = DEFAULT_PROMPT_LATTICE,
                      backend: str = "pallas",
                      act_bits: float = 8.0,
                      spec: Optional[SpecDecodeConfig] = None,
                      **model_kw) -> CostTableSet:
    """Build every (arch, h, w) cost table in one fused batched dispatch.

    `backend="pallas"` (default) stacks ALL archs' lattice points — decode
    (slots x kv) plus prefill (prompt) — into a single (S, L, 5) layer-set
    tensor and makes ONE `dse_eval_batched` call over the shared (h, w)
    config list. `backend="numpy"` is the float64 per-scenario reference
    loop (used by the equivalence tests and the deterministic golden
    fixture); `backend="pallas-loop"` is the one-dispatch-per-lattice-point
    baseline the benchmark times the fusion against.

    `spec` additionally lowers two speculative-decode lattices per arch
    into the SAME dispatch: the draft arch's decode grid (same slot/kv
    lattices, same (h, w) array) and the target arch's verify grid at
    batch `slot * (k + 1)`. The default `spec=None` adds no lattice
    point and produces byte-identical tables.

    Traces `tables.lower` (the lattice lowering) and `tables.assemble`
    (the `CostTable`s from the kernel's columns).
    """
    import time

    archs = list(list_archs()) if archs is None else list(archs)
    hw = [(int(h), int(w)) for h, w in hw]
    slot_l = [float(b) for b in slot_lattice]
    kv_l = [float(s) for s in kv_lattice]
    prompt_l = [float(p) for p in prompt_lattice]
    nb, nk, npr = len(slot_l), len(kv_l), len(prompt_l)
    per_arch = nb * nk + npr
    if spec is not None:
        draft_cfg = get_config(spec.draft_arch)
        per_arch += 2 * nb * nk

    tr = _obs_tracer()
    with tr.span("tables.lower", "dse"):
        workload_lists, metas = [], []
        for arch in archs:
            cfg = get_config(arch)
            for shape in _lattice_shapes(slot_lattice, kv_lattice,
                                         prompt_lattice):
                workload_lists.append(extract_workloads(cfg, shape))
            if spec is not None:
                # draft-model steps: the draft arch's decode lattice
                for b in slot_lattice:
                    for s in kv_lattice:
                        workload_lists.append(extract_workloads(
                            draft_cfg,
                            ShapeConfig(f"sd{b}x{s}", int(s), int(b),
                                        "decode")))
                # verify batches: each of the k+1 speculated positions is a
                # GEMM row, so one verify step is decode at batch b*(k+1)
                for b in slot_lattice:
                    for s in kv_lattice:
                        workload_lists.append(extract_workloads(
                            cfg,
                            ShapeConfig(f"sv{b}x{s}", int(s),
                                        int(b) * (spec.k + 1), "decode")))
            metas.append((arch, kv_bits_per_token(cfg, act_bits)))

    t0 = time.perf_counter()
    cols = _eval_lattice(workload_lists, hw, backend, **model_kw)
    build_s = time.perf_counter() - t0

    with tr.span("tables.assemble", "dse"):
        # cols: (S, C) arrays for cycles / energy / macs
        tables: Dict[Tuple[str, int, int], CostTable] = {}
        for a, (arch, kvb) in enumerate(metas):
            base = a * per_arch
            dec = slice(base, base + nb * nk)
            pre = slice(base + nb * nk, base + nb * nk + npr)
            for c, (h, w) in enumerate(hw):
                dc = cols["cycles"][dec, c].reshape(nb, nk)
                de = cols["energy"][dec, c].reshape(nb, nk)
                dm = cols["macs"][dec, c].reshape(nb, nk)
                spec_kw = {}
                if spec is not None:
                    sd = slice(base + nb * nk + npr,
                               base + nb * nk + npr + nb * nk)
                    sv = slice(base + nb * nk + npr + nb * nk, base + per_arch)
                    spec_kw = dict(
                        spec_k=int(spec.k), draft_arch=spec.draft_arch,
                        draft_cycles=cols["cycles"][sd, c]
                        .reshape(nb, nk).tolist(),
                        draft_energy=cols["energy"][sd, c]
                        .reshape(nb, nk).tolist(),
                        draft_macs=cols["macs"][sd, c]
                        .reshape(nb, nk).tolist(),
                        verify_cycles=cols["cycles"][sv, c]
                        .reshape(nb, nk).tolist(),
                        verify_energy=cols["energy"][sv, c]
                        .reshape(nb, nk).tolist(),
                        verify_macs=cols["macs"][sv, c]
                        .reshape(nb, nk).tolist())
                tables[(arch, h, w)] = CostTable(
                    arch=arch, h=h, w=w,
                    slot_lattice=slot_l, kv_lattice=kv_l,
                    prompt_lattice=prompt_l,
                    decode_cycles=dc.tolist(), decode_energy=de.tolist(),
                    decode_macs=dm.tolist(),
                    prefill_cycles=cols["cycles"][pre, c].tolist(),
                    prefill_energy=cols["energy"][pre, c].tolist(),
                    kv_bits_per_token=kvb, pe=float(h * w), **spec_kw)
    return CostTableSet(tables=tables, archs=archs, hw=hw,
                        n_scenarios=len(workload_lists), n_configs=len(hw),
                        backend=backend, build_seconds=build_s)


def _eval_lattice(workload_lists, hw, backend, **model_kw):
    """(S, C) metric columns for S lattice points x C configs."""
    cfgs = np.asarray(hw, np.float64)
    C = cfgs.shape[0]
    if backend == "numpy":
        from repro.core import systolic
        h = cfgs[:, 0]
        w = cfgs[:, 1]
        out = {k: np.empty((len(workload_lists), C), np.float64)
               for k in ("cycles", "energy", "macs")}
        for i, wls in enumerate(workload_lists):
            m = systolic.analyze_network(list(wls), h, w, **model_kw)
            for k in out:
                out[k][i] = np.broadcast_to(
                    np.asarray(getattr(m, k), np.float64), (C,))
        return out
    if backend == "pallas-loop":
        # one dse_eval dispatch per lattice point: the unfused baseline
        from repro.core.dse import _pallas_eval_configs
        out = {k: np.empty((len(workload_lists), C), np.float64)
               for k in ("cycles", "energy", "macs")}
        for i, wls in enumerate(workload_lists):
            col = _pallas_eval_configs(wls, cfgs, **model_kw)
            for k in out:
                out[k][i] = col[k]
        return out
    if backend == "pallas":
        from repro.core.dse import pad_layer_sets
        from repro.kernels import ops
        from repro.kernels.dse_eval import OUT_COLS
        tr = _obs_tracer()
        with tr.span("sweep.put", "dse"):
            layer_sets = pad_layer_sets(workload_lists)
        out = ops.sweep_batched(cfgs, layer_sets, **model_kw)
        with tr.span("sweep.fetch", "dse"):
            out = np.asarray(out)
        with tr.span("sweep.assemble", "dse"):
            return {k: out[:, :, OUT_COLS.index(k)].astype(np.float64)
                    for k in ("cycles", "energy", "macs")}
    raise ValueError(
        f"unknown backend {backend!r} (numpy|pallas|pallas-loop)")
