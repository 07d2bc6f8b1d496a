"""Model partitioning across array pools: pipeline splits + tensor splits.

A fleet server is a *group* of arrays that jointly hold one model
instance: `n_stages` pipeline stages (layer-contiguous spans chosen by DP
over per-stage cycle tables) each replicated over `tp` tensor-parallel
ranks (head/column splits that lower back into `model_core` workloads,
plus collective wire terms). The output is a synthesized
`traffic.cost_table.CostTable` for the whole server, so the discrete-event
simulator and the SLO bisection run on partitioned servers unchanged.

Three closed-form anchors pin the construction (tests/test_fleet.py):

  * `tp_parallel_metrics` over a FREE link reproduces the paper's
    `multi_array` dataflow exactly (cycles equal, energy = P x per-array);
  * a 1-stage, tp=1, free-link server table is bit-equal (modulo float
    summation order) to `traffic.build_cost_tables`;
  * `pipeline_pass_cycles` — the exact event-level fill-drain recurrence —
    collapses to the GPipe closed form on uniform stages: makespan
    (M + S - 1) * c, bubble fraction (S - 1) / (M + S - 1), mirroring
    `sharding/pipeline.py`.

Stage tables are built the same way `traffic.cost_table` builds its
lattices: every (block kind, tp, lattice point) lowers to a padded layer
table and ALL of them sweep against the shared (h, w) config list in ONE
fused `dse_eval_batched` dispatch (`build_stage_tables`). Blocks of one
architecture repeat a handful of kinds (attention layer, MoE layer,
unembedding, ...), so the dispatch stays small while the DP sees a
per-block table: stage cost is a prefix-sum difference because every
closed-form counter is additive over layers.

Boundary traffic follows the residual stream (tokens x d_model words per
cut, plus the encoder output on post-encoder cuts of enc-dec models) —
cross-checked against `graph.ir.Graph.cut_bits` on the full serving graph.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig, get_config, \
    list_archs, resolve_dims
from repro.core.lm_workloads import (_attn_workloads, _mamba_workloads,
                                     _mlp_workloads, _moe_workloads)
from repro.core.workloads import Workload
from repro.fleet.interconnect import (FREE_LINK, LinkModel, allgather_bits,
                                      ring_allreduce_bits)
from repro.traffic.cost_table import (DEFAULT_HW, DEFAULT_KV_LATTICE,
                                      DEFAULT_PROMPT_LATTICE,
                                      DEFAULT_SLOT_LATTICE, CostTable,
                                      _eval_lattice)

DEFAULT_ACT_BITS = 8.0


# ------------------------------------------------------ per-block lowering --

def block_plan(cfg: ArchConfig) -> List[str]:
    """Pipeline-block kinds of one architecture, in layer order: encoder
    blocks (enc-dec models), one block per decoder layer (mirroring
    `graph.builders._layer_plan` so counts match the flat lowering), and
    the unembedding. Concatenating `block_workloads` over this plan
    reproduces `extract_workloads` GEMM totals exactly (anchor-tested)."""
    from repro.graph.builders import _layer_plan
    kinds: List[str] = []
    if cfg.family == "audio":
        kinds += ["enc"] * cfg.encoder_layers
    for mixer, mlp in _layer_plan(cfg):
        parts = [mixer]
        if cfg.family == "audio":
            parts.append("xattn")
        parts.append(mlp)
        kinds.append("+".join(p for p in parts if p))
    kinds.append("unembed")
    return kinds


def block_workloads(cfg: ArchConfig, kind: str, *, B: int, Sq: int,
                    Skv: int, T: int) -> List[Workload]:
    """GEMM rows of ONE pipeline block at serving dims (B, Sq, Skv, T),
    built from the same `lm_workloads` component helpers as the flat
    extraction with a layer count of 1 — every counter is linear in
    repeats, so block sums equal whole-model metrics exactly."""
    d = resolve_dims(cfg, 1)
    wl: List[Workload] = []
    for part in kind.split("+"):
        if part == "attn":
            wl += _attn_workloads(cfg, B, Sq, Skv, 1)
        elif part == "enc":
            te = B * cfg.encoder_seq
            wl += _attn_workloads(cfg, B, cfg.encoder_seq, cfg.encoder_seq, 1)
            wl += _mlp_workloads(cfg, te, 1)
        elif part == "xattn":
            wl += [(Sq, d.head_dim, cfg.encoder_seq, B * cfg.num_heads, 1),
                   (Sq, cfg.encoder_seq, d.head_dim, B * cfg.num_heads, 1),
                   (T, cfg.d_model, cfg.d_model, 1, 2)]
        elif part == "mamba":
            wl += _mamba_workloads(cfg, T, 1)
        elif part == "mlstm":
            din = 2 * cfg.d_model
            wl += [(T, cfg.d_model, 2 * din, 1, 1),
                   (T, din, 3 * din + 2 * cfg.num_heads, 1, 1),
                   (T, din, cfg.d_model, 1, 1)]
        elif part == "slstm":
            wl += [(T, cfg.d_model, 4 * cfg.d_model, 1, 1),
                   (T, cfg.d_model, cfg.d_model, 1, 1)]
        elif part == "mlp":
            wl += _mlp_workloads(cfg, T, 1)
        elif part == "moe":
            wl += _moe_workloads(cfg, T, 1)
        elif part == "unembed":
            # serving emits one position per sequence (t_out = B); train
            # rewrites this to all T positions in `arch_block_workloads`
            wl.append((B, cfg.d_model, cfg.vocab_size, 1, 1))
        else:
            raise ValueError(f"unknown block part {part!r}")
    return wl


def _serving_dims(shape: ShapeConfig) -> Tuple[int, int, int, int]:
    """(B, Sq, Skv, T) under the `lm_workloads` serving conventions."""
    if shape.kind == "decode":
        return shape.global_batch, 1, shape.seq_len, shape.global_batch
    B = shape.global_batch
    return B, shape.seq_len, shape.seq_len, B * shape.seq_len


def arch_block_workloads(cfg: ArchConfig,
                         shape: ShapeConfig) -> List[List[Workload]]:
    """Per-block workload lists of the whole model at one serving shape
    (train triples repeats like the flat lowering). Concatenated, the
    (M, K, N, groups) -> repeats totals equal `extract_workloads`."""
    B, Sq, Skv, T = _serving_dims(shape)
    out = [block_workloads(cfg, kind, B=B, Sq=Sq, Skv=Skv, T=T)
           for kind in block_plan(cfg)]
    if shape.kind == "train":
        # training unembeds every position and triples GEMM volume
        # (dgrad + wgrad), exactly like the flat lowering
        out[-1] = [(T, cfg.d_model, cfg.vocab_size, 1, 1)]
        out = [[(m, k, n, g, 3 * r) for (m, k, n, g, r) in wls]
               for wls in out]
    return out


# -------------------------------------------------------- tensor-parallel --

def tp_split_workloads(workloads: Sequence[Workload], tp: int,
                       split: str = "auto") -> List[Workload]:
    """One rank's share of a `tp`-way tensor-parallel pass.

    ``split="column"`` divides every GEMM's N over the ranks (output-
    channel parallel, ceil like the paper's `multi_array` N-partition);
    ``split="auto"`` keeps that for dense GEMMs but divides the *group*
    axis for per-head/per-expert grouped GEMMs (head parallelism — the
    natural LM split, since a head's score GEMM cannot be column-cut
    without breaking the softmax)."""
    if split not in ("auto", "column"):
        raise ValueError(f"unknown split {split!r} (auto|column)")
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    out: List[Workload] = []
    for (m, k, n, g, r) in workloads:
        if split == "auto" and g > 1:
            out.append((m, k, n, -(-g // tp), r))
        else:
            out.append((m, k, -(-n // tp), g, r))
    return out


def tp_parallel_metrics(workloads: Sequence[Workload], h, w, tp: int,
                        link: LinkModel = FREE_LINK, split: str = "column",
                        act_bits: float = DEFAULT_ACT_BITS,
                        **model_kw) -> Dict[str, object]:
    """Aggregate metrics of one pass tensor-partitioned over `tp` arrays.

    Cycles are the parallel makespan (one rank's pass plus the collective
    wire time); energy sums all ranks plus the collective traffic. Each
    workload's full output activation is re-gathered for the next layer
    (`allgather_bits`), which is the term the paper's free-interconnect
    `multi_array` dataflow drops: with ``link=FREE_LINK`` and
    ``split="column"`` this reproduces `analyze_network(...,
    dataflow="multi_array", n_arrays=tp)` exactly (the differential
    anchor in tests/test_fleet.py)."""
    from repro.core import systolic
    per_rank = systolic.analyze_network(
        tp_split_workloads(workloads, tp, split=split), h, w, **model_kw)
    coll_bits = sum(allgather_bits(float(m * n * g * r) * act_bits, tp)
                    for (m, k, n, g, r) in workloads)
    coll_cycles = link.transfer_cycles(coll_bits)
    return {
        "cycles": np.asarray(per_rank.cycles) + coll_cycles,
        "energy": tp * np.asarray(per_rank.energy)
        + link.transfer_energy(coll_bits),
        "collective_bits": coll_bits,
        "per_rank": per_rank,
    }


# ------------------------------------------------------------ DP partition --

def _stage_cost(pref: np.ndarray, bnd: Optional[np.ndarray], i: int,
                j: int, L: int) -> float:
    """Cost of stage [i, j): compute plus the boundary transfers it takes
    part in (receive at i, send at j — store-and-forward both ways)."""
    c = pref[j] - pref[i]
    if bnd is not None:
        if i > 0:
            c += bnd[i - 1]
        if j < L:
            c += bnd[j - 1]
    return float(c)


def dp_pipeline_split(costs: Sequence[float], n_stages: int,
                      boundary_costs: Optional[Sequence[float]] = None
                      ) -> Tuple[Tuple[int, ...], float]:
    """Layer-contiguous split of `costs` (per-block cycles) into
    `n_stages` stages minimizing the BOTTLENECK stage cost — the steady-
    state pipeline throughput objective. `boundary_costs[i]` (optional,
    length L-1) is the transfer cost of cutting between blocks i and i+1,
    charged to both adjacent stages.

    Returns (bounds, bottleneck) with bounds = (0, b1, ..., L): stage s
    owns blocks [bounds[s], bounds[s+1]). O(L^2 * S) exact DP (matches
    brute-force enumeration; hypothesis-tested)."""
    costs = np.asarray(costs, np.float64)
    L = len(costs)
    if not 1 <= n_stages <= L:
        raise ValueError(f"need 1 <= n_stages <= {L}, got {n_stages}")
    bnd = None if boundary_costs is None \
        else np.asarray(boundary_costs, np.float64)
    if bnd is not None and len(bnd) != L - 1:
        raise ValueError(f"boundary_costs must have length {L - 1}")
    pref = np.concatenate([[0.0], np.cumsum(costs)])
    INF = float("inf")
    f = np.full((n_stages + 1, L + 1), INF)
    arg = np.zeros((n_stages + 1, L + 1), np.int64)
    for j in range(1, L + 1):
        f[1][j] = _stage_cost(pref, bnd, 0, j, L)
    for s in range(2, n_stages + 1):
        for j in range(s, L + 1):
            best, bi = INF, s - 1
            for i in range(s - 1, j):
                v = max(f[s - 1][i], _stage_cost(pref, bnd, i, j, L))
                if v < best:
                    best, bi = v, i
            f[s][j], arg[s][j] = best, bi
    bounds = [L]
    for s in range(n_stages, 1, -1):
        bounds.append(int(arg[s][bounds[-1]]))
    bounds.append(0)
    return tuple(reversed(bounds)), float(f[n_stages][L])


def brute_force_split(costs: Sequence[float], n_stages: int,
                      boundary_costs: Optional[Sequence[float]] = None
                      ) -> Tuple[Tuple[int, ...], float]:
    """Exhaustive reference for `dp_pipeline_split` (small L only)."""
    costs = np.asarray(costs, np.float64)
    L = len(costs)
    bnd = None if boundary_costs is None \
        else np.asarray(boundary_costs, np.float64)
    pref = np.concatenate([[0.0], np.cumsum(costs)])
    best, best_bounds = float("inf"), None
    for cuts in itertools.combinations(range(1, L), n_stages - 1):
        bounds = (0,) + cuts + (L,)
        bot = max(_stage_cost(pref, bnd, bounds[s], bounds[s + 1], L)
                  for s in range(n_stages))
        if bot < best:
            best, best_bounds = bot, bounds
    return best_bounds, best


# --------------------------------------------------- GPipe fill-drain math --

def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe fill-drain bubble: (S - 1) / (M + S - 1) — the same closed
    form as `sharding.pipeline.bubble_fraction` (mirrored here so the
    analytical fleet layer does not import the jax execution layer)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_pass_cycles(stage_cycles, n_micro: int, xfer=None,
                         micro_axis: bool = False):
    """Exact makespan of one fill-drain pipeline pass, by the event-level
    recurrence: microbatch m enters stage s when BOTH stage s finished
    microbatch m-1 AND stage s-1's copy of m has arrived over the link —
    t[s][m] = max(t[s][m-1], t[s-1][m] + xfer[s-1]) + c[s][m].

    `stage_cycles` is (S, ...) per-microbatch stage cycles (trailing dims
    broadcast, e.g. a KV-span lattice axis), or (M, S, ...) when
    `micro_axis=True` (microbatches of unequal cost — e.g. chunked
    prefill, where later chunks attend over a longer prefix); `xfer` is
    (S-1, ...) link cycles per boundary. On uniform stages with free
    links this collapses to the GPipe closed form (M + S - 1) * c — i.e.
    a bubble fraction of exactly `bubble_fraction(S, M)`
    (property-tested)."""
    stage_cycles = np.asarray(stage_cycles, np.float64)
    if not micro_axis:
        stage_cycles = np.broadcast_to(
            stage_cycles, (int(n_micro),) + stage_cycles.shape)
    elif stage_cycles.shape[0] != int(n_micro):
        raise ValueError(f"micro_axis stage_cycles has "
                         f"{stage_cycles.shape[0]} rows != M={n_micro}")
    S = stage_cycles.shape[1]
    tail = stage_cycles.shape[2:]
    if xfer is None or S == 1:
        xfer = np.zeros((max(S - 1, 1),) + tail)
    else:
        xfer = np.broadcast_to(np.asarray(xfer, np.float64),
                               (S - 1,) + tail)
    prev = np.zeros((S,) + tail, np.float64)
    for m in range(int(n_micro)):
        inbound = np.zeros(tail, np.float64)
        for s in range(S):
            start = np.maximum(inbound, prev[s])
            prev[s] = start + stage_cycles[m, s]
            if s < S - 1:
                inbound = prev[s] + xfer[s]
    return prev[S - 1]


# ------------------------------------------------------------ stage tables --

@dataclasses.dataclass
class StageTables:
    """Per-block cost lattices of ONE (arch, h, w, tp) design point — the
    DP partitioner's input. Decode lattices are (L, slots, kv spans);
    prefill lattices (L, prompts). Boundary/collective entries are BIT
    counts (link-independent; the partitioner prices them with its
    `LinkModel`)."""
    arch: str
    h: int
    w: int
    tp: int
    kinds: List[str]
    slot_lattice: List[float]
    kv_lattice: List[float]
    prompt_lattice: List[float]
    dec_cycles: np.ndarray       # (L, nb, nk)
    dec_energy: np.ndarray
    dec_macs: np.ndarray
    pre_cycles: np.ndarray       # (L, npr)
    pre_energy: np.ndarray
    bnd_dec_bits: np.ndarray     # (L-1, nb) bits crossing cut i per step
    bnd_pre_bits: np.ndarray     # (L-1, npr)
    coll_dec_bits: np.ndarray    # (L, nb) tp-collective bits per step
    coll_pre_bits: np.ndarray    # (L, npr)
    kv_bits_per_block: np.ndarray  # (L,) KV bits one token adds per block

    @property
    def n_blocks(self) -> int:
        return len(self.kinds)


@dataclasses.dataclass
class StageTableSet:
    """All (arch, h, w, tp) stage tables from one fused build."""
    tables: Dict[Tuple[str, int, int, int], StageTables]
    archs: List[str]
    hw: List[Tuple[int, int]]
    tps: List[int]
    n_scenarios: int
    n_configs: int
    backend: str
    build_seconds: float = 0.0

    def table(self, arch: str, h: int, w: int, tp: int = 1) -> StageTables:
        return self.tables[(arch, int(h), int(w), int(tp))]

    def __len__(self) -> int:
        return len(self.tables)


def _block_bits(cfg: ArchConfig, kinds: List[str], tp: int,
                slot_l: List[float], prompt_l: List[float],
                act_bits: float):
    """(bnd_dec, bnd_pre, coll_dec, coll_pre, kv_per_block) bit tables.

    Boundary cuts carry the residual stream (tokens x d_model words);
    every cut at or past the encoder/decoder seam of an enc-dec model
    additionally carries the encoder output, which all downstream decoder
    stages consume (cross-checked against `Graph.cut_bits` on the serving
    graph). Collectives per block: one ring all-reduce of the residual per
    row-parallel sub-block (Megatron convention), an all-gather of the
    sharded logits at the unembedding."""
    L = len(kinds)
    dmb = cfg.d_model * act_bits
    n_enc = sum(1 for k in kinds if k == "enc")
    slot = np.asarray(slot_l, np.float64)
    prompt = np.asarray(prompt_l, np.float64)

    # tokens crossing cut i: decode moves B (= slots) stream tokens, the
    # batch's encoder frames ride along past the seam; prefill is batch 1.
    bnd_dec = np.empty((max(L - 1, 0), len(slot)))
    bnd_pre = np.empty((max(L - 1, 0), len(prompt)))
    for i in range(L - 1):
        enc_dec = slot * cfg.encoder_seq if (n_enc and i >= n_enc - 1) \
            else 0.0
        enc_pre = float(cfg.encoder_seq) if (n_enc and i >= n_enc - 1) \
            else 0.0
        bnd_dec[i] = (slot + enc_dec) * dmb
        bnd_pre[i] = (prompt + enc_pre) * dmb

    coll_dec = np.zeros((L, len(slot)))
    coll_pre = np.zeros((L, len(prompt)))
    kv_blk = np.zeros(L)
    kv_bits = 2.0 * cfg.num_kv_heads * cfg.resolved_head_dim * act_bits
    for l, kind in enumerate(kinds):
        parts = kind.split("+")
        if "attn" in parts and cfg.family != "ssm":
            kv_blk[l] = kv_bits
        if tp > 1:
            if kind == "unembed":
                coll_dec[l] = allgather_bits(
                    slot * cfg.vocab_size * act_bits, tp)
                coll_pre[l] = allgather_bits(
                    np.full(len(prompt), cfg.vocab_size * act_bits), tp)
            else:
                n_ar = sum(2 if p == "enc" else 1 for p in parts)
                tok_d = slot * cfg.encoder_seq if kind == "enc" else slot
                tok_p = (np.full(len(prompt), float(cfg.encoder_seq))
                         if kind == "enc" else prompt)
                coll_dec[l] = n_ar * ring_allreduce_bits(1.0, tp) \
                    * tok_d * dmb
                coll_pre[l] = n_ar * ring_allreduce_bits(1.0, tp) \
                    * tok_p * dmb
    return bnd_dec, bnd_pre, coll_dec, coll_pre, kv_blk


def build_stage_tables(archs: Optional[Sequence[str]] = None,
                       hw: Sequence[Tuple[int, int]] = DEFAULT_HW,
                       tps: Sequence[int] = (1,),
                       slot_lattice: Sequence[int] = DEFAULT_SLOT_LATTICE,
                       kv_lattice: Sequence[int] = DEFAULT_KV_LATTICE,
                       prompt_lattice: Sequence[int] = DEFAULT_PROMPT_LATTICE,
                       backend: str = "pallas",
                       act_bits: float = DEFAULT_ACT_BITS,
                       **model_kw) -> StageTableSet:
    """Build per-block stage tables for every (arch, h, w, tp) point in
    ONE fused batched dispatch — the `scenario_sweep`/`build_cost_tables`
    trick applied to pipeline stages: every (distinct block kind, tp,
    lattice point) lowers to a padded layer table, all of them sweep the
    shared (h, w) config list in a single `dse_eval_batched` call
    (`backend="pallas"`), and the per-BLOCK lattices scatter out of the
    per-kind columns. `backend="numpy"` is the float64 reference;
    `backend="pallas-loop"` the one-dispatch-per-stage baseline the
    benchmark times the fusion against."""
    archs = list(list_archs()) if archs is None else list(archs)
    hw = [(int(h), int(w)) for h, w in hw]
    tps = sorted({int(t) for t in tps})
    slot_l = [float(b) for b in slot_lattice]
    kv_l = [float(s) for s in kv_lattice]
    prompt_l = [float(p) for p in prompt_lattice]
    nb, nk, npr = len(slot_l), len(kv_l), len(prompt_l)
    per_kind = nb * nk + npr

    workload_lists: List[List[Workload]] = []
    metas = []
    for arch in archs:
        cfg = get_config(arch)
        kinds = block_plan(cfg)
        distinct = list(dict.fromkeys(kinds))
        for tp in tps:
            base = len(workload_lists)
            for kind in distinct:
                for b in slot_l:
                    for s in kv_l:
                        wl = block_workloads(cfg, kind, B=int(b), Sq=1,
                                             Skv=int(s), T=int(b))
                        workload_lists.append(
                            tp_split_workloads(wl, tp))
                for p in prompt_l:
                    wl = block_workloads(cfg, kind, B=1, Sq=int(p),
                                         Skv=int(p), T=int(p))
                    workload_lists.append(tp_split_workloads(wl, tp))
            metas.append((arch, cfg, kinds, distinct, tp, base))

    t0 = time.perf_counter()
    cols = _eval_lattice(workload_lists, hw, backend, **model_kw)
    build_s = time.perf_counter() - t0

    tables: Dict[Tuple[str, int, int, int], StageTables] = {}
    for arch, cfg, kinds, distinct, tp, base in metas:
        kidx = {k: i for i, k in enumerate(distinct)}
        rows = np.asarray([base + kidx[k] * per_kind for k in kinds])
        bnd_d, bnd_p, col_d, col_p, kv_blk = _block_bits(
            cfg, kinds, tp, slot_l, prompt_l, act_bits)
        for c, (h, w) in enumerate(hw):
            def grab(key, c=c):
                return cols[key][:, c]
            dec = {key: np.stack([grab(key)[r:r + nb * nk].reshape(nb, nk)
                                  for r in rows])
                   for key in ("cycles", "energy", "macs")}
            pre = {key: np.stack(
                [grab(key)[r + nb * nk:r + per_kind] for r in rows])
                for key in ("cycles", "energy")}
            tables[(arch, h, w, tp)] = StageTables(
                arch=arch, h=h, w=w, tp=tp, kinds=list(kinds),
                slot_lattice=slot_l, kv_lattice=kv_l,
                prompt_lattice=prompt_l,
                dec_cycles=dec["cycles"], dec_energy=dec["energy"],
                dec_macs=dec["macs"],
                pre_cycles=pre["cycles"], pre_energy=pre["energy"],
                bnd_dec_bits=bnd_d, bnd_pre_bits=bnd_p,
                coll_dec_bits=col_d, coll_pre_bits=col_p,
                kv_bits_per_block=kv_blk)
    return StageTableSet(tables=tables, archs=archs, hw=hw, tps=tps,
                         n_scenarios=len(workload_lists), n_configs=len(hw),
                         backend=backend, build_seconds=build_s)


# ----------------------------------------------------- partitioned servers --

@dataclasses.dataclass
class PipelinePlan:
    """Provenance of one partitioned server: where the DP cut, what the
    pipeline costs at the representative decode point."""
    arch: str
    h: int
    w: int
    tp: int
    n_stages: int
    n_micro: int
    bounds: Tuple[int, ...]          # stage s = blocks [b[s], b[s+1])
    link: LinkModel
    stage_cycles_rep: np.ndarray     # (S,) at the representative point
    bottleneck_rep: float
    bubble: float                    # closed form at (n_stages, n_micro)

    @property
    def stage_blocks(self) -> List[Tuple[int, int]]:
        return [(self.bounds[s], self.bounds[s + 1])
                for s in range(self.n_stages)]


@dataclasses.dataclass
class PartitionedServer:
    """One fleet server: `n_stages x tp` arrays jointly serving a model,
    collapsed into a simulator-ready `CostTable` (the per-step lattices
    already include pipeline fill-drain, link serialization/hop time and
    collective traffic; `pe` counts every array of the group)."""
    table: CostTable
    plan: PipelinePlan

    @property
    def arrays(self) -> int:
        return self.plan.n_stages * self.plan.tp


def _interp_rows(lat: np.ndarray, grid: Sequence[float], x: float):
    """Clamped linear interp of `lat` (S, n, ...) along axis 1 at x."""
    grid = list(grid)
    if x <= grid[0]:
        return lat[:, 0]
    if x >= grid[-1]:
        return lat[:, -1]
    import bisect
    i = bisect.bisect_right(grid, x) - 1
    f = (x - grid[i]) / (grid[i + 1] - grid[i])
    return lat[:, i] + f * (lat[:, i + 1] - lat[:, i])


def partition_server_table(st: StageTables, n_stages: int = 1,
                           n_micro: int = 4,
                           link: LinkModel = FREE_LINK
                           ) -> PartitionedServer:
    """Partition one model across `n_stages` pipeline stages (each of
    `st.tp` tensor ranks) and synthesize the server-level `CostTable`.

    Boundaries come from `dp_pipeline_split` over the per-block decode
    cycles at the representative lattice point (largest slot count,
    median KV span) with link transfer as boundary cost. Each decode step
    / prefill then runs as a GPipe fill-drain pass of
    ``min(n_micro, tokens)`` microbatches through the exact event
    recurrence; energy adds all stages, boundary shipping and collective
    traffic. With one stage there is nothing to pipeline, so the pass is
    a single microbatch and the table equals the unpartitioned
    `build_cost_tables` lattice (differential-tested)."""
    L = st.n_blocks
    S = int(n_stages)
    if not 1 <= S <= L:
        raise ValueError(f"need 1 <= n_stages <= {L} blocks, got {S}")
    nb, nk = len(st.slot_lattice), len(st.kv_lattice)
    npr = len(st.prompt_lattice)
    rep_b, rep_k = nb - 1, nk // 2
    m_plan = 1 if S == 1 else max(1, int(n_micro))

    costs = st.dec_cycles[:, rep_b, rep_k]
    bnd_rep = None
    if S > 1:
        m_rep = max(1, min(m_plan, int(st.slot_lattice[rep_b])))
        bnd_rep = [link.transfer_cycles(b / m_rep)
                   for b in st.bnd_dec_bits[:, rep_b]]
    bounds, bottleneck = dp_pipeline_split(costs, S, bnd_rep)
    starts = np.asarray(bounds[:-1], np.int64)

    seg = lambda a: np.add.reduceat(a, starts, axis=0)
    stage_dec_c = seg(st.dec_cycles)
    stage_dec_e = seg(st.dec_energy)
    stage_dec_m = seg(st.dec_macs)
    stage_pre_c = seg(st.pre_cycles)
    stage_pre_e = seg(st.pre_energy)
    stage_col_d = seg(st.coll_dec_bits)
    stage_col_p = seg(st.coll_pre_bits)
    stage_kv = seg(st.kv_bits_per_block)
    cut = np.asarray(bounds[1:-1], np.int64) - 1     # (S-1,) boundary ids

    dec_c = np.empty((nb, nk))
    dec_e = np.empty((nb, nk))
    dec_m = np.empty((nb, nk))
    for bi, b in enumerate(st.slot_lattice):
        m_eff = max(1, min(m_plan, int(b)))
        bm = b / m_eff
        cs = _interp_rows(stage_dec_c, st.slot_lattice, bm)     # (S, nk)
        es = _interp_rows(stage_dec_e, st.slot_lattice, bm)
        ms = _interp_rows(stage_dec_m, st.slot_lattice, bm)
        coll = stage_col_d[:, bi]                               # (S,)
        cs = cs + np.asarray([link.transfer_cycles(cb / m_eff)
                              for cb in coll])[:, None]
        xfer = np.asarray([link.transfer_cycles(xb / m_eff)
                           for xb in st.bnd_dec_bits[cut, bi]]) \
            if S > 1 else None
        dec_c[bi] = pipeline_pass_cycles(
            cs, m_eff, None if xfer is None else xfer[:, None])
        wire = sum(link.transfer_energy(xb)
                   for xb in st.bnd_dec_bits[cut, bi]) \
            + link.transfer_energy(float(coll.sum()))
        # stage lattices are PER-RANK (tp-split workloads): the server
        # pays all tp ranks — including the activation replication the
        # paper's multi_array analysis flags as the multi-array tax
        dec_e[bi] = m_eff * st.tp * es.sum(axis=0) + wire
        dec_m[bi] = m_eff * st.tp * ms.sum(axis=0)

    pre_c = np.empty(npr)
    pre_e = np.empty(npr)
    for pi, p in enumerate(st.prompt_lattice):
        m_eff = max(1, min(m_plan, int(p)))
        # chunked prefill: chunk m covers tokens ((m-1)p/M, m*p/M] and
        # attends over its WHOLE prefix, so its cost is the INCREMENT of
        # the cumulative prompt lattice — per-stage chunk costs telescope
        # to exactly the full-prompt cost (interpolating each chunk at
        # p/M would drop the quadratic attention term and, for short
        # prompts, charge the lattice floor M times over)
        cum = np.stack([_interp_rows(stage_pre_c, st.prompt_lattice,
                                     p * (m + 1) / m_eff)
                        for m in range(m_eff)])            # (M, S)
        inc = np.diff(cum, axis=0, prepend=np.zeros((1, S)))
        coll = stage_col_p[:, pi]
        inc = inc + np.asarray([link.transfer_cycles(cb / m_eff)
                                for cb in coll])[None, :]
        xfer = np.asarray([link.transfer_cycles(xb / m_eff)
                           for xb in st.bnd_pre_bits[cut, pi]]) \
            if S > 1 else None
        pre_c[pi] = float(pipeline_pass_cycles(inc, m_eff, xfer,
                                               micro_axis=True))
        wire = sum(link.transfer_energy(xb)
                   for xb in st.bnd_pre_bits[cut, pi]) \
            + link.transfer_energy(float(coll.sum()))
        pre_e[pi] = st.tp * float(
            _interp_rows(stage_pre_e, st.prompt_lattice, p).sum()) + wire

    plan = PipelinePlan(
        arch=st.arch, h=st.h, w=st.w, tp=st.tp, n_stages=S,
        n_micro=m_plan, bounds=bounds, link=link,
        stage_cycles_rep=stage_dec_c[:, rep_b, rep_k],
        bottleneck_rep=bottleneck, bubble=bubble_fraction(S, m_plan))
    table = CostTable(
        arch=st.arch, h=st.h, w=st.w,
        slot_lattice=list(st.slot_lattice),
        kv_lattice=list(st.kv_lattice),
        prompt_lattice=list(st.prompt_lattice),
        decode_cycles=dec_c.tolist(), decode_energy=dec_e.tolist(),
        decode_macs=dec_m.tolist(),
        prefill_cycles=pre_c.tolist(), prefill_energy=pre_e.tolist(),
        # the binding Unified Buffer is the most KV-loaded stage's, and
        # head-parallel ranks split their stage's cache tp ways
        kv_bits_per_token=float(stage_kv.max()) / st.tp,
        pe=float(st.h * st.w * S * st.tp),
        pipeline_bubble=plan.bubble)
    return PartitionedServer(table=table, plan=plan)
