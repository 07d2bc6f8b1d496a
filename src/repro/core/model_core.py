"""Single-source CAMUY metrics core: backend-agnostic closed forms.

This module is the ONE place the tile-class closed forms of the analytical
model live.  Everything here is written against an array-namespace parameter
``xp`` (``numpy`` or ``jax.numpy``) and uses only elementwise/broadcasting
ops, so the same code drives:

  * the float64 numpy path (`core/systolic.py`, exactness-validated against
    the cycle-level emulator),
  * the vectorized Pallas sweep kernel (`kernels/dse_eval.py`, float32 on
    TPU / interpret mode on CPU).

Dataflows are pluggable through a registry (`register_dataflow`):

  ``ws``          weight-stationary (the paper's §3 machine),
  ``os``          output-stationary (paper future work),
  ``multi_array`` P independent weight-stationary arrays, N-partitioned.

A dataflow function returns *per-operand component counts* (activation /
weight / output movement split out at every level of the hierarchy); the
shared :func:`finalize` applies the paper's Eq. 1 weights AND the per-operand
bitwidth scaling, so precision-aware accounting is automatic for every
dataflow.

Bitwidth-aware accounting
-------------------------
The paper counts word movements; real arrays (TPUv1 int8, ArrayFlex-style
configurable precision) move operands of different widths.  ``Precision``
carries per-operand bitwidths; every Eq. 1 movement term is scaled by
``bits / REF_BITS`` (reference word = 8 bits), so ``energy`` becomes
*bit-normalized*: with the default 8/8/8 precision it equals the classic
word-count Eq. 1 exactly, and it is linear in operand widths (halving all
widths halves energy).  ``ub_bandwidth_bits`` reports the stall-free
Unified-Buffer bandwidth in bits/cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

REF_BITS = 8.0

# Eq. 1-relative cost of moving one REF_BITS word to/from DRAM. Eq. 1 prices
# a UB access at 6; off-chip DRAM is one energy order of magnitude above the
# on-chip SRAM hierarchy (SCALE-Sim / Eyeriss accounting), so spill traffic
# from a finite Unified Buffer (graph/occupancy.py) is charged at this
# weight. A single constant here keeps the graph-level spill accounting in
# the same unit system as every other Eq. 1 term.
DRAM_COST_PER_WORD = 100.0


def dram_spill_energy(spill_bits):
    """Eq. 1-relative energy of `spill_bits` of DRAM spill/refetch traffic
    (bit-normalized like every other term: bits / REF_BITS words)."""
    return DRAM_COST_PER_WORD * spill_bits / REF_BITS


@dataclasses.dataclass(frozen=True)
class Precision:
    """Per-operand bitwidths (frozen => hashable => usable as a jit-static
    argument). The default 8/8/8 reproduces the paper's unit-word counts."""
    act_bits: float = 8
    weight_bits: float = 8
    out_bits: float = 8

    def scales(self):
        """(act, weight, out) Eq.1 multipliers relative to the 8-bit word."""
        return (self.act_bits / REF_BITS, self.weight_bits / REF_BITS,
                self.out_bits / REF_BITS)


DEFAULT_PRECISION = Precision()


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Accounting options shared by all dataflows (ablated in benchmarks).

    `relaxed=True` swaps the exact ceil-based tiling for its continuous
    relaxation (see `tiling`): the closed forms become differentiable in
    (h, w) so `jax.grad` can steer a design-point refiner. Relaxed numbers
    are PROPOSAL-quality only — anything reported must be re-evaluated
    with the exact forms."""
    act_reread: bool = False
    count_weight_load_hops: bool = False
    idle_pe_energy: float = 0.0
    n_arrays: int = 1
    relaxed: bool = False


# --------------------------------------------------------------------------
# The tile-class decomposition — THE closed-form kernel of the whole model.
# --------------------------------------------------------------------------

def tiling(xp, D, s, relaxed: bool = False):
    """Tile a problem dimension D over an array dimension s.

    Returns (T, r): number of tiles T = ceil(D/s) and the edge-tile extent
    r = D - (T-1)*s in 1..s.  Edge tiles are partially occupied — this is
    where the paper's pow2 utilization effects come from.

    D and s are whole numbers (a GEMM dimension, an array side), so D/s is
    a multiple of 1/s and T = ceil((D - 1/2) / s) exactly. The half keeps
    T exact where division is not correctly rounded: the TPU's float32
    division can land just above a whole quotient (25088 / 224 = 112), and
    ceil(D/s) would then count a tile of no rows.

    With `relaxed=True` the ceil is replaced by its continuous envelope
    T = max(D/s, 1): identical when D <= s, smooth in s (and D) elsewhere,
    with r -> s for D > s. This makes every downstream closed form
    differentiable — the objective surface `jax.grad` descends in the
    design-point refiner (`core.search.refine_design_point`). Relaxed
    values under-count the edge-tile raggedness, so they are proposals,
    never reported numbers.
    """
    if relaxed:
        T = xp.maximum(D / s, 1.0)
    else:
        T = xp.ceil((D - 0.5) / s)
    return T, D - (T - 1) * s


def tile_sum(fn, T1, r1, s1, T2, r2, s2):
    """Exact sum of fn(d1_t, d2_t) over all T1*T2 tiles via the 4 tile
    classes (full / edge-row / edge-col / corner)."""
    return ((T1 - 1) * (T2 - 1) * fn(s1, s2)
            + (T1 - 1) * fn(s1, r2)
            + (T2 - 1) * fn(r1, s2)
            + fn(r1, r2))


# --------------------------------------------------------------------------
# Dataflow registry
# --------------------------------------------------------------------------

_DATAFLOWS: Dict[str, Callable] = {}


def register_dataflow(name: str, pe_mult: Callable = lambda opt: 1.0):
    """Register a dataflow component model. `pe_mult(opt)` reports the
    PE-count multiplier of the configuration (e.g. the number of arrays) —
    every consumer that normalizes by the PE count (utilization, idle
    energy) reads it from the registry rather than special-casing names."""
    def deco(fn):
        fn.pe_mult = pe_mult
        _DATAFLOWS[name] = fn
        return fn
    return deco


def get_dataflow(name: str) -> Callable:
    if name not in _DATAFLOWS:
        raise KeyError(f"unknown dataflow {name!r}; have {list_dataflows()}")
    return _DATAFLOWS[name]


def list_dataflows() -> List[str]:
    return sorted(_DATAFLOWS)


def pe_multiplier(dataflow: str, n_arrays: int = 1) -> float:
    """PE-count multiplier of `dataflow` at the given options."""
    return float(get_dataflow(dataflow).pe_mult(
        ModelOptions(n_arrays=n_arrays)))


# --------------------------------------------------------------------------
# Dataflow component models. Each returns a dict of PER-GROUP counts, split
# per operand so finalize() can apply bitwidth scaling:
#   cycles, weight_load_cycles, macs,
#   ub_act / ub_weight / ub_out            (Unified Buffer accesses)
#   inter_act / inter_psum / inter_wload   (neighbour-register hops)
#   intra_act / intra_weight / intra_out   (local register accesses)
#   aa                                     (accumulator-array accesses, out)
#   update_ports, bw_act / bw_weight / bw_out   (per-cycle, not group-scaled)
# --------------------------------------------------------------------------

@register_dataflow("ws")
def ws_components(xp, M, K, N, h, w, opt: ModelOptions):
    """Weight-stationary: K maps to rows (h), N to columns (w); activations
    stream horizontally, partial sums sink to the Accumulator Array."""
    Tk, rk = tiling(xp, K, h, opt.relaxed)
    Tn, rn = tiling(xp, N, w, opt.relaxed)
    tsum = lambda fn: tile_sum(fn, Tk, rk, h, Tn, rn, w)

    # Subsequent weight loads are ALWAYS hidden by double buffering: a load
    # takes h_t <= h cycles while the previous pass runs
    # M + h_prev + w_prev - 1 >= h cycles. Only the first load is exposed,
    # and it fills the FIRST K-tile's rows: h when K spans several row
    # tiles, else the single ragged tile's rk (the cycle-level emulator
    # pins this exactly — charging h for a K < h problem would stall on
    # rows that hold no weights).
    pass_cycles = tsum(lambda ht, wt: M + ht + wt - 1)
    first_load = xp.where(Tk > 1, h, rk)
    min_pass = M + xp.minimum(h, rk) + xp.minimum(w, rn) - 1

    zero = pass_cycles * 0.0
    comp = {
        "cycles": pass_cycles + first_load,
        # pure streaming cycles (one M-row per cycle per tile); the rest of
        # `cycles` is skew fill/drain + the exposed first weight load —
        # split out for the attribution layer (obs/attribution.py)
        "stream_cycles": Tk * Tn * M,
        "weight_load_cycles": first_load,
        "macs": M * K * N,
        # act fetched once by the Systolic Data Setup Unit (paper-faithful);
        # act_reread=True charges the Tn column-tile re-streams to the UB.
        "ub_act": (Tn * M * K) if opt.act_reread else (M * K),
        "ub_weight": K * N,
        "ub_out": M * N,
        "inter_act": tsum(lambda ht, wt: M * ht * (wt - 1)),
        "inter_psum": tsum(lambda ht, wt: M * wt * (ht - 1)),
        # pass-through hops of weights sinking to their rows during loads
        # (penalizes extreme heights; off by default, not in Eq. 1)
        "inter_wload": tsum(lambda ht, wt: wt * ht * (ht - 1) / 2.0)
        if opt.count_weight_load_hops else zero,
        # per MAC: weight-reg read + psum write + activation latch,
        # plus K*N double-buffer weight-reg writes
        "intra_act": M * K * N,
        "intra_weight": M * K * N + K * N,
        "intra_out": M * K * N,
        # each deposited partial is an accumulator read-modify-write; this
        # 2*Tk*M*N term is what makes energy height-dominated (Fig. 2/5)
        "aa": 2.0 * tsum(lambda ht, wt: M * wt),
        "update_ports": xp.maximum(
            xp.ceil(h / xp.maximum(min_pass, 1.0)), 1.0),
        # stall-free UB rates: act in (h/cyc), AA drain (w/cyc), weight
        # prefetch (h*w words over one pass)
        "bw_act": h + zero,
        "bw_weight": h * w / xp.maximum(min_pass, 1.0),
        "bw_out": w + zero,
    }
    return comp


@register_dataflow("os")
def os_components(xp, M, K, N, h, w, opt: ModelOptions):
    """Output-stationary: each PE owns one o(m, j); A streams from the left,
    W from the top, the K reduction happens in place (no accumulator array).
    A is re-read per column tile, W per row tile."""
    Tm, rm = tiling(xp, M, h, opt.relaxed)
    Tn, rn = tiling(xp, N, w, opt.relaxed)
    tsum = lambda fn: tile_sum(fn, Tm, rm, h, Tn, rn, w)

    pass_cycles = tsum(lambda ht, wt: K + ht + wt - 1)
    zero = pass_cycles * 0.0
    comp = {
        "cycles": pass_cycles,
        "stream_cycles": Tm * Tn * K,
        "weight_load_cycles": zero,
        "macs": M * K * N,
        "ub_act": Tn * M * K,
        "ub_weight": Tm * K * N,
        "ub_out": M * N,
        "inter_act": tsum(lambda ht, wt: K * ht * (wt - 1)),  # A right-hops
        "inter_psum": zero,                                   # in-place acc
        "inter_wload": tsum(lambda ht, wt: K * wt * (ht - 1)),  # W down-hops
        # per MAC: act latch + weight latch + accumulator r/w, plus the
        # final M*N register -> UB stores
        "intra_act": M * K * N,
        "intra_weight": M * K * N,
        "intra_out": M * K * N + M * N,
        "aa": zero,
        "update_ports": 1.0 + zero,
        "bw_act": h + zero,
        "bw_weight": w + zero,
        "bw_out": zero,
    }
    return comp


@register_dataflow("multi_array", pe_mult=lambda opt: float(opt.n_arrays))
def multi_array_components(xp, M, K, N, h, w, opt: ModelOptions):
    """P independent weight-stationary h x w arrays, GEMM partitioned N-wise
    (output-channel parallel). Cycles reflect the parallel makespan; data
    movement sums all arrays; the activation stream REPLICATES per array —
    the energy/parallelism tension the TPU's single big array avoids."""
    P = float(opt.n_arrays)
    Np = tiling(xp, N, P)[0]
    comp = ws_components(xp, M, K, Np, h, w, opt)
    for key in ("macs", "ub_act", "ub_weight", "ub_out", "inter_act",
                "inter_psum", "inter_wload", "intra_act", "intra_weight",
                "intra_out", "aa",
                # stall-free UB rates and weight-update ports are aggregate
                # demand: all P arrays stream distinct weights/outputs and
                # replicated activations concurrently
                "bw_act", "bw_weight", "bw_out", "update_ports"):
        comp[key] = comp[key] * P
    return comp


# --------------------------------------------------------------------------
# Shared finalization: Eq. 1 with bitwidth scaling, utilization, bandwidth.
# --------------------------------------------------------------------------

def finalize(xp, comp, h, w, groups, precision: Precision,
             opt: ModelOptions, pe_mult: float = 1.0,
             breakdown: bool = False):
    """Turn per-group component counts into the full metrics dict.

    Eq. 1 (paper): E = 6*M_UB + 2*(M_INTER_PE + M_AA) + M_INTRA_PE, with
    every term scaled by its operand's bits/REF_BITS — at the default 8/8/8
    precision this is exactly the paper's word-count accounting.

    With ``breakdown=True`` the dict additionally carries the attribution
    split (`cycles_compute`/`cycles_fill_drain`,
    `energy_compute`/`energy_ub_stream`/`energy_fill_drain`) consumed by
    obs/attribution.py. The split terms are computed as fresh expressions —
    never by subtracting from the totals — so the 1e-9 conservation gate
    genuinely re-checks the Eq. 1 algebra. The default path is untouched.
    """
    sa, sw, so = precision.scales()
    g = groups
    cycles = g * comp["cycles"]
    macs = g * comp["macs"]
    m_ub_act = g * comp["ub_act"]
    m_ub_weight = g * comp["ub_weight"]
    m_ub_out = g * comp["ub_out"]
    m_ub = m_ub_act + m_ub_weight + m_ub_out
    inter_act = g * comp["inter_act"]
    inter_psum = g * comp["inter_psum"]
    inter_wload = g * comp["inter_wload"]
    m_inter = inter_act + inter_psum + inter_wload
    intra_act = g * comp["intra_act"]
    intra_weight = g * comp["intra_weight"]
    intra_out = g * comp["intra_out"]
    m_intra = intra_act + intra_weight + intra_out
    m_aa = g * comp["aa"]

    energy = (6.0 * (sa * m_ub_act + sw * m_ub_weight + so * m_ub_out)
              + 2.0 * (sa * inter_act + so * inter_psum + sw * inter_wload
                       + so * m_aa)
              + (sa * intra_act + sw * intra_weight + so * intra_out))

    pe = h * w * pe_mult
    if opt.idle_pe_energy:
        # optional clock/leakage cost of idle PE-cycles: strict Eq.1 carries
        # no such term; with it, group-conv models sharply prefer SMALL
        # arrays (the paper's "smaller is better" finding).
        energy = energy + opt.idle_pe_energy * (cycles * pe - macs)

    utilization = macs / xp.maximum(cycles * pe, 1.0)
    ub_bandwidth = comp["bw_act"] + comp["bw_weight"] + comp["bw_out"]
    ub_bandwidth_bits = (precision.act_bits * comp["bw_act"]
                         + precision.weight_bits * comp["bw_weight"]
                         + precision.out_bits * comp["bw_out"])

    out = {
        "cycles": cycles,
        "utilization": utilization,
        "macs": macs,
        "m_ub": m_ub,
        "m_ub_act": m_ub_act,
        "m_ub_weight": m_ub_weight,
        "m_ub_out": m_ub_out,
        "m_inter_pe": m_inter,
        "m_intra_pe": m_intra,
        "m_aa": m_aa,
        "energy": energy,
        "weight_load_cycles": g * comp["weight_load_cycles"],
        "update_ports": comp["update_ports"],
        "ub_bandwidth": ub_bandwidth,
        "ub_bandwidth_bits": ub_bandwidth_bits,
    }
    if breakdown:
        # cycles: pure streaming vs skew fill/drain + exposed weight load
        out["cycles_compute"] = g * comp["stream_cycles"]
        out["cycles_fill_drain"] = g * (comp["cycles"]
                                        - comp["stream_cycles"])
        # energy: the three Eq. 1 cost tiers — UB streaming (6*M_UB), the
        # in-array compute movement (inter-PE + AA + intra-PE), and the
        # idle-PE leakage (only priced when opt.idle_pe_energy is set; it
        # is exactly the fill/drain + raggedness bubble)
        out["energy_ub_stream"] = 6.0 * (sa * m_ub_act + sw * m_ub_weight
                                         + so * m_ub_out)
        out["energy_compute"] = (
            2.0 * (sa * inter_act + so * inter_psum + sw * inter_wload
                   + so * m_aa)
            + (sa * intra_act + sw * intra_weight + so * intra_out))
        out["energy_fill_drain"] = (
            opt.idle_pe_energy * (cycles * pe - macs)
            if opt.idle_pe_energy else cycles * 0.0)
    return out


def analyze_gemm_core(xp, M, K, N, h, w, *, dataflow: str = "ws",
                      groups=1.0, precision: Precision = None,
                      act_reread: bool = False,
                      count_weight_load_hops: bool = False,
                      idle_pe_energy: float = 0.0,
                      n_arrays: int = 1, relaxed: bool = False,
                      breakdown: bool = False):
    """Backend-agnostic analytical metrics for a (grouped) GEMM.

    All of M, K, N, h, w, groups may be broadcastable arrays of whatever
    dtype the caller chose (float64 on the numpy path, float32 inside the
    Pallas kernel); ``xp`` selects the namespace. Returns a plain dict keyed
    by the SystolicMetrics field names.
    """
    precision = DEFAULT_PRECISION if precision is None else precision
    opt = ModelOptions(act_reread=act_reread,
                       count_weight_load_hops=count_weight_load_hops,
                       idle_pe_energy=idle_pe_energy, n_arrays=n_arrays,
                       relaxed=relaxed)
    fn = get_dataflow(dataflow)
    comp = fn(xp, M, K, N, h, w, opt)
    return finalize(xp, comp, h, w, groups, precision, opt,
                    pe_mult=fn.pe_mult(opt), breakdown=breakdown)

METRIC_FIELDS = (
    "cycles", "utilization", "macs", "m_ub", "m_ub_act", "m_ub_weight",
    "m_ub_out", "m_inter_pe", "m_intra_pe", "m_aa", "energy",
    "weight_load_cycles", "update_ports", "ub_bandwidth",
    "ub_bandwidth_bits")
