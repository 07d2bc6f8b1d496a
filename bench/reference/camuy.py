"""Plain reference of the weight-stationary systolic-array closed forms.

An independent transcription of the paper's analytical model (Kühn et al.,
"On the Difficulty of Designing Processor Arrays for Deep Neural
Networks", 2020, §3): a GEMM O[M,N] = A[M,K] @ W[K,N] on an h x w array
maps K to rows and N to columns, tiles Tk = ceil(K/h) x Tn = ceil(N/w);
each tile pass costs M + h_t + w_t - 1 cycles, and only the first weight
load is exposed. Data movement follows Eq. 1,
E = 6*M_UB + 2*(M_INTER_PE + M_AA) + M_INTRA_PE, each operand's term
scaled by its bitwidth over 8 bits.

The default accounting options are the only ones modelled (no activation
re-read, no weight-load hops, no idle-PE energy, one array). Everything is
numpy in the dtype the caller asks for: float64 for the reference, and a
narrower type (bfloat16) for the control that must fail the comparison.
"""
from __future__ import annotations

import numpy as np

# network-level outputs, as the sweep reports them
COLS = ("cycles", "energy", "macs", "utilization", "m_ub", "m_inter_pe",
        "m_aa", "ub_bw_bits")


def _tile(D, s):
    T = np.ceil(D / s)
    return T, D - (T - 1) * s


def gemm(M, K, N, g, h, w, bits=(8, 8, 8)):
    """Per-GEMM counters of a (grouped) GEMM, broadcast over h/w.

    `g` is groups x repeats: every counter below is linear in it except
    the per-cycle bandwidth, which is a maximum and not scaled."""
    ab, wb, ob = bits
    sa, sw, so = ab / 8.0, wb / 8.0, ob / 8.0
    Tk, rk = _tile(K, h)
    Tn, rn = _tile(N, w)

    def over_tiles(f):
        return ((Tk - 1) * (Tn - 1) * f(h, w) + (Tk - 1) * f(h, rn)
                + (Tn - 1) * f(rk, w) + f(rk, rn))

    passes = over_tiles(lambda a, b: M + a + b - 1)
    first_load = np.where(Tk > 1, h, rk)
    macs = M * K * N
    ub_act, ub_w, ub_out = M * K, K * N, M * N
    inter_act = over_tiles(lambda a, b: M * a * (b - 1))
    inter_psum = over_tiles(lambda a, b: M * b * (a - 1))
    aa = 2 * over_tiles(lambda a, b: M * b)
    energy = (6 * (sa * ub_act + sw * ub_w + so * ub_out)
              + 2 * (sa * inter_act + so * inter_psum + so * aa)
              + (sa * macs + sw * (macs + K * N) + so * macs))
    shortest = M + np.minimum(h, rk) + np.minimum(w, rn) - 1
    bw_bits = ab * h + wb * (h * w / np.maximum(shortest, 1)) + ob * w
    return {"cycles": g * (passes + first_load), "energy": g * energy,
            "macs": g * macs, "m_ub": g * (ub_act + ub_w + ub_out),
            "m_inter_pe": g * (inter_act + inter_psum), "m_aa": g * aa,
            "ub_bw_bits": bw_bits}


def network(rows, hw, bits=(8, 8, 8), dtype=np.float64):
    """Whole-network counters of `rows` ((M, K, N, groups, repeats) GEMMs)
    on every (h, w) of `hw` ((C, 2)): a dict of (C,) arrays, COLS order.
    Counters add over the layers; the bandwidth is their maximum."""
    hw = np.asarray(hw, np.float64).astype(dtype)
    h, w = hw[:, 0], hw[:, 1]
    zero = np.zeros(len(hw), dtype)
    out = {k: zero.copy() for k in COLS}
    for M, K, N, g, r in rows:
        c = dtype(M), dtype(K), dtype(N), dtype(g * r)
        t = gemm(*c, h, w, bits)
        for k in ("cycles", "energy", "macs", "m_ub", "m_inter_pe", "m_aa"):
            out[k] = out[k] + t[k]
        out["ub_bw_bits"] = np.maximum(out["ub_bw_bits"], t["ub_bw_bits"])
    out["utilization"] = out["macs"] / np.maximum(out["cycles"] * h * w, 1)
    return out
