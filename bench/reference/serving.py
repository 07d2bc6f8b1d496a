"""Plain reference of one design point serving a seeded request trace.

Three pieces, each written from its definition:

* the trace: `n` requests from `numpy.random.default_rng(seed)`, one
  stream feeding the arrivals first and then the prompt and output
  lengths. Poisson arrivals are the running sum of exponential gaps of
  mean 1/rate. Two-state MMPP arrivals alternate a low and a high state
  (rates 2*rate/(1+r) and r times that) with exponential sojourns;
  inside a sojourn Poisson(rate * dwell) arrivals fall uniformly, and
  the sojourn that overshoots `n` keeps only the arrivals still wanted,
  over the part of the sojourn that holds them at the state's rate.
  Lengths are round(LogNormal(ln median, sigma)) clipped to their range;
* the per-step cost lattice: cycles and Eq. 1 energy of a decode step at
  (active slots, KV span) and of a batch-1 prefill at a prompt length,
  from the closed forms (`camuy`), read between lattice points by clamped
  piecewise-linear interpolation;
* the engine: continuous batching on `slots` decode slots, FIFO
  admission, each admitted prompt prefilled at once while decode waits;
  a decode step advances every active slot by one token. Between two
  events every step sees the same slots, so a run of k steps is charged
  at its midpoint KV span. The buffer is unbounded (no spill).

`summary` reports the percentiles and energy per token that a capacity
answer states about its operating point; `slo_load` says how full the
SLO is there, and `bracket_step` how finely a bisection resolves a rate.
"""
from __future__ import annotations

import heapq
from bisect import bisect_right

import numpy as np

from bench.reference import camuy


# ----------------------------------------------------------------- trace --

def _lengths(rng, median, sigma, lo, hi, n):
    x = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _mmpp(rng, rate, ratio, sojourn, n):
    lo = 2.0 * rate / (1.0 + ratio)
    out, t, high, total = [], 0.0, False, 0
    while total < n:
        dwell = rng.exponential(sojourn)
        k = int(rng.poisson((lo * ratio if high else lo) * dwell))
        if k > n - total:
            need = n - total
            out.append(t + np.sort(rng.uniform(0.0, dwell * need / k, need)))
            total = n
        elif k:
            out.append(t + np.sort(rng.uniform(0.0, dwell, k)))
            total += k
        t += dwell
        high = not high
    return np.concatenate(out)[:n]


def trace(traffic, rate, n, seed):
    """(arrival seconds, prompt lengths, output lengths) at `rate` qps."""
    rng = np.random.default_rng(seed)
    if traffic["arrival"] == "poisson":
        arr = np.cumsum(rng.exponential(1.0 / rate, n))
    elif traffic["arrival"] == "mmpp":
        arr = _mmpp(rng, rate, traffic["burst_ratio"],
                    traffic["mean_sojourn_s"], n)
    else:
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    p = _lengths(rng, traffic["prompt_median"], traffic["prompt_sigma"],
                 *traffic["prompt_range"], n)
    o = _lengths(rng, traffic["output_median"], traffic["output_sigma"],
                 *traffic["output_range"], n)
    return arr, p, o


# --------------------------------------------------------------- lattice --

def lattice_shapes(lattice):
    """(phase, batch, seq) of every lattice point: decode (slot x kv)
    row-major, then prefill (prompt)."""
    return ([("decode", b, s) for b in lattice["slots"]
             for s in lattice["kv"]]
            + [("prefill", 1, p) for p in lattice["prompt"]])


def tables(lower, cfg, lattice, hw, dtype=np.float64):
    """{(h, w): table} for every design point, each table a dict of the
    decode (slots x kv) and prefill (prompt) cycle and energy lattices,
    computed in `dtype` and handed on as float64."""
    shapes = lattice_shapes(lattice)
    cols = [camuy.network(lower(cfg, s), hw, dtype=dtype) for s in shapes]
    nb, nk = len(lattice["slots"]), len(lattice["kv"])
    out = {}
    for c, (h, w) in enumerate(hw):
        col = {k: np.array([x[k][c] for x in cols], np.float64)
               for k in ("cycles", "energy", "macs")}
        out[(int(h), int(w))] = {
            "slots": [float(x) for x in lattice["slots"]],
            "kv": [float(x) for x in lattice["kv"]],
            "prompt": [float(x) for x in lattice["prompt"]],
            "decode_cycles": col["cycles"][:nb * nk].reshape(nb, nk),
            "decode_energy": col["energy"][:nb * nk].reshape(nb, nk),
            "decode_macs": col["macs"][:nb * nk].reshape(nb, nk),
            "prefill_cycles": col["cycles"][nb * nk:],
            "prefill_energy": col["energy"][nb * nk:]}
    return out


def _axis(lat, x):
    if x <= lat[0]:
        return 0, 0.0
    if x >= lat[-1]:
        return len(lat) - 2, 1.0
    i = bisect_right(lat, x) - 1
    return i, (x - lat[i]) / (lat[i + 1] - lat[i])


def _decode(tab, key, active, kv):
    i, fa = _axis(tab["slots"], active)
    j, fk = _axis(tab["kv"], kv)
    g = tab[key]
    lo = g[i][j] + fk * (g[i][j + 1] - g[i][j])
    hi = g[i + 1][j] + fk * (g[i + 1][j + 1] - g[i + 1][j])
    return lo + fa * (hi - lo)


def _prefill(tab, plen):
    i, f = _axis(tab["prompt"], plen)
    c, e = tab["prefill_cycles"], tab["prefill_energy"]
    return c[i] + f * (c[i + 1] - c[i]), e[i] + f * (e[i + 1] - e[i])


# ---------------------------------------------------------------- engine --

def replay(tab, arr, plen, olen, slots, clock_hz):
    """Per-request TTFT and TPOT seconds, and the run's energy and output
    tokens, of one trace on one design point."""
    tab = dict(tab, decode_cycles=tab["decode_cycles"].tolist(),
               decode_energy=tab["decode_energy"].tolist(),
               prefill_cycles=tab["prefill_cycles"].tolist(),
               prefill_energy=tab["prefill_energy"].tolist())
    arr, plen, olen = arr.tolist(), plen.tolist(), olen.tolist()
    n = len(arr)
    ttft = [float("nan")] * n
    tpot = [float("nan")] * n
    t = kv = energy = 0.0
    step = nxt = active = tokens = 0
    finish = []                                  # (finish step, request)
    while True:
        while active < slots and nxt < n and arr[nxt] <= t:
            r = nxt
            nxt += 1
            cyc, en = _prefill(tab, plen[r])
            t += cyc / clock_hz
            energy += en
            ttft[r] = t - arr[r]
            kv += plen[r]
            active += 1
            heapq.heappush(finish, (step + olen[r], r))
        if active == 0:
            if nxt < n:
                t = max(t, arr[nxt])
                continue
            break
        k = finish[0][0] - step
        if active < slots and nxt < n:
            # stop at the first step that ends past the next arrival
            one = _decode(tab, "decode_cycles", active, kv / active)
            ratio = (arr[nxt] - t) / (one / clock_hz)
            if ratio < k:
                k = min(k, int(ratio) + 1)
        mid = kv / active + (k - 1) * 0.5
        t += k * _decode(tab, "decode_cycles", active, mid) / clock_hz
        energy += k * _decode(tab, "decode_energy", active, mid)
        step += k
        kv += k * active
        while finish and finish[0][0] <= step:
            _, r = heapq.heappop(finish)
            active -= 1
            kv -= plen[r] + olen[r]
            tokens += olen[r]
            tpot[r] = (t - arr[r] - ttft[r]) / olen[r]
    return np.array(ttft), np.array(tpot), energy, tokens


def summary(ttft, tpot, energy, tokens, slo):
    """What a capacity answer states about its operating point."""
    return {"ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p99_s": float(np.percentile(ttft, slo["pct"])),
            "tpot_p50_s": float(np.percentile(tpot, 50)),
            "tpot_p99_s": float(np.percentile(tpot, slo["pct"])),
            "energy_per_token": energy / max(tokens, 1),
            "completed": int(np.isfinite(tpot).sum())}


def slo_load(s, n, slo):
    """How full the SLO is at an operating point: the larger of p99 TTFT
    and p99 TPOT over its target (infinite when a request is left
    unfinished). The point meets the SLO when this is at most 1."""
    if s["completed"] < n:
        return float("inf")
    return max(s["ttft_p99_s"] / slo["ttft_s"],
               s["tpot_p99_s"] / slo["tpot_s"])


def lowest_probe_qps(tab, traffic, slots, clock_hz):
    """The lowest rate a capacity bisection probes: 1/1024 of twice the
    rate at which every slot decodes at the typical KV span."""
    span = traffic["prompt_median"] + 0.5 * traffic["output_median"]
    step = _decode(tab, "decode_cycles", slots, span)
    sat = slots * clock_hz / max(step, 1.0) / max(traffic["output_median"],
                                                  1.0)
    return 2.0 * sat / 1024.0


def bracket_step(qps, lowest, iters):
    """The resolution of a capacity bisection that answered `qps` > 0:
    the width of its last bracket. The bracket starts at [lowest, 1024 *
    lowest], doubles while its top still meets the SLO, and is halved
    `iters` times; the answer is the bracket's bottom, and its top (the
    answer plus this width) is the lowest rate seen to miss."""
    lo, hi = lowest, 1024.0 * lowest
    while qps >= hi * (1.0 - 1e-6):
        lo, hi = hi, 2.0 * hi
    return (hi - lo) / 2.0 ** iters
