"""The capacity search's fast replay engine, and the MMPP sampler, in C.

`core.search` replays hundreds of (design point, probe) lanes per
bisection round. A plain C transcription of the scalar event loop
(`traffic.sim.simulate`, `prefill_first` policy) runs one such replay in
microseconds. This module compiles it ONCE per process with the system C
compiler (no third-party deps: `ctypes` + `cc`) and runs a round of
lanes in one call (`NativeBatch`); `_to_result` turns a lane of the
round back into a `SimResult`.

Bit-identity: the C source is an op-for-op transcription of
`traffic.sim.simulate`'s prefill_first path, and x86-64/AArch64 doubles
follow IEEE-754 exactly at -O2 (no reassociation). `-ffp-contract=off`
additionally forbids contracting `a*b + c` into a single-rounding fma,
so every expression rounds exactly like the interpreted source. The heap
of (finish_step, rid) pairs becomes a linear scan over packed int64 keys
`finish_step * (n+1) + rid`, whose minimum reproduces the heap's
lexicographic pop order. The engine runs on the host CPU whatever JAX's
backend is, so it is exact on a TPU host too.

Everything degrades gracefully: if no C compiler is present or the
compile fails, `available()` returns False and callers fall back to the
scalar simulator. The shared object is cached under the system temp
directory keyed by source hash.

The module also builds, as a separate shared object, the MMPP arrival
sampler behind `workload.mmpp_arrivals`: the sojourn loop in C, linked
against the installed numpy's `libnpyrandom.a` and run on the caller's
Generator through `bit_generator.ctypes`, so it calls numpy's own
exponential and poisson and draws its uniforms as `Generator.uniform`
does, in the Python loop's order. Its cache key also hashes
`numpy.__version__` and the static library's bytes, so a numpy upgrade
rebuilds it; if it does not build, `mmpp_sampler()` returns None and the
Python loop runs, and the replay engine is unaffected.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Dict, Optional, Sequence

import numpy as np

from repro.core.model_core import DRAM_COST_PER_WORD, REF_BITS
from repro.traffic.sim import SimConfig, SimResult
from repro.traffic.workload import RequestTrace

_KPAD = 8                       # lattice axes padded to this (with +inf)

_C_SOURCE = r"""
#include <stdint.h>

#define BIGKEY 0x7fffffffffffffffLL

static void interp_axis(const double* lat, int k, double x,
                        int* i_out, double* f_out) {
    if (x <= lat[0]) { *i_out = 0; *f_out = 0.0; return; }
    if (x >= lat[k - 1]) { *i_out = k - 2; *f_out = 1.0; return; }
    int lo = 0, hi = k;                       /* bisect_right */
    while (lo < hi) {
        int mid = (lo + hi) / 2;
        if (x < lat[mid]) hi = mid; else lo = mid + 1;
    }
    int i = lo - 1;
    *i_out = i;
    *f_out = (x - lat[i]) / (lat[i + 1] - lat[i]);
}

static double bilerp(const double* g, int nk, int ia, double fa,
                     int j, double fk) {
    const double* r0 = g + (int64_t)ia * nk;
    const double* r1 = r0 + nk;
    double lo = r0[j] + fk * (r0[j + 1] - r0[j]);
    double hi = r1[j] + fk * (r1[j + 1] - r1[j]);
    return lo + fa * (hi - lo);
}

/* One scalar replay per lane; transcribed op-for-op from
   traffic/sim.py (prefill_first, no timeline). Returns 0. */
int replay_lanes(
    int n_lanes, int n_max, int nb, int nk, int np_,
    int slots, int has_ub,
    double clock, double ub_bits, double dram_bpc, double spe,
    const double* lat,          /* (L, 3, KPAD) padded lattices */
    const double* grid,         /* (L, 2*np + 2*nb*nk) */
    const double* kvb_arr,      /* (L,) */
    const double* req,          /* (L, 3, n_max): arr | plen | olen */
    const int64_t* n_arr,       /* (L,) live lengths */
    double* ttft_out,           /* (L, n_max), pre-filled NaN */
    double* tpot_out,           /* (L, n_max), pre-filled NaN */
    double* agg_out)            /* (L, 9): t nstep tok dec pre sp en ms - */
{
    int kpad = 8;
    for (int lane = 0; lane < n_lanes; lane++) {
        const double* lslot = lat + (int64_t)lane * 3 * kpad;
        const double* lkv = lslot + kpad;
        const double* lprm = lslot + 2 * kpad;
        const double* pcyc = grid + (int64_t)lane * (2 * np_ + 2 * nb * nk);
        const double* pen_g = pcyc + np_;
        const double* dcyc = pen_g + np_;
        const double* den_g = dcyc + nb * nk;
        double kvb = kvb_arr[lane];
        const double* arr = req + (int64_t)lane * 3 * n_max;
        const double* plen = arr + n_max;
        const double* olen = plen + n_max;
        int64_t n = n_arr[lane];
        double* ttft = ttft_out + (int64_t)lane * n_max;
        double* tpot = tpot_out + (int64_t)lane * n_max;

        int64_t key[64];
        for (int s = 0; s < slots; s++) key[s] = BIGKEY;
        double t = 0.0, kv_tok = 0.0;
        int64_t nstep = 0, nxt = 0, tokens_out = 0;
        int active = 0;
        double decode_secs = 0.0, prefill_secs = 0.0, spill_secs = 0.0;
        double energy = 0.0, max_step = 0.0;
        int ia, jk, ip;
        double fa, fk, fp;

        while (1) {
            /* admissions (FIFO; exclusive prefill) */
            while (active < slots && nxt < n && arr[nxt] <= t) {
                int64_t rid = nxt;
                nxt += 1;
                interp_axis(lprm, np_, plen[rid], &ip, &fp);
                double pc = pcyc[ip] + fp * (pcyc[ip + 1] - pcyc[ip]);
                double pe = pen_g[ip] + fp * (pen_g[ip + 1] - pen_g[ip]);
                double sp = 0.0;
                if (has_ub) {
                    double over = (kv_tok + plen[rid]) * kvb - ub_bits;
                    if (over > 0.0) sp = 2.0 * over / dram_bpc;
                }
                double dt = (pc + sp) / clock;
                t += dt;
                prefill_secs += dt;
                spill_secs += sp / clock;
                if (active && dt > max_step) max_step = dt;
                energy += pe + sp * dram_bpc * spe;
                ttft[rid] = t - arr[rid];
                kv_tok += plen[rid];
                active += 1;
                int64_t fin = nstep + (int64_t)olen[rid];
                for (int s = 0; s < slots; s++)
                    if (key[s] == BIGKEY) {
                        key[s] = fin * (n + 1) + rid;
                        break;
                    }
            }

            if (active == 0) {
                if (nxt < n) {
                    if (arr[nxt] > t) t = arr[nxt];   /* idle jump */
                    continue;
                }
                break;                                /* drained */
            }

            /* bulk decode: identical steps until the next event */
            int64_t minkey = BIGKEY;
            for (int s = 0; s < slots; s++)
                if (key[s] < minkey) minkey = key[s];
            int64_t k = minkey / (n + 1) - nstep;
            if (active < slots && nxt < n) {
                double gap = arr[nxt] - t;
                interp_axis(lslot, nb, (double)active, &ia, &fa);
                interp_axis(lkv, nk, kv_tok / active, &jk, &fk);
                double ds = bilerp(dcyc, nk, ia, fa, jk, fk);
                double sp0 = 0.0;
                if (has_ub) {
                    double over = kv_tok * kvb - ub_bits;
                    if (over > 0.0) sp0 = 2.0 * over / dram_bpc;
                }
                double dur1 = (ds + sp0) / clock;
                double ratio = gap / dur1;
                if (ratio < (double)k) {
                    int64_t k_arr = (int64_t)ratio + 1;
                    if (k_arr < k) k = k_arr;
                }
            }
            double kv_mid = kv_tok / active + (k - 1) * 0.5;
            interp_axis(lslot, nb, (double)active, &ia, &fa);
            interp_axis(lkv, nk, kv_mid, &jk, &fk);
            double cyc = bilerp(dcyc, nk, ia, fa, jk, fk);
            double sp = 0.0;
            if (has_ub) {
                double over = (kv_tok + k * active * 0.5) * kvb - ub_bits;
                if (over > 0.0) sp = 2.0 * over / dram_bpc;
            }
            double dt = k * (cyc + sp) / clock;
            t += dt;
            decode_secs += dt;
            spill_secs += k * sp / clock;
            energy += k * (bilerp(den_g, nk, ia, fa, jk, fk)
                           + sp * dram_bpc * spe);
            nstep += k;
            kv_tok += k * active;
            if (dt / k > max_step) max_step = dt / k;
            while (1) {                               /* completions */
                minkey = BIGKEY;
                int sm = -1;
                for (int s = 0; s < slots; s++)
                    if (key[s] < minkey) { minkey = key[s]; sm = s; }
                if (minkey / (n + 1) > nstep) break;
                int64_t rid = minkey % (n + 1);
                key[sm] = BIGKEY;
                active -= 1;
                kv_tok -= plen[rid] + olen[rid];
                tokens_out += (int64_t)olen[rid];
                tpot[rid] = (t - arr[rid] - ttft[rid]) / olen[rid];
            }
        }

        double* agg = agg_out + (int64_t)lane * 9;
        agg[0] = t;
        agg[1] = (double)nstep;
        agg[2] = (double)tokens_out;
        agg[3] = decode_secs;
        agg[4] = prefill_secs;
        agg[5] = spill_secs;
        agg[6] = energy;
        agg[7] = max_step;
        agg[8] = 0.0;
    }
    return 0;
}
"""

_lib: Optional[object] = None
_tried = False


def _build(source: str, name: str, flags: Sequence[str] = (),
           key: bytes = b"") -> Optional[str]:
    """Compile `source` into a shared object (or reuse the cached one) and
    return its path; None when no compiler builds it. The cache name
    hashes the source, the flags and `key` (whatever else the object
    depends on)."""
    h = hashlib.sha256(source.encode())
    h.update("\0".join(flags).encode())
    h.update(key)
    cache = os.path.join(tempfile.gettempdir(),
                         f"{name}_{h.hexdigest()[:16]}.so")
    if not os.path.exists(cache):
        src = cache[:-3] + ".c"
        with open(src, "w") as f:
            f.write(source)
        tmp = cache + f".tmp{os.getpid()}"
        for cc in ("cc", "gcc", "clang"):
            try:
                r = subprocess.run(
                    [cc, "-O2", "-fPIC", "-shared", "-ffp-contract=off",
                     "-o", tmp, src, *flags],
                    capture_output=True, timeout=120)
            except (OSError, subprocess.TimeoutExpired):
                continue
            if r.returncode == 0:
                os.replace(tmp, cache)       # atomic vs. racing builds
                break
        else:
            return None
    return cache


def _compile() -> Optional[object]:
    """Build (or reuse) the replay shared object; None on any failure."""
    path = _build(_C_SOURCE, "repro_native")
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    d, i = ctypes.c_double, ctypes.c_int
    pd = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    pi = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.replay_lanes.restype = ctypes.c_int
    lib.replay_lanes.argtypes = [i, i, i, i, i, i, i, d, d, d, d,
                                 pd, pd, pd, pd, pi, pd, pd, pd]
    return lib


def available() -> bool:
    """True iff the native executor compiled (cached per process)."""
    global _lib, _tried
    if not _tried:
        _tried = True
        try:
            _lib = _compile()
        except Exception:
            _lib = None
    return _lib is not None


def _pack_tables(tables: Sequence[object]):
    """The per-lane table arrays `replay_lanes` reads: `lat` (L, 3*_KPAD),
    each lattice +inf-padded to `_KPAD`; the cost grid (L, 2*np + 2*nb*nk)
    of [prefill cycles | prefill energy | decode cycles | decode energy];
    `kvb` (L,); and the lattice shape (nb, nk, np) all lanes share."""
    dims = {(len(t.slot_lattice), len(t.kv_lattice), len(t.prompt_lattice))
            for t in tables}
    if len(dims) != 1:
        raise ValueError(f"one lattice shape per batch, got {sorted(dims)}")
    (nb, nk, npr), = dims
    if max(nb, nk, npr) > _KPAD:
        raise ValueError(f"lattice axes longer than {_KPAD} unsupported")
    L = len(tables)
    lat = np.full((L, 3, _KPAD), np.inf)
    grid = np.empty((L, 2 * npr + 2 * nb * nk))
    kvb = np.empty(L)
    for i, tb in enumerate(tables):
        lat[i, 0, :nb] = tb.slot_lattice
        lat[i, 1, :nk] = tb.kv_lattice
        lat[i, 2, :npr] = tb.prompt_lattice
        grid[i] = np.concatenate([
            np.asarray(tb.prefill_cycles, np.float64),
            np.asarray(tb.prefill_energy, np.float64),
            np.asarray(tb.decode_cycles, np.float64).ravel(),
            np.asarray(tb.decode_energy, np.float64).ravel()])
        kvb[i] = tb.kv_bits_per_token
    return lat.reshape(L, 3 * _KPAD), grid, kvb, (nb, nk, npr)


class NativeBatch:
    """A reusable lane batch over FIXED tables: the table arrays are
    packed once, then `replay` runs many probe rounds that differ only in
    their traces (the capacity bisection's access pattern: same design
    points, fresh arrivals per probe). The (L, 3, n_max) request buffer
    [arrivals | prompt | output] persists between rounds and is edited in
    place; arrivals past a lane's live length are +inf."""

    def __init__(self, tables: Sequence[object], cfg: SimConfig,
                 n_max: int):
        if not available():
            raise RuntimeError("no C compiler available")
        if cfg.policy != "prefill_first":
            raise ValueError("NativeBatch supports prefill_first only")
        if cfg.slots > 64:
            raise ValueError("NativeBatch supports at most 64 slots")
        self.cfg = cfg
        self.n_max = int(n_max)
        self._lat, self._grid, self._kvb, self.dims = _pack_tables(tables)
        L = len(tables)
        self._req = np.empty((L, 3, self.n_max), np.float64)
        self._req[:, 0, :] = np.inf
        self._req[:, 0, 0] = 0.0
        self._req[:, 1:, :] = 1.0
        self._n = np.ones(L, np.int64)
        self._dirty: set = set()

    def replay(self, jobs: Dict[int, RequestTrace]
               ) -> Dict[str, np.ndarray]:
        """One round: lane i replays `jobs[i]`; a lane without a job is
        parked on a trivial 1-request trace. Returns ttft/tpot
        (L, n_max) and the per-lane aggregate vectors."""
        req, n = self._req, self._n
        for i in self._dirty - jobs.keys():      # park lanes just retired
            req[i, 0, :] = np.inf
            req[i, 0, 0] = 0.0
            n[i] = 1
        self._dirty = set(jobs)
        for i, tr in jobs.items():
            k = len(tr)
            n[i] = k
            req[i, 0, :k] = tr.arrival_s
            req[i, 0, k:] = np.inf
            req[i, 1, :k] = tr.prompt_len
            req[i, 1, k:] = 1.0
            req[i, 2, :k] = tr.output_len
            req[i, 2, k:] = 1.0
        cfg = self.cfg
        L = req.shape[0]
        nb, nk, npr = self.dims
        has_ub = cfg.ub_kib is not None
        ttft = np.full((L, self.n_max), np.nan)
        tpot = np.full((L, self.n_max), np.nan)
        agg = np.zeros((L, 9))
        _lib.replay_lanes(
            L, self.n_max, nb, nk, npr, cfg.slots, int(has_ub),
            float(cfg.clock_hz),
            float(cfg.ub_kib) * 8192.0 if has_ub else 0.0,
            float(cfg.dram_bits_per_cycle),
            DRAM_COST_PER_WORD / REF_BITS,
            self._lat, self._grid, self._kvb, req.reshape(L, -1), n,
            ttft, tpot, agg)
        return {"ttft": ttft, "tpot": tpot, "t": agg[:, 0],
                "nstep": agg[:, 1].astype(np.int64),
                "tokens_out": agg[:, 2].astype(np.int64),
                "decode_seconds": agg[:, 3], "prefill_seconds": agg[:, 4],
                "spill_seconds": agg[:, 5], "energy": agg[:, 6],
                "max_step": agg[:, 7]}


_EMPTY_TIMELINE = np.empty((0, 3), np.float64)


def _to_result(table, trace: RequestTrace, cfg: SimConfig,
               res: Dict[str, np.ndarray], lane: int,
               wall: float) -> SimResult:
    """Assemble one lane of a packed round into a scalar-shaped
    SimResult (no timeline; `wall` is the whole round's)."""
    k = len(trace)
    return SimResult(
        n=k, arch=table.arch, h=table.h, w=table.w, policy=cfg.policy,
        slots=cfg.slots, ttft_s=res["ttft"][lane, :k].copy(),
        tpot_s=res["tpot"][lane, :k].copy(),
        sim_seconds=float(res["t"][lane]), wall_seconds=wall,
        offered_qps=trace.offered_qps,
        tokens_out=int(res["tokens_out"][lane]),
        decode_steps=int(res["nstep"][lane]),
        decode_seconds=float(res["decode_seconds"][lane]),
        prefill_seconds=float(res["prefill_seconds"][lane]),
        spill_seconds=float(res["spill_seconds"][lane]),
        max_step_seconds=float(res["max_step"][lane]),
        energy_eq1=float(res["energy"][lane]), timeline=_EMPTY_TIMELINE)


# ------------------------------------------------------ MMPP arrival sampler --

_MMPP_SOURCE = r"""
#include <math.h>
#include <stdint.h>
#include <stdlib.h>
#include "numpy/random/bitgen.h"

/* numpy/random/distributions.h includes Python.h: declare the two
   libnpyrandom.a functions Generator.exponential/.poisson call, and
   the header's inline next_double behind Generator.uniform. */
extern double random_exponential(bitgen_t *bitgen_state, double scale);
extern int64_t random_poisson(bitgen_t *bitgen_state, double lam);

static inline double next_double(bitgen_t *bitgen_state) {
    return bitgen_state->next_double(bitgen_state->state);
}

static int cmp_double(const void *a, const void *b) {
    double x = *(const double *)a, y = *(const double *)b;
    return (x > y) - (x < y);
}

/* The sojourn loop of workload._mmpp_arrivals_py, draw for draw. Returns
   0, or which draw numpy would refuse (1 exponential scale, 2 poisson
   lam, 3 uniform range) with the refused value in *bad. */
int mmpp_arrivals(bitgen_t *bg, double rate_lo, double rate_hi,
                  int64_t n, double mean_sojourn_s, double lam_max,
                  double *out, double *bad) {
    double t = 0.0;
    int hi = 0;
    int64_t total = 0;
    if (!isnan(mean_sojourn_s) && signbit(mean_sojourn_s)) {
        *bad = mean_sojourn_s;
        return 1;
    }
    while (total < n) {
        double dwell = random_exponential(bg, mean_sojourn_s);
        double lam = (hi ? rate_hi : rate_lo) * dwell;
        if (!(lam >= 0.0) || lam > lam_max) { *bad = lam; return 2; }
        int64_t k = random_poisson(bg, lam);
        int64_t need = n - total;
        int64_t m = k > need ? need : k;
        double range = k > need ? dwell * (double)need / (double)k : dwell;
        if (m) {
            if (!isfinite(range) || signbit(range)) { *bad = range; return 3; }
            double *x = out + total;
            for (int64_t i = 0; i < m; i++)
                x[i] = 0.0 + range * next_double(bg);
            qsort(x, (size_t)m, sizeof(double), cmp_double);
            for (int64_t i = 0; i < m; i++)
                x[i] = t + x[i];
            total += m;
        }
        t += dwell;
        hi = !hi;
    }
    return 0;
}
"""

# numpy's own bound on Generator.poisson's lam (numpy/random/_common.pyx)
_POISSON_LAM_MAX = float(np.iinfo("l").max - np.sqrt(np.iinfo("l").max) * 10)

_mmpp_lib: Optional[object] = None
_mmpp_tried = False


def _compile_mmpp() -> Optional[object]:
    """Build (or reuse) the sampler's shared object, linked against the
    installed numpy's libnpyrandom.a; None on any failure. Its own object,
    so a failed link never costs the replay engine."""
    root = os.path.dirname(np.__file__)
    static = os.path.join(root, "random", "lib", "libnpyrandom.a")
    include = os.path.join(root, "_core", "include")
    if not (os.path.exists(static) and os.path.exists(
            os.path.join(include, "numpy", "random", "bitgen.h"))):
        return None
    with open(static, "rb") as f:
        key = np.__version__.encode() + f.read()
    path = _build(_MMPP_SOURCE, "repro_mmpp", (f"-I{include}", static, "-lm"),
                  key)
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    d = ctypes.c_double
    lib.mmpp_arrivals.restype = ctypes.c_int
    lib.mmpp_arrivals.argtypes = [
        ctypes.c_void_p, d, d, ctypes.c_int64, d, d,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.POINTER(d)]
    return lib


def mmpp_sampler() -> Optional[object]:
    """The compiled MMPP sampler, or None where it did not build (cached
    per process)."""
    global _mmpp_lib, _mmpp_tried
    if not _mmpp_tried:
        _mmpp_tried = True
        try:
            _mmpp_lib = _compile_mmpp()
        except Exception:
            _mmpp_lib = None
    return _mmpp_lib


def mmpp_arrivals(lib: object, rate_lo: float, rate_hi: float, n: int,
                  rng: np.random.Generator, mean_sojourn_s: float
                  ) -> np.ndarray:
    """`workload.mmpp_arrivals` run by the compiled sampler `lib` on
    `rng`'s own bit generator: the same bytes, and `rng` left in the same
    state. A draw numpy would refuse raises numpy's own exception."""
    out = np.empty(n, np.float64)
    bad = ctypes.c_double()
    bitgen = rng.bit_generator
    with bitgen.lock:
        code = lib.mmpp_arrivals(bitgen.ctypes.bit_generator,
                                 float(rate_lo), float(rate_hi), n,
                                 float(mean_sojourn_s), _POISSON_LAM_MAX,
                                 out, ctypes.byref(bad))
    if code:
        # numpy's argument check raises what the Python loop would have
        refuse = (rng.exponential, rng.poisson,
                  lambda r: rng.uniform(0.0, r))[code - 1]
        refuse(bad.value)
        raise AssertionError(f"numpy accepted {bad.value!r} (code {code})")
    return out
