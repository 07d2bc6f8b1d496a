"""Lockstep vectorized replay: many (table, trace) lanes in ONE dispatch.

The capacity bisections behind `core.dse.slo_capacity_sweep` and
`fleet_capacity_sweep` replay the discrete-event simulator once per
(design point, probe) — hundreds of sequential `traffic.sim.simulate`
calls whose Python event loops dominate sweep wall-clock. This module
runs every design point's replay as one *lane* of a single jit-compiled
`lax.while_loop` program: each device iteration advances every lane by
one scalar-loop event, so a whole probe round over the full lattice
costs max-events iterations of fused compiled code instead of
sum-of-events Python dispatches.

The loop body is shaped by measured XLA:CPU costs. `jax.vmap` of a
`while_loop` wraps every carry in a per-lane select that copies the big
buffers every iteration, so the body is written directly over the lane
axis with explicit masks. Scatters cost ~100ns PER ELEMENT on CPU, so
the body contains none: per-event results stream into an
iteration-indexed log via `dynamic_update_slice` (every lane writes the
same column — in-place; events per lane are provably ≤ 5n+1, statically
bounding the log) and host numpy replays the log into dense arrays
after the loop. Per-op dispatch overhead (~0.5-3µs regardless of size)
dominates everything else, so ops are fused aggressively: ALL slot
state lives in one (lanes, 2·(slots+1)) carry — column s holds the slot
sort key `finish_step·(N+1) + rid` as an exactly-representable f64
(reproducing the scalar heap's lexicographic pop order), column
slots+1+s the slot's finished-prefill timestamp term — updated by a
single one-hot compare/select per step; the twelve interpolation corner
reads collapse into one 14-column gather from a per-lane concatenated
[lattices | cost grid] row plus one 10-column gather for the bulk
midpoint. Two event merges cut step count ~40%: an idle jump fuses into
the admission it always precedes, and a bulk-decode segment fuses with
its following slot completion when exactly one slot comes due.

Bit-identity contract (the whole point — property-tested in
tests/test_search.py): `simulate_many([(t, tr), ...], cfg)` returns
SimResults whose ttft/tpot arrays and float aggregates are BIT-IDENTICAL
to `traffic.sim.simulate(t, tr, cfg)` per lane. Three disciplines make
IEEE-754 doubles reproducible through XLA:

  * op-for-op replication — every float expression of the scalar loop
    (`traffic/sim.py`) is transcribed with the same association order,
    and each lane executes its own next event per iteration, so the
    accumulation order per lane is exactly the scalar loop's (the two
    event merges replay their sub-events in sequential order within the
    step);
  * `mul` (product + runtime zero) — XLA:CPU compiles with
    `AllowFPOpFusion::Fast`, which contracts a multiply feeding an add
    into one fused-multiply-add at instruction selection (single
    rounding, ≠ numpy). Adding an *opaque runtime* 0.0 to every product
    lets the contraction target THAT add: `fma(a, b, 0.0)` rounds
    exactly like a lone multiply, and the fma node cannot contract into
    the following true add — restoring two-rounding numpy semantics;
  * runtime divisors — XLA rewrites division by a compile-time constant
    into multiplication by its reciprocal (inexact for non-powers of
    two), so every bit-critical divisor (clock, lattice gaps, step
    counts) is a traced runtime scalar, never baked into the program.
    (Integer-valued f64 arithmetic below 2^53 — the slot keys — is
    exact under any compilation and needs no guard.)

These disciplines assume the backend does IEEE-754 binary64 arithmetic.
A TPU emulates float64: on a v5e most f64 products, sums and quotients
differ from the host's in the last bits (up to 3e-14 relative), and a
1200-request replay drifts by up to 4.8e-10 relative in TTFT. So the
engine refuses to run on a TPU backend (`exact_on_backend`) rather than
drift silently from the reference.

The infinite-buffer default (`ub_kib=None`) compiles a specialized
no-spill engine: the scalar path's spill terms are all exact `+ 0.0` on
strictly positive quantities there, so eliding them preserves bits.

Scope: the `prefill_first` policy (the sweeps' default). Other policies
fall back to the scalar simulator in `simulate_many` — chunked prefill
interleaves a per-lane deque whose lockstep transcription is not worth
its audit surface. Timelines are not recorded (`timeline` is empty;
`summarize`/`meets_slo` never read it) and `wall_seconds` is the whole
batch's wall time, not per-lane.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.model_core import DRAM_COST_PER_WORD, REF_BITS
from repro.traffic.sim import SimConfig, SimResult, simulate
from repro.traffic.workload import RequestTrace

_BIGF = np.float64(2.0**62)     # "free slot" sentinel key (f64-exact)
_KPAD = 8                       # lattice axes padded to this (with +inf)


def _spe() -> float:
    return DRAM_COST_PER_WORD / REF_BITS


# --------------------------------------------------------------- packing ----

def _pack_tables(tables: Sequence[object]) -> Dict[str, object]:
    """Static per-lane arrays, stacked over lanes.

    Requires every table to share one (NB, NK, NP) lattice-shape triple
    (callers group by shape first). `lat` keeps the three lattices
    +inf-padded to `_KPAD` for the fused count-based coordinate search
    (padding never wins a `<= x` test; left indices clip to len-2);
    `sg` concatenates [lat.ravel | prefill cyc | prefill en | decode cyc
    | decode en] per lane so all corner reads are gathers from one row.
    """
    L = len(tables)
    nb = len(tables[0].slot_lattice)
    nk = len(tables[0].kv_lattice)
    npr = len(tables[0].prompt_lattice)
    if max(nb, nk, npr) > _KPAD:
        raise ValueError(f"lattice axes longer than {_KPAD} unsupported")
    lat = np.full((L, 3, _KPAD), np.inf)
    first = np.empty((L, 3))
    last = np.empty((L, 3))
    sg = np.empty((L, 3 * _KPAD + 2 * npr + 2 * nb * nk))
    kvb = np.empty(L)
    for i, tb in enumerate(tables):
        sl = np.asarray(tb.slot_lattice, np.float64)
        kl = np.asarray(tb.kv_lattice, np.float64)
        pl = np.asarray(tb.prompt_lattice, np.float64)
        lat[i, 0, :nb], lat[i, 1, :nk], lat[i, 2, :npr] = sl, kl, pl
        first[i] = sl[0], kl[0], pl[0]
        last[i] = sl[-1], kl[-1], pl[-1]
        sg[i] = np.concatenate([
            lat[i].ravel(),
            np.asarray(tb.prefill_cycles, np.float64),
            np.asarray(tb.prefill_energy, np.float64),
            np.asarray(tb.decode_cycles, np.float64).ravel(),
            np.asarray(tb.decode_energy, np.float64).ravel()])
        kvb[i] = tb.kv_bits_per_token
    return {"lat": lat, "first": first, "last": last,
            "sg": sg, "kvb": kvb,
            "dims": (nb, nk, npr)}          # popped before device upload


def _pack_traces(traces: Sequence[RequestTrace], n_max: int):
    """(L, 3*(n_max+1)) request stack [arrivals | prompt | output] plus
    the per-lane live length. Row n_max is scratch; arrivals pad +inf."""
    L = len(traces)
    n1 = n_max + 1
    req = np.empty((L, 3, n1))
    n = np.empty(L, np.int64)
    for i, tr in enumerate(traces):
        k = len(tr)
        n[i] = k
        req[i, 0, :k] = tr.arrival_s
        req[i, 0, k:] = np.inf
        req[i, 1, :k] = tr.prompt_len
        req[i, 1, k:] = 1.0
        req[i, 2, :k] = tr.output_len
        req[i, 2, k:] = 1.0
    return req.reshape(L, 3 * n1), n


# ---------------------------------------------------------------- engine ----

def _build_engine(slots: int, spill: bool, dims: Tuple[int, int, int]):
    import jax
    import jax.numpy as jnp
    from jax import lax

    NB, NK, NP = dims
    GRID = 3 * _KPAD                # sg offset of the grids
    DEC = GRID + 2 * NP             # sg offset of decode cycles
    DEN = GRID + 2 * NP + NB * NK   # sg offset of decode energy
    IMAX = np.array([NB - 2, NK - 2, NP - 2], np.int64)

    def engine(static, req, n, scal):
        zero = scal["zero"]
        clock = scal["clock"]
        lat, first, last = static["lat"], static["first"], static["last"]
        sg, kvb = static["sg"], static["kvb"]
        L = req.shape[0]
        N1 = req.shape[1] // 3
        N1f = np.float64(N1)
        E = 5 * (N1 - 1) + 8        # events/lane <= 5n+1 (see module doc)
        S1 = slots + 1              # scratch slot column
        iota2s = jnp.arange(2 * S1)
        iota_k = jnp.arange(_KPAD)
        imax = jnp.asarray(IMAX)
        soff = jnp.asarray([0, _KPAD, 2 * _KPAD])

        def mul(a, b):
            return a * b + zero

        if spill:
            dram_bpc, spe, ub_bits = (scal["dram_bpc"], scal["spe"],
                                      scal["ub_bits"])

            def sp_cycles(occ_tok):
                over = mul(occ_tok, kvb) - ub_bits
                return jnp.where(over > 0.0, (2.0 * over) / dram_bpc, 0.0)

        def step(st):
            (it, t, kv, dec_s, pre_s, sp_s, energy, ms, nstep, nxt,
             active, tok, sl, lval, lidx, done) = st
            skey = sl[:, :S1]
            nstep_f = nstep.astype(jnp.float64)

            # ---- earliest-finishing slot & first free slot ------------
            minv = jnp.min(skey, axis=1)
            j = jnp.argmin(skey, axis=1)
            free = jnp.argmax(skey == _BIGF, axis=1)
            fin_r = jnp.floor(minv / N1f)           # exact for live keys
            rid = (minv - fin_r * N1f).astype(jnp.int64)
            rid_c = jnp.clip(rid, 0, N1 - 1)
            due = (~done) & (active > 0) & (minv < (nstep_f + 1.0) * N1f)

            # ---- branch masks (pop > admit[+idle] > fin > bulk) -------
            r6 = jnp.take_along_axis(
                req, jnp.stack([nxt, N1 + nxt, 2 * N1 + nxt,
                                rid_c, N1 + rid_c, 2 * N1 + rid_c], 1),
                1, mode="clip")
            arr_nxt, p_nxt, o_nxt = r6[:, 0], r6[:, 1], r6[:, 2]
            arr_r, p_r, o_r = r6[:, 3], r6[:, 4], r6[:, 5]
            ttft_r = jnp.take_along_axis(sl, S1 + j[:, None], 1,
                                         mode="clip")[:, 0]
            act0 = active == 0
            admit = ((~done) & (~due) & (active < slots) & (nxt < n)
                     & ((arr_nxt <= t) | act0))
            quiet = (~done) & (~due) & (~admit)
            fin = quiet & act0
            bulk = quiet & (~act0)

            # ---- fused lattice-coordinate search (all three axes) -----
            active_f = active.astype(jnp.float64)
            kv_per = kv / active_f
            x3 = jnp.stack([active_f, kv_per, p_nxt], 1)
            cnt = jnp.sum(lat <= x3[:, :, None], axis=2)
            i3 = jnp.clip(cnt - 1, 0, imax) + soff
            ia, j1, ip = i3[:, 0], i3[:, 1] - _KPAD, i3[:, 2] - 2 * _KPAD
            b0 = DEC + ia * NK + j1
            g14 = jnp.take_along_axis(sg, jnp.stack(
                [i3[:, 0], i3[:, 0] + 1, i3[:, 1], i3[:, 1] + 1,
                 i3[:, 2], i3[:, 2] + 1,
                 GRID + ip, GRID + ip + 1,
                 GRID + NP + ip, GRID + NP + ip + 1,
                 b0, b0 + 1, b0 + NK, b0 + NK + 1], 1), 1, mode="clip")
            f3 = (x3 - g14[:, 0:6:2]) / (g14[:, 1:6:2] - g14[:, 0:6:2])
            f3 = jnp.where(x3 <= first, 0.0,
                           jnp.where(x3 >= last, 1.0, f3))
            fa, f1, fp = f3[:, 0], f3[:, 1], f3[:, 2]
            pc = g14[:, 6] + mul(fp, g14[:, 7] - g14[:, 6])
            pen = g14[:, 8] + mul(fp, g14[:, 9] - g14[:, 8])
            plo = g14[:, 10] + mul(f1, g14[:, 11] - g14[:, 10])
            phi = g14[:, 12] + mul(f1, g14[:, 13] - g14[:, 12])
            dstep_per = plo + mul(fa, phi - plo)

            # ---- admission (an idle jump folds into its admission) ----
            t_eff = jnp.where(act0 & (arr_nxt > t), arr_nxt, t)
            if spill:
                sp_a = sp_cycles(kv + p_nxt)
                dt_a = (pc + sp_a) / clock
            else:
                dt_a = pc / clock
            t_adm = t_eff + dt_a
            ttft_val = t_adm - arr_nxt
            skey_a = (nstep_f + o_nxt) * N1f + nxt.astype(jnp.float64)

            # ---- bulk decode (midpoint-KV O(1) charging) --------------
            k0f = fin_r - nstep_f
            if spill:
                dur1 = (dstep_per + sp_cycles(kv)) / clock
            else:
                dur1 = dstep_per / clock
            k_arr = jnp.floor((arr_nxt - t) / dur1) + 1.0
            app = (active < slots) & (nxt < n)
            k = jnp.where(app & (k_arr < k0f), k_arr, k0f)
            kv_mid = kv / active_f + mul(k - 1.0, 0.5)
            cnt2 = jnp.sum(lat[:, 1] <= kv_mid[:, None], axis=1)
            j2 = jnp.clip(cnt2 - 1, 0, NK - 2)
            c0 = DEC + ia * NK + j2
            d0 = DEN + ia * NK + j2
            m10 = jnp.take_along_axis(sg, jnp.stack(
                [_KPAD + j2, _KPAD + j2 + 1,
                 c0, c0 + 1, c0 + NK, c0 + NK + 1,
                 d0, d0 + 1, d0 + NK, d0 + NK + 1], 1), 1, mode="clip")
            f2 = (kv_mid - m10[:, 0]) / (m10[:, 1] - m10[:, 0])
            f2 = jnp.where(kv_mid <= first[:, 1], 0.0,
                           jnp.where(kv_mid >= last[:, 1], 1.0, f2))
            clo = m10[:, 2] + mul(f2, m10[:, 3] - m10[:, 2])
            chi = m10[:, 4] + mul(f2, m10[:, 5] - m10[:, 4])
            cyc = clo + mul(fa, chi - clo)
            elo = m10[:, 6] + mul(f2, m10[:, 7] - m10[:, 6])
            ehi = m10[:, 8] + mul(f2, m10[:, 9] - m10[:, 8])
            den = elo + mul(fa, ehi - elo)
            if spill:
                sp_b = sp_cycles(kv + mul(mul(k, active_f), 0.5))
                dt_b = mul(k, cyc + sp_b) / clock
                en_b = den + mul(mul(sp_b, dram_bpc), spe)
                en_a = pen + mul(mul(sp_a, dram_bpc), spe)
            else:
                dt_b = mul(k, cyc) / clock
                en_b = den
                en_a = pen
            step1 = dt_b / k
            k_int = k.astype(jnp.int64)
            nstep_b = nstep + jnp.where(bulk, k_int, 0)

            # a bulk segment fuses with its completion when exactly one
            # slot comes due at its end (replayed in sequential order)
            dcnt = jnp.sum(skey < ((nstep_b.astype(jnp.float64) + 1.0)
                                   * N1f)[:, None], axis=1)
            mpop = bulk & (dcnt == 1)
            pop = due | mpop
            t_pop = jnp.where(mpop, t + dt_b, t)
            tpot_val = ((t_pop - arr_r) - ttft_r) / o_r

            # ---- merge branches ---------------------------------------
            t2 = jnp.where(admit, t_adm,
                           jnp.where(bulk, t + dt_b, t))
            kv_base = jnp.where(bulk, kv + mul(k, active_f), kv)
            kv2 = jnp.where(pop, kv_base - (p_r + o_r),
                            jnp.where(admit, kv + p_nxt, kv_base))
            dec2 = jnp.where(bulk, dec_s + dt_b, dec_s)
            pre2 = jnp.where(admit, pre_s + dt_a, pre_s)
            if spill:
                sp2 = jnp.where(admit, sp_s + sp_a / clock,
                                jnp.where(bulk,
                                          sp_s + mul(k, sp_b) / clock,
                                          sp_s))
            else:
                sp2 = sp_s
            en2 = jnp.where(admit, energy + en_a,
                            jnp.where(bulk, energy + mul(k, en_b),
                                      energy))
            ms2 = jnp.where(admit & (active > 0) & (dt_a > ms), dt_a,
                            jnp.where(bulk & (step1 > ms), step1, ms))
            nxt2 = jnp.where(admit, nxt + 1, nxt)
            active2 = jnp.where(pop, active - 1,
                                jnp.where(admit, active + 1, active))
            tok2 = jnp.where(pop, tok + o_r.astype(jnp.int64), tok)
            done2 = done | fin

            # ---- slot-state write (one one-hot select) + log column ---
            wcol = jnp.where(pop, j, free)
            hit1 = (iota2s == wcol[:, None]) & (pop | admit)[:, None]
            hit2 = ((iota2s == S1 + free[:, None]) & admit[:, None])
            val1 = jnp.where(pop, _BIGF, skey_a)
            sl2 = jnp.where(hit1, val1[:, None],
                            jnp.where(hit2, ttft_val[:, None], sl))
            wval = jnp.where(admit, ttft_val, tpot_val)
            widx = jnp.where(admit, nxt,
                             jnp.where(pop, N1 + rid_c, -1)
                             ).astype(jnp.int32)
            z = jnp.zeros((), it.dtype)
            lval2 = lax.dynamic_update_slice(lval, wval[:, None], (z, it))
            lidx2 = lax.dynamic_update_slice(lidx, widx[:, None], (z, it))
            return (it + 1, t2, kv2, dec2, pre2, sp2, en2, ms2, nstep_b,
                    nxt2, active2, tok2, sl2, lval2, lidx2, done2)

        def body(st):               # 2x unroll (no-op on finished lanes)
            return step(step(st))

        f64z = jnp.zeros(L)
        i64z = jnp.zeros(L, jnp.int64)
        init = (jnp.int32(0), f64z, f64z, f64z, f64z, f64z, f64z, f64z,
                i64z, i64z, i64z, i64z,
                jnp.concatenate([jnp.full((L, S1), _BIGF),
                                 jnp.zeros((L, S1))], axis=1),
                jnp.zeros((L, E)), jnp.full((L, E), -1, jnp.int32),
                n == 0)
        fs = lax.while_loop(lambda st: ~jnp.all(st[-1]), body, init)
        (it, t, _kv, dec_s, pre_s, sp_s, energy, ms, nstep, _nxt, _a,
         tok, _sl, lval, lidx, _d) = fs
        return {"t": t, "nstep": nstep, "tokens_out": tok,
                "iters": it, "log_val": lval, "log_idx": lidx,
                "decode_seconds": dec_s, "prefill_seconds": pre_s,
                "spill_seconds": sp_s, "energy": energy, "max_step": ms}

    return jax.jit(engine)


_ENGINES: Dict[Tuple, object] = {}


def _engine(slots: int, spill: bool, dims: Tuple[int, int, int]):
    k = (slots, spill, dims)
    if k not in _ENGINES:
        _ENGINES[k] = _build_engine(slots, spill, dims)
    return _ENGINES[k]


# ----------------------------------------------------------- public API ----

def exact_on_backend() -> bool:
    """False on a TPU backend, whose emulated float64 breaks the
    bit-identity contract (see the module docstring)."""
    import jax
    return jax.default_backend() != "tpu"


class LockstepBatch:
    """A reusable lane batch over FIXED tables: pack the table-side
    statics once, then `run` many probe rounds that differ only in their
    traces (the capacity bisection's access pattern — same design
    points, fresh arrivals per probe). All tables must share one
    lattice-shape triple and every run must pass exactly one trace per
    table, padded to the batch's `n_max`."""

    def __init__(self, tables: Sequence[object], cfg: SimConfig,
                 n_max: int):
        import jax.numpy as jnp
        from jax import enable_x64

        if cfg.policy != "prefill_first":
            raise ValueError("LockstepBatch supports prefill_first only")
        if not exact_on_backend():
            raise RuntimeError(
                "the lockstep replay engine is not bit-identical to "
                "traffic.sim.simulate on a TPU backend (float64 is "
                "emulated there); use the 'scalar' or 'native' backend")
        self.tables = list(tables)
        self.cfg = cfg
        self.n_max = int(n_max)
        packed = _pack_tables(self.tables)
        self.dims = packed.pop("dims")
        self.spill = cfg.ub_kib is not None
        scal = {"zero": np.float64(0.0),
                "clock": np.float64(cfg.clock_hz)}
        if self.spill:
            scal.update(
                dram_bpc=np.float64(cfg.dram_bits_per_cycle),
                spe=np.float64(_spe()),
                ub_bits=np.float64(float(cfg.ub_kib) * 8192.0))
        with enable_x64():
            self._static = {k: jnp.asarray(v) for k, v in packed.items()}
            self._scal = {k: jnp.asarray(v) for k, v in scal.items()}

    def run(self, traces: Sequence[RequestTrace]) -> Dict[str, np.ndarray]:
        """One lockstep round. Returns the raw per-lane result columns
        (host numpy): ttft/tpot (L, n_max) plus the aggregate vectors."""
        req, n = _pack_traces(traces, self.n_max)
        return self.run_packed(req, n)

    def run_packed(self, req: np.ndarray, n: np.ndarray
                   ) -> Dict[str, np.ndarray]:
        """`run` on pre-packed request arrays (see `_pack_traces`) — the
        bisection driver edits only the arrival third between rounds."""
        import jax.numpy as jnp
        from jax import enable_x64

        eng = _engine(self.cfg.slots, self.spill, self.dims)
        with enable_x64():
            res = eng(self._static, jnp.asarray(req), jnp.asarray(n),
                      self._scal)
            res = {k: np.asarray(v) for k, v in res.items()}
        return self._unlog(res, req.shape[0], req.shape[1] // 3)

    @staticmethod
    def _unlog(res: Dict[str, np.ndarray], L: int, N1: int
               ) -> Dict[str, np.ndarray]:
        """Replay the event log into dense ttft/tpot arrays on the host
        (numpy fancy assignment — each (lane, request) written once)."""
        it = int(res.pop("iters"))
        lidx = res.pop("log_idx")[:, :it]
        lval = res.pop("log_val")[:, :it]
        out = np.full((L, 2 * N1), np.nan)
        lane_of = np.broadcast_to(np.arange(L)[:, None], lidx.shape)
        m = lidx >= 0
        out[lane_of[m], lidx[m]] = lval[m]
        res["ttft"] = out[:, :N1 - 1]
        res["tpot"] = out[:, N1:2 * N1 - 1]
        return res


def simulate_many(items: Sequence[Tuple[object, RequestTrace]],
                  cfg: SimConfig = SimConfig()) -> List[SimResult]:
    """Replay every (table, trace) lane in lockstep on-device.

    Returns one `SimResult` per item, bit-identical to
    `simulate(table, trace, cfg)` except `wall_seconds` (whole-batch) and
    `timeline` (not recorded). Non-`prefill_first` policies fall back to
    the scalar simulator; lanes whose lattice shapes differ are grouped
    into separate dispatches (shapes are jit-static)."""
    items = list(items)
    if cfg.policy != "prefill_first":
        return [simulate(tb, tr, cfg) for tb, tr in items]
    t_wall = time.perf_counter()
    out: List[Optional[SimResult]] = [None] * len(items)
    groups: Dict[Tuple[int, int, int], List[int]] = {}
    for i, (tb, _tr) in enumerate(items):
        shape = (len(tb.slot_lattice), len(tb.kv_lattice),
                 len(tb.prompt_lattice))
        groups.setdefault(shape, []).append(i)
    for idx in groups.values():
        sub = [items[i] for i in idx]
        batch = LockstepBatch([tb for tb, _ in sub], cfg,
                              max(len(tr) for _, tr in sub))
        res = batch.run([tr for _, tr in sub])
        wall = time.perf_counter() - t_wall
        for li, i in enumerate(idx):
            out[i] = _to_result(sub[li][0], sub[li][1], cfg, res, li,
                                wall)
    return out                                          # type: ignore


_EMPTY_TIMELINE = np.empty((0, 3), np.float64)


def _to_result(table, trace: RequestTrace, cfg: SimConfig,
               res: Dict[str, np.ndarray], lane: int,
               wall: float) -> SimResult:
    """Assemble one lane of a lockstep round into a scalar-shaped
    SimResult (also used by the batched bisection driver)."""
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
