"""Bring-up check of the DSE's device path on one TPU chip.

    python chip_smoke.py

Runs the main entry points once at their real sizes, in one process on
one chip, and checks each against its float64 / scalar reference:

  (a) refuse to run without a TPU backend (no CPU fallback);
  (b) `grid_sweep(resnet152, backend="pallas")` over the 961-point grid:
      the sweep kernel must lower to a Mosaic `tpu_custom_call`, and match
      `backend="numpy"` to 1e-6 normalized error;
  (c) `build_cost_tables()` — 10 configs x DEFAULT_HW x the default
      lattice in ONE fused dispatch — against `backend="numpy"` (1e-5);
  (d) `slo_capacity_sweep(search="auto")` for yi-9b and mixtral-8x22b:
      the max-QPS table must equal `search="sequential"`;
  (e) the search engines: the x64 lockstep replay must refuse a TPU
      (its emulated float64 is not bit-identical to the scalar simulator)
      and `backend="auto"` must match the scalar search bitwise;
      `nsga2_device` (pop 64, gens 40) must match its numpy transcription
      bitwise; `refine_design_point` must never be worse than its seed.

Each phase prints its wall seconds, the part of them spent lowering and
compiling (a warm persistent compile cache shrinks it), and its
comparison as numbers. Any failed check raises, so the script exits
non-zero; the last line, printed only when every phase passed, is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

# lowering to MLIR and the backend (XLA / Mosaic) compile, once per
# executable; tracing is left out because nested jits record it twice
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, duration, **_kw):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def _require(ok, msg):
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def _max_rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.nanmax(np.abs(a - b) / (np.abs(a) + 1.0)))


def _phase(name, fn):
    """Run one phase; print its wall and compile seconds and its result."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(f"[{name}] wall_s={wall:.3f} compile_s={_compile_s[0] - c0:.3f} "
          + " ".join(f"{k}={v}" for k, v in out.items()), flush=True)


def check_device():
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"[a] backend={jax.default_backend()} platform={info['platform']} "
          f"kind={info['kind']} count={info['count']}", flush=True)
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU backend (JAX backend is "
                 f"{jax.default_backend()!r}); refusing to run")
    return info


def grid_phase():
    from repro.core import get_workloads, grid_sweep
    from repro.kernels import ops
    from repro.kernels.dse_eval import BLOCK_C, dse_eval

    wl = get_workloads("resnet152")
    pl = grid_sweep(wl, backend="pallas")
    ref = grid_sweep(wl, backend="numpy")
    # the exact kernel program the sweep dispatched: padded configs,
    # the resident layer table, the default block
    C = -(-pl.H.size // BLOCK_C) * BLOCK_C
    text = dse_eval.lower(
        jax.ShapeDtypeStruct((C, 2), np.float32),
        jax.ShapeDtypeStruct((len(wl), 5), np.float32),
        block_c=BLOCK_C, interpret=False).compile().as_text()
    mosaic = "tpu_custom_call" in text
    err = max(_max_rel(getattr(ref, k), getattr(pl, k))
              for k in ("cycles", "energy", "utilization", "m_ub",
                        "m_inter_pe", "m_aa", "ub_bw_bits"))
    _require(ops.kernel_backend() == "mosaic", ops.kernel_backend())
    _require(mosaic, "sweep kernel did not lower to a tpu_custom_call")
    _require(err <= 1e-6, f"pallas vs numpy max rel err {err:.3e} > 1e-6")
    return {"configs": pl.H.size, "layers": len(wl), "block_c": BLOCK_C,
            "kernel": ops.kernel_backend(), "tpu_custom_call": mosaic,
            "max_rel_vs_numpy": f"{err:.3e}"}


def cost_table_phase(out):
    from repro.obs import metrics, reset_metrics
    from repro.traffic import build_cost_tables

    reset_metrics()
    ts = build_cost_tables()
    dispatches = int(metrics().get("kernels.fused_dispatches"))
    ref = build_cost_tables(backend="numpy")
    err = 0.0
    for key, a in ref.tables.items():
        b = ts.tables[key]
        for f in ("decode_cycles", "decode_energy", "decode_macs",
                  "prefill_cycles", "prefill_energy"):
            err = max(err, _max_rel(getattr(a, f), getattr(b, f)))
    _require(dispatches == 1, f"{dispatches} fused dispatches, want 1")
    _require(err <= 1e-5, f"pallas vs numpy max rel err {err:.3e} > 1e-5")
    out["tables"] = ts
    return {"archs": len(ts.archs), "configs": ts.n_configs,
            "lattice_points": ts.n_scenarios, "fused_dispatches": dispatches,
            "max_rel_vs_numpy": f"{err:.3e}"}


SLO_ARCHS = ["yi-9b", "mixtral-8x22b"]


def _slo():
    from repro.traffic import SLO, TrafficModel
    return TrafficModel(), SLO(ttft_s=2.0, tpot_s=0.1)


def slo_phase(ts):
    from repro.core.dse import slo_capacity_sweep

    tm, slo = _slo()
    auto = slo_capacity_sweep(tm, slo, archs=SLO_ARCHS, tables=ts,
                              search="auto")
    seq = slo_capacity_sweep(tm, slo, archs=SLO_ARCHS, tables=ts,
                             search="sequential")
    same = bool(np.array_equal(auto.max_qps, seq.max_qps))
    _require(same, f"auto {auto.max_qps} != sequential {seq.max_qps}")
    return {"points": auto.max_qps.size, "auto_equals_sequential": same}


def lockstep_phase(ts):
    """The x64 lockstep engine is not bit-identical on a TPU (emulated
    float64), so an explicit `backend="xla"` must refuse to run there and
    `auto` must pick an exact engine that matches the scalar search."""
    from repro.core.search import batched_max_sustainable_qps
    from repro.traffic import max_sustainable_qps
    from repro.traffic.cost_table import DEFAULT_HW

    tm, slo = _slo()
    tables = [ts.table(a, h, w) for a in SLO_ARCHS for h, w in DEFAULT_HW]
    refused = ""
    try:
        batched_max_sustainable_qps(tables, [tm] * len(tables), slo,
                                    n_requests=1200, backend="xla")
    except RuntimeError as e:
        refused = str(e)
    _require("not bit-identical" in refused, "xla engine ran on the TPU")
    stats = {}
    bat = batched_max_sustainable_qps(tables, [tm] * len(tables), slo,
                                      n_requests=1200, backend="auto",
                                      stats=stats)
    seq = [max_sustainable_qps(t, tm, slo, n_requests=1200)
           for t in tables]
    q_bat = np.asarray([q for q, _ in bat])
    q_seq = np.asarray([q for q, _ in seq])
    keys = ("ttft_p99_s", "tpot_p99_s", "energy_per_token")
    s_bat = np.asarray([[s[k] for k in keys] for _, s in bat])
    s_seq = np.asarray([[s[k] for k in keys] for _, s in seq])
    bitwise = bool(np.array_equal(q_bat, q_seq)
                   and np.array_equal(s_bat, s_seq, equal_nan=True))
    err = max(_max_rel(q_seq, q_bat), _max_rel(s_seq, s_bat))
    _require(stats["backend"] != "xla", f"auto picked {stats}")
    _require(bitwise,
             f"{stats['backend']} vs scalar differ: max rel {err:.3e}")
    return {"lanes": len(tables), "xla_refused": True,
            "auto_engine": stats["backend"], "rounds": stats["rounds"],
            "max_rel_vs_scalar": f"{err:.3e}", "bitwise": bitwise}


def nsga2_phase():
    from repro.core import get_workloads
    from repro.core.search import nsga2_device
    from repro.core.systolic import analyze_network

    wls = list(get_workloads("alexnet"))

    def eval_fn(pop):
        m = analyze_network(wls, pop[:, 0].astype(np.float64),
                            pop[:, 1].astype(np.float64))
        return np.stack([np.asarray(m.energy), np.asarray(m.cycles)], 1)

    bounds = ((16, 256), (16, 256))
    Pj, Fj = nsga2_device(eval_fn, bounds, pop=64, gens=40)
    Pn, Fn = nsga2_device(eval_fn, bounds, pop=64, gens=40,
                          backend="numpy")
    bitwise = bool(np.array_equal(Pj, Pn) and np.array_equal(Fj, Fn))
    _require(bitwise, f"nsga2_device fronts differ: {len(Pj)} vs "
                      f"{len(Pn)} points")
    return {"pop": 64, "gens": 40, "front": len(Pj), "bitwise": bitwise}


def refiner_phase():
    from repro.core import get_workloads
    from repro.core.search import refine_design_point

    r = refine_design_point(list(get_workloads("alexnet")), (128, 128),
                            steps=48)
    ok = r["objective"] <= r["seed_objective"]
    _require(ok, f"refined {r['objective']} > seed {r['seed_objective']}")
    return {"seed": r["seed"], "point": (r["h"], r["w"]),
            "objective": r["objective"],
            "seed_objective": r["seed_objective"], "never_worse": ok}


def main():
    info = check_device()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.kernels.ops import use_compile_cache

    print(f"[cache] dir={use_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    out = {}
    _phase("b grid_sweep", grid_phase)
    _phase("c cost_tables", lambda: cost_table_phase(out))
    _phase("d slo_capacity_sweep", lambda: slo_phase(out["tables"]))
    _phase("e lockstep", lambda: lockstep_phase(out["tables"]))
    _phase("e nsga2_device", nsga2_phase)
    _phase("e refiner", refiner_phase)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
