"""Design-space-exploration sweep as a Pallas kernel.

Evaluates the CAMUY closed forms for a whole block of (h, w) configurations
against a VMEM-resident layer table in one grid step — the TPU-native
version of the paper's config sweep (961 configs x O(100) layers).

The closed forms are NOT duplicated here: the kernel body calls the same
backend-agnostic core as the float64 numpy path (core/model_core.py with
xp=jax.numpy), so every model option (dataflow ws/os/multi_array,
act_reread, count_weight_load_hops, idle_pe_energy, per-operand bitwidths)
is supported identically on both backends. Options are jit-static: each
distinct option set compiles once.

Inputs:
  configs: (C, 2) float32 — (h, w) per design point, C % block_c == 0
  layers:  (L, 5) float32 — (M, K, N, groups, repeats) per GEMM workload;
           a table longer than LAYER_CHUNK is padded to a multiple of it
Outputs:
  (C, 8) float32 — OUT_COLS per design point (movement counters summed over
  layers, ub_bw_bits maxed, utilization normalized by the PE count).

`dse_eval_batched` extends the same kernel body to BATCHED layer sets: a
(S, L, 5) tensor of S padded per-scenario layer tables evaluated against
the shared config list in ONE fused dispatch over the (scenario, config
block) grid — the serving-scenario sweep (core/dse.scenario_sweep) runs the
whole scenario matrix without a Python loop of per-scenario sweeps, and the
traffic cost-table build (traffic/cost_table.py) lowers its full
(arch x slot x kv-span / prompt) lattice the same way, one kernel call for
every simulator lookup table. Padding rows are PAD_LAYER (1, 1, 1, 0, 0):
groups*repeats == 0 zeroes every summed counter, and the per-cycle
bandwidth/port maxima are masked on that same weight. `kernels.ops` pads
config lists to the block for both kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.model_core import (Precision, analyze_gemm_core,
                                   pe_multiplier)

OUT_COLS = ("cycles", "energy", "macs", "utilization", "m_ub", "m_inter_pe",
            "m_aa", "ub_bandwidth_bits")
_SUM_COLS = ("cycles", "energy", "macs", "m_ub", "m_inter_pe", "m_aa")

# Padding row for layer tables: groups*repeats == 0 zeroes every summed
# counter; the maxed bandwidth term is masked on that same weight.
PAD_LAYER = (1.0, 1.0, 1.0, 0.0, 0.0)

# Mosaic gives every intermediate of the closed forms its own scoped-VMEM
# buffer: about 11 KiB per (config, layer) element of a grid step (a v5e
# compile of a single pass over the table needs 16.8 MB at 8 configs x 192
# layers and 33.5 MB at 128 x 18; the scoped limit is 16 MiB). So a grid
# step takes one sublane tile of configs, and its body walks the resident
# layer table one lane tile of layers at a time (~11 MiB live whatever the
# table's length).
BLOCK_C = 8
LAYER_CHUNK = 128


def padded_length(L: int) -> int:
    """Rows the kernels walk for an L-row layer table: L up to one chunk,
    else L rounded up to a multiple of LAYER_CHUNK."""
    return L if L <= LAYER_CHUNK else -(-L // LAYER_CHUNK) * LAYER_CHUNK


def _pad_layers(layers):
    """Pad the layer axis (second to last) of a float32 layer table up to
    `padded_length` rows."""
    L = layers.shape[-2]
    pad = padded_length(L) - L
    if not pad:
        return layers
    rows = jnp.broadcast_to(jnp.asarray(PAD_LAYER, jnp.float32),
                            layers.shape[:-2] + (pad, 5))
    return jnp.concatenate([layers, rows], axis=-2)


def _layer_terms(h, w, layers, *, dataflow, precision, act_reread,
                 count_weight_load_hops, idle_pe_energy, n_arrays):
    """(block_c,) h/w vs (l, 5) layer rows -> the _SUM_COLS counters summed
    over the rows and ub_bandwidth_bits maxed over them, each (block_c,)."""
    M = layers[:, 0][None, :]
    K = layers[:, 1][None, :]
    N = layers[:, 2][None, :]
    g = (layers[:, 3] * layers[:, 4])[None, :]
    h = h[:, None]
    w = w[:, None]
    d = analyze_gemm_core(
        jnp, M, K, N, h, w, dataflow=dataflow, groups=g,
        precision=precision, act_reread=act_reread,
        count_weight_load_hops=count_weight_load_hops,
        idle_pe_energy=idle_pe_energy, n_arrays=n_arrays)
    # terms independent of (h, w) — e.g. macs, UB word counts — come back
    # (1, l); broadcast to the full (block_c, l) before reducing over layers.
    # Padding rows carry groups*repeats == 0, which already zeroes the
    # summed counters; the maxed per-cycle terms (bandwidth, ports) must be
    # masked explicitly or a (1, 1, 1) pad row would dominate them.
    full = (h.shape[0], layers.shape[0])
    valid = g > 0.0
    sums = tuple(jnp.sum(jnp.broadcast_to(d[k], full), axis=1)
                 for k in _SUM_COLS)
    bw = jnp.max(jnp.where(jnp.broadcast_to(valid, full),
                           jnp.broadcast_to(d["ub_bandwidth_bits"], full),
                           0.0), axis=1)
    return sums, bw


def _eval_block(h, w, layers_ref, **opts):
    """(block_c,) h/w vs the resident (L, 5) layer table -> (block_c, 8)
    metrics. A table longer than LAYER_CHUNK (padded to a multiple of it)
    is reduced chunk by chunk in a loop, so the live set stays one chunk's
    worth of intermediates."""
    L = layers_ref.shape[0]
    if L <= LAYER_CHUNK:
        sums, bw = _layer_terms(h, w, layers_ref[...], **opts)
    else:
        def body(i, acc):
            start = pl.multiple_of(i * LAYER_CHUNK, LAYER_CHUNK)
            s, b = _layer_terms(
                h, w, layers_ref[pl.ds(start, LAYER_CHUNK), :], **opts)
            return (tuple(a + x for a, x in zip(acc[0], s)),
                    jnp.maximum(acc[1], b))

        zero = jnp.zeros_like(h)
        sums, bw = jax.lax.fori_loop(0, L // LAYER_CHUNK, body,
                                     ((zero,) * len(_SUM_COLS), zero))
    cols = dict(zip(_SUM_COLS, sums))
    pe = h * w * pe_multiplier(opts["dataflow"], opts["n_arrays"])
    cols["utilization"] = cols["macs"] / jnp.maximum(cols["cycles"] * pe,
                                                     1.0)
    cols["ub_bandwidth_bits"] = bw
    return jnp.stack([cols[k] for k in OUT_COLS], axis=1)


def _kernel(cfg_ref, layers_ref, out_ref, **opts):
    out_ref[...] = _eval_block(cfg_ref[:, 0], cfg_ref[:, 1], layers_ref,
                               **opts)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "interpret", "dataflow", "precision",
                     "act_reread", "count_weight_load_hops",
                     "idle_pe_energy", "n_arrays"))
def dse_eval(configs, layers, *, block_c: int = BLOCK_C,
             interpret: bool = False, dataflow: str = "ws",
             precision: Precision = None, act_reread: bool = False,
             count_weight_load_hops: bool = False,
             idle_pe_energy: float = 0.0, n_arrays: int = 1):
    C = configs.shape[0]
    assert C % block_c == 0, (C, block_c)
    layers = _pad_layers(layers.astype(jnp.float32))
    L = layers.shape[0]
    kernel = functools.partial(
        _kernel, dataflow=dataflow, precision=precision,
        act_reread=act_reread,
        count_weight_load_hops=count_weight_load_hops,
        idle_pe_energy=idle_pe_energy, n_arrays=n_arrays)
    return pl.pallas_call(
        kernel,
        grid=(C // block_c,),
        in_specs=[
            pl.BlockSpec((block_c, 2), lambda i: (i, 0)),
            pl.BlockSpec((L, 5), lambda i: (0, 0)),   # layer table resident
        ],
        out_specs=pl.BlockSpec((block_c, len(OUT_COLS)), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, len(OUT_COLS)), jnp.float32),
        interpret=interpret,
    )(configs.astype(jnp.float32), layers)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "interpret", "dataflow", "precision",
                     "act_reread", "count_weight_load_hops",
                     "idle_pe_energy", "n_arrays"))
def dse_eval_batched(configs, layer_sets, *, block_c: int = BLOCK_C,
                     interpret: bool = False, dataflow: str = "ws",
                     precision: Precision = None, act_reread: bool = False,
                     count_weight_load_hops: bool = False,
                     idle_pe_energy: float = 0.0, n_arrays: int = 1):
    """Fused sweep over S scenarios x C configs in a single dispatch.

    configs: (C, 2) float32, C % block_c == 0 — shared (h, w) design points
    layer_sets: (S, L, 5) float32 — one padded layer table per scenario
      (pad rows are PAD_LAYER; see module docstring)
    Returns (S, C, 8) float32 — OUT_COLS per (scenario, design point).
    """
    C = configs.shape[0]
    assert C % block_c == 0, (C, block_c)
    layer_sets = _pad_layers(layer_sets.astype(jnp.float32))
    S, L, _ = layer_sets.shape
    kernel = functools.partial(
        _kernel, dataflow=dataflow, precision=precision,
        act_reread=act_reread,
        count_weight_load_hops=count_weight_load_hops,
        idle_pe_energy=idle_pe_energy, n_arrays=n_arrays)
    # the scenario axis is squeezed out of both blocks (None), so the
    # body is `dse_eval`'s: Mosaic refuses the chunk loop's row slices of
    # a (1, L, 5) block ("slice shape along dimension 2 must be aligned
    # to tiling (128)")
    return pl.pallas_call(
        kernel,
        grid=(S, C // block_c),
        in_specs=[
            pl.BlockSpec((block_c, 2), lambda s, i: (i, 0)),
            pl.BlockSpec((None, L, 5), lambda s, i: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_c, len(OUT_COLS)),
                               lambda s, i: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, C, len(OUT_COLS)), jnp.float32),
        interpret=interpret,
    )(configs.astype(jnp.float32), layer_sets)


def relaxed_objectives(workloads, objectives=("energy", "cycles"),
                       **model_kw):
    """Differentiable network objectives as a jnp function of (h, w).

    Builds the same closed forms as the sweep kernels — one
    `analyze_gemm_core(jnp, ...)` call over the network's layer table —
    but with the continuous tiling relaxation (`model_core.tiling` with
    `relaxed=True`), so the returned ``f(x)`` (x = jnp array [h, w]) is
    smooth and `jax.grad(f)` exists everywhere on the design plane.

    Objective names follow `core.dse`: "energy" / "cycles" minimized,
    "utilization" negated so it is minimized too. Returns a (k,) jnp
    vector per call. Relaxed values under-count edge-tile raggedness:
    they steer proposals (`core.search.refine_design_point`); every
    reported number comes from the exact numpy forms
    (`core.systolic.analyze_network`).
    """
    import numpy as np
    for o in objectives:
        if o not in ("energy", "cycles", "utilization"):
            raise ValueError(f"unknown objective {o!r}")
    layers = np.asarray([(M, K, N, g, rep)
                         for (M, K, N, g, rep) in workloads], np.float64)
    M = jnp.asarray(layers[:, 0])
    K = jnp.asarray(layers[:, 1])
    N = jnp.asarray(layers[:, 2])
    g = jnp.asarray(layers[:, 3] * layers[:, 4])
    dataflow = model_kw.pop("dataflow", "ws")
    n_arrays = model_kw.pop("n_arrays", 1)
    pe_mult = pe_multiplier(dataflow, n_arrays)

    def f(x):
        h, w = x[0], x[1]
        d = analyze_gemm_core(jnp, M, K, N, h, w, dataflow=dataflow,
                              groups=g, n_arrays=n_arrays, relaxed=True,
                              **model_kw)
        cyc = jnp.sum(d["cycles"])
        cols = {"cycles": lambda: cyc,
                "energy": lambda: jnp.sum(d["energy"]),
                "utilization": lambda: -jnp.sum(d["macs"]) / (
                    jnp.maximum(cyc, 1.0) * h * w * pe_mult)}
        return jnp.stack([cols[o]() for o in objectives])

    return f
