"""Design-space-exploration sweep as a Pallas kernel.

Evaluates the CAMUY closed forms for every (h, w) configuration of a
sweep against one or more layer tables in one dispatch — the TPU-native
version of the paper's config sweep (961 configs x O(100) layers).

The closed forms are NOT duplicated here: the kernel body calls the same
backend-agnostic core as the float64 numpy path (core/model_core.py with
xp=jax.numpy), so every model option (dataflow ws/os/multi_array,
act_reread, count_weight_load_hops, idle_pe_energy, per-operand bitwidths)
is supported identically on both backends. Options are jit-static: each
distinct option set compiles once.

`dse_eval_batched` is the one kernel:
  configs:    (C, 2) float32 — (h, w) per design point, C a multiple of
              CONFIG_TILE (`kernels.ops` pads to it)
  layer_sets: (S, L, 5) float32 — (M, K, N, groups, repeats) rows, one
              table per scenario, each padded to L with PAD_LAYER rows
  returns     (S, C, 8) float32 — OUT_COLS per (scenario, design point):
              movement counters summed over rows, ub_bw_bits maxed,
              utilization normalized by the PE count.
`dse_eval` is its S = 1 case, an (L, 5) table -> (C, 8).

Layout: configs on lanes. A grid step holds CONFIG_TILE configs as one
(8, 128) tile of h and one of w, and walks one scenario's rows as
scalars read from SMEM (the table flattened, one scenario's block a
step) in a `fori_loop` that stops at the table's last row of weight
groups * repeats > 0 (a scalar-prefetched length). Each row is broadcast
to the tile and evaluated once; the summed counters add into (8, 128)
carries, compensated (Kahan), so a long table's sums stay within
float32 roundoff of the exact totals. PAD_LAYER rows (1, 1, 1, 0, 0)
past a table's length are never walked; one inside it zeroes every
summed counter, and the maxed bandwidth is masked on that same weight.

Mosaic gives every intermediate of the closed forms its own scoped-VMEM
buffer, about 11 KiB per (config, row) element evaluated at once (16
MiB is the v5e's scoped limit). Here that is one row against one tile,
whatever the table's length or the number of tables. Configs on
sublanes against rows on lanes, the other way round, held a step to 8
configs x 128 rows for the same reason, padded every table to the
batch's longest and to whole 128-row chunks, and cost 11.95 us a step
on a TPU v5e, where a row against a full tile here costs 0.046 us: 2x
less per element even at 8 configs and 128 real rows, 255x at a full
tile. `kernel_ns_per_elem.sweep` in `bench/` reads the kernel's time
per (config, row) element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.model_core import analyze_gemm_core, pe_multiplier

OUT_COLS = ("cycles", "energy", "macs", "utilization", "m_ub", "m_inter_pe",
            "m_aa", "ub_bandwidth_bits")
_SUM_COLS = ("cycles", "energy", "macs", "m_ub", "m_inter_pe", "m_aa")

# Padding row for layer tables: groups*repeats == 0 zeroes every summed
# counter; the maxed bandwidth term is masked on that same weight.
PAD_LAYER = (1.0, 1.0, 1.0, 0.0, 0.0)

# Configs per grid step: one (8, 128) tile.
TILE = (8, 128)
CONFIG_TILE = TILE[0] * TILE[1]


def walked_rows(xp, layer_sets):
    """Per table of a (..., L, 5) layer-set array (namespace `xp`, numpy
    or jax.numpy): the rows the kernel walks, up to and including the
    last of groups * repeats > 0."""
    g = layer_sets[..., 3] * layer_sets[..., 4] > 0.0
    L = layer_sets.shape[-2]
    return xp.max(xp.where(g, xp.arange(1, L + 1), 0), axis=-1)


def _kernel(lens_ref, cfg_ref, rows_ref, out_ref, *, dataflow="ws",
            n_arrays=1, **opts):
    """One (8, 128) tile of h and of w vs one scenario's rows, read as
    SMEM scalars up to its length -> (8, 8, 128), OUT_COLS first."""
    h = cfg_ref[0]
    w = cfg_ref[1]

    def body(r, carry):
        sums, comp, bw = carry
        M, K, N, groups, repeats = (
            jnp.full(TILE, rows_ref[0, 5 * r + j], jnp.float32)
            for j in range(5))
        g = groups * repeats
        d = analyze_gemm_core(jnp, M, K, N, h, w, groups=g,
                              dataflow=dataflow, n_arrays=n_arrays, **opts)
        # Kahan: `comp` carries the low-order bits each addition drops
        new_sums, new_comp = [], []
        for s, c, k in zip(sums, comp, _SUM_COLS):
            y = d[k] - c
            t = s + y
            new_comp.append((t - s) - y)
            new_sums.append(t)
        bw = jnp.maximum(bw, jnp.where(g > 0.0, d["ub_bandwidth_bits"],
                                       0.0))
        return tuple(new_sums), tuple(new_comp), bw

    zero = jnp.zeros(TILE, jnp.float32)
    zeros = (zero,) * len(_SUM_COLS)
    sums, _, bw = jax.lax.fori_loop(0, lens_ref[pl.program_id(0)], body,
                                    (zeros, zeros, zero))
    cols = dict(zip(_SUM_COLS, sums))
    pe = h * w * pe_multiplier(dataflow, n_arrays)
    cols["utilization"] = cols["macs"] / jnp.maximum(cols["cycles"] * pe,
                                                     1.0)
    cols["ub_bandwidth_bits"] = bw
    for j, k in enumerate(OUT_COLS):
        out_ref[j] = cols[k]


def _dse_eval(configs, layer_sets, *, interpret: bool = False, **opts):
    C = configs.shape[0]
    assert C % CONFIG_TILE == 0, (C, CONFIG_TILE)
    S, L, _ = layer_sets.shape
    layer_sets = layer_sets.astype(jnp.float32)
    planes = configs.astype(jnp.float32).T.reshape(2, C // TILE[1], TILE[1])
    # a table's SMEM block is (1, L * 5): Mosaic takes a block whose last
    # two dimensions are the array's own, not a squeezed (L * 5,) one
    out = pl.pallas_call(
        functools.partial(_kernel, **opts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, C // CONFIG_TILE),
            in_specs=[
                pl.BlockSpec((2,) + TILE, lambda s, i, lens: (0, i, 0)),
                pl.BlockSpec((None, 1, L * 5), lambda s, i, lens: (s, 0, 0),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((None, len(OUT_COLS)) + TILE,
                                   lambda s, i, lens: (s, 0, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (S, len(OUT_COLS), C // TILE[1], TILE[1]), jnp.float32),
        interpret=interpret,
    )(walked_rows(jnp, layer_sets).astype(jnp.int32), planes,
      layer_sets.reshape(S, 1, L * 5))
    return out.reshape(S, len(OUT_COLS), C).transpose(0, 2, 1)


_jit = functools.partial(
    jax.jit, static_argnames=("interpret", "dataflow", "precision",
                              "act_reread", "count_weight_load_hops",
                              "idle_pe_energy", "n_arrays"))


@_jit
def dse_eval_batched(configs, layer_sets, **kw):
    """Fused sweep over S scenarios x C configs in a single dispatch.

    configs: (C, 2) float32, C % CONFIG_TILE == 0 — shared (h, w) points
    layer_sets: (S, L, 5) float32 — one padded layer table per scenario
      (pad rows are PAD_LAYER; see module docstring)
    kw: `interpret` and the model options of
      `model_core.analyze_gemm_core`
    Returns (S, C, 8) float32 — OUT_COLS per (scenario, design point).
    """
    return _dse_eval(configs, layer_sets, **kw)


@_jit
def dse_eval(configs, layers, **kw):
    """`dse_eval_batched` of one (L, 5) layer table -> (C, 8)."""
    return _dse_eval(configs, layers[None], **kw)[0]


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
