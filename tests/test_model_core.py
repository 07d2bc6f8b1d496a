"""Unified metrics core: numpy/Pallas backend equivalence across dataflows
and options, bitwidth-accounting invariants, DSE dispatch, and the
workload-lowering extensions (non-square inputs, dilation)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (Precision, analyze_gemm, analyze_network,
                        get_workloads, grid_sweep, list_dataflows,
                        precision_sweep)
from repro.core.dse import grid_axes
from repro.core.systolic import combine
from repro.core.workloads import Conv
from repro.kernels import ops, ref
from repro.kernels.dse_eval import OUT_COLS


def _cfgs(n=128):
    hs = grid_axes()
    H, W = np.meshgrid(hs, hs, indexing="ij")
    return np.stack([H.reshape(-1), W.reshape(-1)], 1)[:n]


class _RoundsUp(np.ndarray):
    """float32 values whose quotients land one ulp above the true one, as
    the TPU's float32 division may."""

    def __truediv__(self, other):
        q = np.divide(np.asarray(self), np.asarray(other, np.float32),
                      dtype=np.float32)
        return np.nextafter(q, np.float32(np.inf)).view(_RoundsUp)


def test_tiling_is_exact_when_division_rounds_up():
    """Whole quotients (25088 / 224 = 112) must not gain a tile of no
    rows when the division lands just above them; ragged ones keep
    their edge tile."""
    from repro.core.model_core import tiling
    D = np.array([25088, 576, 4096, 27, 1, 200], np.float32)
    s = np.array([224, 64, 256, 16, 16, 8], np.float32)
    assert np.ceil(D.view(_RoundsUp) / s).tolist()[:3] == [113, 10, 17]
    T, r = tiling(np, D.view(_RoundsUp), s)
    assert np.asarray(T).tolist() == [112, 9, 16, 2, 1, 25]
    assert np.asarray(r).tolist() == [224, 64, 256, 11, 1, 8]


def test_registry_has_all_dataflows():
    assert set(list_dataflows()) >= {"ws", "os", "multi_array"}


OPTION_SETS = [
    {},
    {"dataflow": "os"},
    {"act_reread": True},
    {"count_weight_load_hops": True},
    {"idle_pe_energy": 0.2},
    {"precision": Precision(4, 8, 16)},
    {"dataflow": "multi_array", "n_arrays": 4},
    {"dataflow": "os", "precision": Precision(16, 4, 16)},
]


@pytest.mark.parametrize("model_kw", OPTION_SETS,
                         ids=lambda kw: "-".join(map(str, kw.values()))
                         or "default")
def test_pallas_kernel_matches_numpy_core(model_kw):
    """The Pallas kernel and the float64 numpy path are the SAME closed
    forms (model_core) — they must agree to f32 roundoff for every
    dataflow/option combination, not a stale subset."""
    layers = np.asarray(get_workloads("alexnet"), np.float32)
    cfgs = _cfgs(128)
    got = np.asarray(ops.sweep(jnp.asarray(cfgs, jnp.float32),
                               jnp.asarray(layers), interpret=True,
                               **model_kw))
    want = ref.dse_eval_ref(cfgs, layers, **model_kw)
    rel = np.abs(got - want) / (np.abs(want) + 1.0)
    assert rel.max() < 1e-5, (model_kw, rel.max())


def test_grid_sweep_backends_match_on_full_resnet_sweep():
    """Acceptance: backend="pallas" matches backend="numpy" to <=1e-3
    relative error on the 961-config ResNet-152 sweep (961 is not a
    multiple of the kernel tile — exercises the auto-padding)."""
    wl = get_workloads("resnet152")
    s_np = grid_sweep(wl, backend="numpy")
    s_pl = grid_sweep(wl, backend="pallas")
    for k in ("cycles", "energy", "utilization", "m_ub", "m_inter_pe",
              "m_aa", "ub_bw_bits"):
        a = getattr(s_np, k)
        b = getattr(s_pl, k)
        rel = np.abs(a - b) / (np.abs(a) + 1.0)
        assert rel.max() < 1e-3, (k, rel.max())


def test_grid_sweep_rejects_unknown_backend():
    with pytest.raises(ValueError):
        grid_sweep(get_workloads("alexnet"), backend="fortran")


# ---------------------------------------------------------------- bitwidth --

@pytest.mark.parametrize("dataflow", ["ws", "os"])
def test_energy_halves_when_all_widths_halve(dataflow):
    full = analyze_gemm(196, 576, 128, 24, 40, dataflow=dataflow,
                        precision=Precision(8, 8, 8))
    half = analyze_gemm(196, 576, 128, 24, 40, dataflow=dataflow,
                        precision=Precision(4, 4, 4))
    assert float(half.energy) == pytest.approx(float(full.energy) / 2)
    assert float(half.ub_bandwidth_bits) == pytest.approx(
        float(full.ub_bandwidth_bits) / 2)
    # word counts and timing are width-independent
    assert float(half.cycles) == float(full.cycles)
    assert float(half.m_ub) == float(full.m_ub)


def test_default_precision_is_paper_word_accounting():
    """8/8/8 must reproduce the classic Eq.1 exactly: energy ==
    6*m_ub + 2*(m_inter_pe + m_aa) + m_intra_pe."""
    m = analyze_gemm(196, 576, 128, 24, 40)
    eq1 = (6 * float(m.m_ub) + 2 * (float(m.m_inter_pe) + float(m.m_aa))
           + float(m.m_intra_pe))
    assert float(m.energy) == pytest.approx(eq1)
    assert float(m.ub_bandwidth_bits) == pytest.approx(
        8.0 * float(m.ub_bandwidth))


def test_energy_monotone_in_each_operand_width():
    base = analyze_gemm(196, 576, 128, 24, 40)
    for kw in ({"act_bits": 16, "weight_bits": 8, "out_bits": 8},
               {"act_bits": 8, "weight_bits": 16, "out_bits": 8},
               {"act_bits": 8, "weight_bits": 8, "out_bits": 16}):
        wide = analyze_gemm(196, 576, 128, 24, 40,
                            precision=Precision(**kw))
        assert float(wide.energy) > float(base.energy), kw


def test_precision_sweep_bit_normalized():
    recs = precision_sweep(get_workloads("alexnet"), bit_widths=(4, 8, 16),
                           hs=grid_axes()[:8], ws=grid_axes()[:8])
    assert len(recs) == 9
    by_bits = {(r["act_bits"], r["weight_bits"]): r for r in recs}
    # symmetric widths: energy scales linearly with the operand width
    e4, e8, e16 = (by_bits[(b, b)]["min_energy"] for b in (4, 8, 16))
    assert e4 < e8 < e16
    assert e4 == pytest.approx(e8 / 2)
    assert e16 == pytest.approx(e8 * 2)
    # out_bits defaults to the wider operand
    assert by_bits[(4, 16)]["out_bits"] == 16
    assert all(r["ub_bw_bits_at_best"] > 0 for r in recs)


# ----------------------------------------------------------------- combine --

def test_combine_utilization_from_pe_count():
    parts = [analyze_gemm(16, 32, 32, 16, 16, groups=2),
             analyze_gemm(8, 64, 16, 16, 16, groups=4)]
    tot = combine(parts, pe_count=16 * 16)
    want = float(tot.macs) / (float(tot.cycles) * 256)
    assert float(tot.utilization) == pytest.approx(want)
    # without a PE count the field is explicitly deferred, not silently 1.0
    assert np.isnan(float(combine(parts).utilization))


def test_multi_array_aggregate_bandwidth():
    """UB bandwidth / update ports for P arrays are aggregate demand (all
    arrays stream concurrently), matching the replicated-activation energy
    accounting."""
    one = analyze_gemm(1024, 4608, 512, 128, 128)
    four = analyze_gemm(1024, 4608, 2048, 128, 128, dataflow="multi_array",
                        n_arrays=4)
    # same per-array problem (N split 2048/4 = 512): 4x the rates
    assert float(four.ub_bandwidth) == pytest.approx(
        4 * float(one.ub_bandwidth))
    assert float(four.ub_bandwidth_bits) == pytest.approx(
        4 * float(one.ub_bandwidth_bits))
    assert float(four.update_ports) == pytest.approx(
        4 * float(one.update_ports))


def test_analyze_network_multi_array_pe_count():
    wls = [(64, 128, 96, 1, 1)]
    m = analyze_network(wls, 16, 16, dataflow="multi_array", n_arrays=4)
    one = analyze_gemm(64, 128, 96, 16, 16, dataflow="multi_array",
                       n_arrays=4)
    assert float(m.utilization) == pytest.approx(float(one.utilization))
    assert float(m.utilization) <= 1.0 + 1e-9


# --------------------------------------------------- workload lowering ------

def test_conv_non_square_input():
    c = Conv(56, 64, 128, k=3, w_in=28)
    assert c.h_out == 56 and c.w_out == 28
    m, kk, n, g, r = c.gemm()
    assert m == 56 * 28
    assert kk == 64 * 9 and n == 128


def test_conv_dilation_effective_receptive_field():
    # dilation=2, k=3 -> effective 5-tap field
    c = Conv(32, 16, 32, k=3, dilation=2, pad="valid")
    assert c.k_eff == 5
    assert c.h_out == (32 - 5) + 1
    # K is unchanged by dilation (same number of taps gathered)
    m, kk, n, g, r = c.gemm()
    assert kk == 16 * 9
    assert m == 28 * 28
    # same-padding keeps the spatial size regardless of dilation
    assert Conv(32, 16, 32, k=3, dilation=4).h_out == 32
    # receptive field larger than a valid-padded input must raise, not
    # silently produce a negative (then bogus-positive, squared) M
    with pytest.raises(ValueError):
        Conv(3, 8, 8, k=3, dilation=4, pad="valid").h_out


def test_conv_square_default_unchanged():
    a = Conv(13, 192, 384, k=3)
    assert a.gemm() == (13 * 13, 192 * 9, 384, 1, 1)
