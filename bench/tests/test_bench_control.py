"""The comparison that decides `correct` fails where it must: with the
control (the plain reference computed in bfloat16) in the program's
place, and with the timed path broken underneath in each way a cell can
break."""
import numpy as np
import pytest

from bench.tests.conftest import CELLS

SWEEP_CELLS = ["resnet152.grid", "olmoe-1b-7b.scenario_grid"]
CAPACITY_CELLS = ["olmoe-1b-7b.capacity_poisson",
                  "olmoe-1b-7b.capacity_mmpp"]


def failing(cell, **kw):
    w = cell.window(2 ** 31 + 3, 0.2, **kw)
    assert w["failed"] == 0 and w["kept"]
    checks = cell.check(w["kept"])
    return [k for k, v in checks.items() if not v["value"] <= v["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(small_cell, name):
    cell = small_cell(name)
    assert failing(cell) == []
    assert failing(cell, replace_kept=cell.kind.control)


def _kernel_fault(monkeypatch, fault):
    """Break both sweep kernels' wrappers where their answer is made."""
    from repro.kernels import ops
    for name in ("sweep", "sweep_batched"):
        real = getattr(ops, name)

        def broken(*a, _real=real, **kw):
            return fault(np.array(_real(*a, **kw)))
        monkeypatch.setattr(ops, name, broken)


def _alter_one(out):
    out[..., 0, 1] *= 1.01              # one energy of one design point
    return out


def _half_left_out(out):
    """Half of the batch left out: its rows repeat the other half's."""
    axis = out.ndim - 2 if out.shape[-2] > 1 else 0
    n = out.shape[axis]
    idx = np.arange(n) % max(n // 2, 1)
    return np.take(out, idx, axis=axis)


@pytest.mark.parametrize("fault", [_alter_one, _half_left_out])
@pytest.mark.parametrize("name", SWEEP_CELLS + CAPACITY_CELLS)
def test_broken_kernel_fails(small_cell, monkeypatch, name, fault):
    cell = small_cell(name)
    _kernel_fault(monkeypatch, fault)
    assert failing(cell)


@pytest.mark.parametrize("name", CAPACITY_CELLS)
def test_altered_capacity_answer_fails(small_cell, monkeypatch, name):
    from repro.core import dse
    real = dse.slo_capacity_sweep

    served = []

    def broken(*a, **kw):
        res = real(*a, **kw)
        served.append(res.max_qps.max())
        res.max_qps[0, np.argmax(res.max_qps[0])] *= 1.25
        return res
    cell = small_cell(name)
    monkeypatch.setattr(dse, "slo_capacity_sweep", broken)
    assert failing(cell)
    assert max(served) > 0


@pytest.mark.parametrize("fault", ["early_stop", "answer_high"])
@pytest.mark.parametrize("name", CAPACITY_CELLS)
def test_wrong_capacity_search_fails(small_cell, monkeypatch, name, fault):
    """A bisection that stops one halving early, or answers a rate it saw
    miss the SLO: caught by the check of the answer itself."""
    from bench.calibrate import FAULTS
    cell = small_cell(name)
    FAULTS[fault](monkeypatch.setattr)
    gap = {"early_stop": "qps_low_gap", "answer_high": "qps_high_gap"}
    assert gap[fault] in failing(cell)
