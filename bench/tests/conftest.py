"""Fixtures for the benchmark's CPU rehearsals: cells at a size a test
run holds, with the Pallas kernels in interpret mode."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [c["name"] for c in MANIFEST["workloads"]]

# a 3 x 3 grid, short traces and small draws: the same paths and the same
# comparisons as a chip run, at a size the Pallas interpreter runs quickly
SMALL = {"grid": {"lo": 64, "hi": 256, "step": 96}, "n_requests": 200,
         "draw_batches": 2, "draw_seq_lens": 1, "check_sample": 3}


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Keep the benchmark from turning on JAX's persistent compilation
    cache inside a test process that other tests share."""
    from bench import harness
    monkeypatch.setattr(harness, "_compile_cache", lambda: "off")


_CELLS = {}


@pytest.fixture
def small_cell():
    """A set-up cell at the small size, built once per process."""
    from bench import harness

    def get(name):
        if name not in _CELLS:
            _CELLS[name] = harness.Cell(name, require_tpu=False,
                                        mix_override=SMALL)
        return _CELLS[name]
    return get
