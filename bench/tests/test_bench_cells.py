"""Each cell of BENCHMARK.json rehearsed on the CPU at a small size: set-up,
a window of whole queries, the correctness comparison and the result
line, as a chip run makes them."""
import json

import pytest

from bench import harness
from bench.tests.conftest import CELLS, MANIFEST, SMALL


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    r = harness.run(cell, 2 ** 31 + 11, 0.3, False, require_tpu=False,
                    mix_override=SMALL)
    assert json.loads(json.dumps(r)) == r
    assert list(r)[:3] == ["correct", "attempted", "failed"]
    assert list(r)[-1] == "checks"
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    want = {m["name"] for m in harness.reported_metrics(
        MANIFEST, cell, "end_to_end")}
    assert set(r["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["run"]["window_compiles"] == 0
    assert r["run"]["dispatches_per_query"] == 1.0


def test_traced_run_reports_per_layer_metrics():
    cell = "olmoe-1b-7b.capacity_poisson"
    r = harness.run(cell, 7, 0.3, True, require_tpu=False,
                    mix_override=SMALL)
    assert r["correct"]
    assert {"table_build_ms.capacity", "search_ms.capacity",
            "probes_per_query.capacity"} <= set(r["metrics"])
    assert r["device"]["window_s"] > 0
    assert r["breakdown"]["idle_gaps"]
    assert r["run"]["engine"] == "packed"


def test_same_seed_draws_the_same_queries(small_cell):
    c = small_cell("olmoe-1b-7b.capacity_poisson")
    draws = []
    for _ in range(2):
        import numpy as np
        rng, queue = np.random.default_rng([5, 0]), []
        draws.append([c.kind.draw(c.state, rng, queue) for _ in range(6)])
    assert draws[0] == draws[1]


def test_refuses_a_host_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        harness.run("resnet152.grid", 1, 0.1, False)
    assert e.value.code == 2
