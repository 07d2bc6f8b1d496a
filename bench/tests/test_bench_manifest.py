"""BENCHMARK.json is data: every configuration, traffic mix, query kind,
reference lowering and metric it names is found by file name alone, and
its names, units and links keep to the benchmark's rules."""
import json
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import MANIFEST, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_and_entries_have_exactly_their_keys():
    assert set(MANIFEST) == KEYS
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_and_units_use_the_allowed_characters():
    named = (MANIFEST["configs"] + MANIFEST["workloads"]
             + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    names = [e["name"] for e in named]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    for m in MANIFEST["per_layer"]:
        for cell in m["workloads"]:
            reported = {e["name"] for e in harness.reported_metrics(
                MANIFEST, cell, "end_to_end")}
            assert m["moves"] in reported, (m["name"], cell)
    for w in MANIFEST["workloads"]:
        e2e = harness.reported_metrics(MANIFEST, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.reported_metrics(MANIFEST, w["name"], "per_layer")


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_each_part_of_a_cell_is_found_by_file_name(cell):
    c, cfg, mix, kind, lower = harness.cell_parts(MANIFEST, cell)
    for fn in ("prepare", "variants", "draw", "run", "points",
               "elements", "keep", "control", "check"):
        assert callable(getattr(kind, fn)), fn
    assert callable(lower)
    assert set(mix["limits"]) and mix["check_sample"] >= 1
    files = {e["name"]: e["file"] for e in MANIFEST["configs"]}
    assert files[c["config"]] == f"bench/configs/{c['config']}.json"
    for section in ("end_to_end", "per_layer"):
        for m in harness.reported_metrics(MANIFEST, cell, section):
            path = os.path.join(harness.BENCH, "metrics", m["name"] + ".py")
            mod = harness.load_module(path, "t_" + m["name"].replace(".", "_"))
            assert callable(mod.read)


def test_command_and_paths_stay_inside_the_benchmark():
    assert MANIFEST["paths"] == ["bench"]
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.exists(os.path.join(ROOT, MANIFEST["command"][1]))
    for c in MANIFEST["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
