"""Cells, configurations, traffic, limits and metric readers are found by
name from BENCHMARK.json, and the file keeps to the benchmark's format."""
import json
import os
import re

import pytest

from perfbench import check, harness, model

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert BENCH["command"][1].startswith("perfbench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    spec = harness.cell_spec(cell)
    assert spec["cell"]["chips"] in (1, 4)
    assert spec["traffic"]["loop"] in ("closed", "open")
    assert set(spec["limits"]) <= set(check.NUMBERS)
    assert spec["end_to_end"] and spec["per_layer"]
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    for m in spec["per_layer"]:
        assert m["moves"] in names, (cell, m["name"])
    pcfg = model.pipeline_config(spec["config"])
    assert pcfg.llm.num_layers == \
        spec["config"]["trunk"]["num_hidden_layers"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_end_to_end_metric_has_a_definition(cell):
    spec = harness.cell_spec(cell)
    for m in spec["end_to_end"]:
        harness.end_to_end(m["name"], [], 0.0, 1.0, 1.0, 1.0)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = harness.reader(metric)
    assert callable(read)


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_config_is_used_and_its_file_is_its_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("perfbench/")
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced_from"])
