"""BENCHMARK.json: every cell, configuration, traffic mix and metric
resolves to its files by name, and the file keeps the benchmark's rules."""

import json
import os
import re

import pytest

from benchmark import spec

BENCH = spec.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["command"][:3] == ["python3", "-m", "benchmark.run"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_just_their_keys_and_good_names(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"])
            assert e["better"] in ("lower", "higher")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_resolves_and_states_its_cuts(cfg):
    data = spec.config(BENCH, cfg["name"])
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert data["source"] == cfg["source"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_config_traffic_driver_and_readers(cell):
    spec.config(BENCH, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    assert callable(spec.driver(traffic["kind"]).run)
    assert cell["chips"] in (1, 4)
    e2e = [m["name"] for m in spec.end_to_end(BENCH, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = spec.per_layer(BENCH, cell["name"])
    assert layers
    for m in layers:
        assert callable(spec.reader(m["name"]).read)
        assert m["moves"] in e2e


def test_metric_workload_lists_name_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


def test_each_pair_of_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_file_is_small():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
    json.dumps(BENCH)
