"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix and per-layer metric is found by name, and the names, units and
cross-references keep to the contract."""

import json
import os
import re

import pytest

from sb_limits import limit

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _load(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as fh:
        return json.load(fh)


@limit(20)
def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in manifest["end_to_end"])


@limit(20)
def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for cell in manifest["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert NAME.match(cell["traffic"]) and cell["config"] in configs
        pairs.add((cell["config"], cell["traffic"]))
        mix = _load("served_bench", "traffic", cell["traffic"] + ".json")
        assert mix["mode"] in ("closed", "open")
    assert len(pairs) == len(manifest["workloads"])
    used = {cell["config"] for cell in manifest["workloads"]}
    for name, entry in configs.items():
        assert name in used
        assert any(entry["file"].startswith(p + "/") for p in manifest["paths"])
        config = _load(entry["file"])
        assert config["name"] == name and config["source"] == entry["source"]
        assert len(entry["source"]) <= 200
        for key in entry["reduced"]:
            # a Configuration field is named by its own name
            assert key in config or key in config["configuration"], key
            assert key in config["reduced"], key
        assert config["guarantees"] and config["configuration"]
        assert config["f"] == (config["n"] - 1) // 3


@limit(20)
def test_every_metric_moves_something_its_cells_report(manifest):
    cells = [cell["name"] for cell in manifest["workloads"]]
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    readers = os.path.join(REPO, "served_bench", "readers")
    for m in manifest["per_layer"]:
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        spec = _load("served_bench", "metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(readers, spec["reader"] + ".py"))
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    for cell in cells:
        reported = [m for m in manifest["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2  # setup_s and at least one other
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])
