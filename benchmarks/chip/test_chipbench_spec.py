"""Every name in BENCHMARK.json resolves to its files, and the file keeps to
the benchmark's format: names, units, bounds, cells and metric coverage."""
import json
import os
import re

import pytest

from benchmarks.chip import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.startswith("benchmarks/chip/")
            assert os.path.isfile(os.path.join(harness.ROOT, word))
    assert os.path.getsize(harness.SPEC_FILE) <= 64 * 1024


def test_names_are_unique_and_well_formed():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_resolves(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("benchmarks/chip/configs/")
    with open(os.path.join(harness.ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert body["reduced"] == cfg["reduced"]
    assert {"source", "assumed", "guarantees", "layout"} <= set(body)
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.Cell.find(SPEC, cell)
    assert set(c.workload) == {"name", "config", "traffic", "chips", "why"}
    assert c.workload["chips"] in (1, 4)
    assert 1 <= len(c.workload["why"]) <= 200
    assert c.traffic["executors"] == c.workload["chips"]
    assert hasattr(harness.load_module("jobs", c.traffic["job"]), "Job")
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    e2e = {m["name"] for m in SPEC["end_to_end"] if harness.applies(m, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"] if harness.applies(m, cell)]
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert callable(harness.load_module("metrics", metric["name"]).read)
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all(1 <= len(k) <= 200 for k in layers)


def test_four_chip_cells_and_run_length_fit():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24  # later PRs may add cells up to the limit, at this run length
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200
