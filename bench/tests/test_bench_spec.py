"""`BENCHMARK.json` resolves to its files and keeps the contract's form."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import harness

from bench.harness import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_form(spec):
    assert set(spec) == TOP_KEYS
    assert spec["paths"] == ["bench"]
    assert all(PATH.match(p) and ".." not in p for p in spec["paths"])
    assert 1 <= len(spec["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") for w in spec["command"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entries(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    metrics = spec["end_to_end"] + spec["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names)
    assert len(set(c["name"] for c in spec["configs"])) == len(spec["configs"])
    assert len(set(w["name"] for w in spec["workloads"])) == \
        len(spec["workloads"])
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "source", "layer", "moves"}
        assert _line(m["layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]])
def test_cell_resolves_to_its_files(spec, cell):
    c = harness.Cell(spec, cell, ROOT)
    assert c.config_entry["file"].startswith("bench/")
    cfg = c.config
    for key in ("pipeline", "params", "frame", "plan", "batch_size",
                "backends", "reference", "types", "outputs", "source",
                "assumed", "reduced"):
        assert key in cfg, key
    assert set(cfg["outputs"]) <= set(cfg["types"])
    assert callable(c.reference())
    assert c.traffic["loop"] in ("closed", "open")
    e2e = [m["name"] for m in c.metrics(traced=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = c.metrics(traced=True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
    for m in c.metrics(False) + layer:
        assert callable(c.reader(m["name"]))


def test_per_layer_workloads_report_what_they_move(spec):
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        for cell in m["workloads"]:
            assert cell in cells
            moved = [e for e in spec["end_to_end"] if e["name"] == m["moves"]]
            assert moved and cell in moved[0].get("workloads", [cell])


def test_layers_are_named_alike(spec):
    layers = {m["layer"] for m in spec["per_layer"]}
    assert layers <= {"serving", "executor", "device", "device program"}


def test_four_chip_cells_at_most_half(spec):
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
