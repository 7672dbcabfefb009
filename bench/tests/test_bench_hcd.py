"""The hcd-1080p configuration: its plain reference, its control, and a
whole tiny CPU run of its cell, read by `f64_stages`."""
from __future__ import annotations

import json
import os
import time
import types

import numpy as np
import pytest

from bench import check, harness
from bench.harness import ROOT

CELL = "hcd-1080p.offline"


def _config(frame):
    with open(os.path.join(ROOT, "bench", "configs", "hcd-1080p.json")) as f:
        cfg = json.load(f)
    cfg["frame"] = list(frame)
    cfg["outputs"] = {"harris": list(frame)}
    return cfg


def _reference():
    return harness.load_file(os.path.join(ROOT, "bench", "refs",
                                          "hcd.py")).run


def test_config_types_are_the_plans():
    cfg = _config((64, 128))
    _, plan_types, _ = harness.build_plan(cfg)
    got = {n: ("s" if t.signed else "u") + str(t.alpha)
           for n, t in plan_types.items()}
    assert got == cfg["types"]


@pytest.mark.parametrize("frame", [(37, 50), (64, 128)])
def test_reference_equals_the_program_oracle(frame):
    from repro.dsl.exec import run_fixed
    cfg = _config(frame)
    pipe, plan_types, params = harness.build_plan(cfg)
    for img in harness.frame_pool(7, frame, 3):
        served = run_fixed(pipe, img.astype(np.float64), plan_types, params)
        ref = check.reference_outputs(_reference(), img, cfg)
        assert set(served) == set(ref)
        assert check.count_mismatches(served, ref, ["harris"]) == 0


def test_control_one_fractional_bit_fewer_fails():
    cfg = _config((64, 128))
    pool = harness.frame_pool(11, (64, 128), 4)
    assert check.control_readings(pool, _reference(), cfg) > 1000


def test_traced_tiny_run_is_correct_and_all_integer(tiny_bench):
    root, spec = tiny_bench
    cell = harness.Cell(spec, CELL, root)
    assert "f64_stages" in {m["name"] for m in cell.metrics(traced=True)}
    res = harness.run_cell(cell, 2**31 + 29, 1.0, True, time.perf_counter(),
                           log=lambda m: None)
    assert res["correct"] is True, res["check"]
    assert res["device"]["backend"] == "lowered"
    assert res["metrics"]["f64_stages"] == {"value": 0.0, "unit": "stages"}


def _span(name, t0, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t0 + 0.1, attrs=attrs)


def test_f64_stages_reader():
    read = harness.load_file(os.path.join(ROOT, "bench", "metrics",
                                          "f64_stages.py")).read
    run = types.SimpleNamespace(spans=None, t0=0.0, t1=10.0)
    assert read(run) is None
    run.spans = [_span("exec.lowered", 1.0, f64_stages=2),
                 _span("exec.lowered", 2.0, f64_stages=0),
                 _span("exec.pallas", 20.0, f64_stages=5),
                 _span("serve.batch", 3.0, size=4)]
    assert read(run) == 2
    # a program whose executor spans carry no such attribute
    run.spans = [_span("exec.lowered", 1.0), _span("serve.batch", 1.0,
                                                   size=4)]
    assert read(run) is None
