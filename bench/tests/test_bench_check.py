"""How `correct` is decided: the plain references, the control, and
whole runs on the CPU with the timed path broken underneath."""
from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from bench import check, harness
from bench.harness import ROOT

CONFIGS = ["dus-1080p", "usm-1080p"]


def _config(name, frame):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    (h, w), (th, tw) = cfg["frame"], frame
    cfg["frame"] = list(frame)
    cfg["outputs"] = {k: [-(-oh * th // h), -(-ow * tw // w)]
                      for k, (oh, ow) in cfg["outputs"].items()}
    return cfg


def _reference(cfg):
    return harness.load_file(os.path.join(ROOT, cfg["reference"])).run


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("frame", [(37, 50), (64, 128)])
def test_reference_equals_the_program_oracle(name, frame):
    """The plain reference agrees, stage by stage, with the program's
    own numpy oracle (`run_fixed(backend="numpy")`) under the plan the
    harness builds, also at ragged sizes."""
    from repro.dsl.exec import run_fixed
    cfg = _config(name, frame)
    pipe, types, params = harness.build_plan(cfg)
    for img in harness.frame_pool(7, frame, 3):
        served = run_fixed(pipe, img.astype(np.float64), types, params)
        ref = check.reference_outputs(_reference(cfg), img, cfg)
        assert set(served) == set(ref)
        assert check.count_mismatches(served, ref, list(cfg["outputs"])) == 0


@pytest.mark.parametrize("name", CONFIGS)
def test_control_one_fractional_bit_fewer_fails(name):
    cfg = _config(name, (64, 128))
    pool = harness.frame_pool(11, (64, 128), 4)
    assert check.control_readings(pool, _reference(cfg), cfg) > 1000


def test_mismatch_counting():
    ref = {"a": np.zeros((2, 3)), "b": np.ones(4)}
    assert check.count_mismatches({"a": np.zeros((2, 3)), "b": np.ones(4)},
                                  ref, ["a"]) == 0
    assert check.count_mismatches({"b": np.ones(4)}, ref, ["a"]) == 6
    assert check.count_mismatches({"a": np.zeros((3, 2))}, ref, ["a"]) == 6
    assert check.count_mismatches({"a": np.zeros((2, 3)), "z": np.ones(5)},
                                  ref, ["a"]) == 5
    served = {"a": np.zeros((2, 3))}
    served["a"][1, 2] = 0.0625
    assert check.count_mismatches(served, ref, ["a"]) == 1


def test_sample_is_seeded_and_bounded():
    class F:
        def __init__(self, i):
            self.pool_idx = i

    def fill(seed):
        s = check.Sample(seed, size=4)
        for i in range(100):
            s.offer(F(i), {"x": i})
        return [k for k, _ in s.kept]

    assert fill(3) == fill(3)
    assert len(fill(3)) == 4 and fill(3) != fill(4)
    assert max(fill(3)) >= 4          # later frames are drawn too


# -- whole runs on the CPU --------------------------------------------------

def _altered(out, prev):
    """An answer altered where it is produced: one LSB on one pixel of
    the first output of every frame."""
    out = {k: np.array(v) for k, v in out.items()}
    first = sorted(out)[0]
    out[first][:, 0, 0] += 2.0 ** -4
    return out


def _half_batch(out, prev):
    """Half of the batch left out: its second half gets the results of
    the first half."""
    out = {k: np.array(v) for k, v in out.items()}
    for v in out.values():
        h = v.shape[0] // 2
        v[h:] = v[: v.shape[0] - h]
    return out


def _stale(out, prev):
    """A step that hands back its state unchanged: each batch returns
    the previous batch's results."""
    return prev


def _plant(monkeypatch, fault):
    from repro.dsl import exec as dsl_exec
    real = dsl_exec._lowered_executor

    def lowered_executor(*a, **k):
        fn = real(*a, **k)
        last = {}

        def run(image, *rest):
            out = fn(image, *rest)
            bad = fault(out, last.get("out", out))
            last["out"] = out
            return bad

        run.lowered = fn.lowered
        return run

    monkeypatch.setattr(dsl_exec, "_lowered_executor", lowered_executor)


def _run(root, spec, cell, seed=2**31 + 3, seconds=1.0):
    c = harness.Cell(spec, cell, root)
    return harness.run_cell(c, seed, seconds, False, time.perf_counter(),
                            log=lambda m: None)


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_spec(
    ROOT)["workloads"]])
def test_sound_run_is_correct(tiny_bench, cell):
    root, spec = tiny_bench
    res = _run(root, spec, cell)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert res["check"]["frames_compared"]["value"] >= 1
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert res["device"]["platform"] == "cpu"


def _faults():
    """(cell, fault) for every cell: an altered answer and a stale state
    everywhere, half a batch left out where the closed loop fills every
    batch (an open loop at the tiny size serves one frame per padded
    batch, where a lost half batch is only padding)."""
    spec = harness.load_spec(ROOT)
    out = []
    for w in spec["workloads"]:
        cell = harness.Cell(spec, w["name"], ROOT)
        faults = [_altered, _stale]
        if cell.traffic["loop"] == "closed":
            faults.insert(1, _half_batch)
        out += [pytest.param(w["name"], f, id=f"{w['name']}-{f.__name__[1:]}")
                for f in faults]
    return out


@pytest.mark.parametrize("cell,fault", _faults())
def test_planted_fault_is_not_correct(tiny_bench, monkeypatch, fault, cell):
    """Each fault a one-chip pipeline server can have (a cross-chip
    exchange does not exist here) turns `correct` false."""
    root, spec = tiny_bench
    _plant(monkeypatch, fault)
    res = _run(root, spec, cell)
    assert res["correct"] is False
    assert res["check"]["mismatched_px"]["value"] > 0
