"""A later change adds a configuration, a traffic mix and a per-layer
metric by adding files and entries alone: no file of the harness is
edited, and the harness finds and runs them by name."""
from __future__ import annotations

import json
import os
import time

from bench import harness

REF = '''
import numpy as np
from bench.refs.fixed import snap, stencil

def run(frame, types, beta, params):
    out = {}
    def q(n, x):
        out[n] = snap(x, types[n], beta)
        return out[n]
    img = q("img", np.asarray(frame, dtype=np.float64))
    dx = q("Dx", stencil(img, [[1, 2, 1]], 1 / 4, stride=(1, 2)))
    dy = q("Dy", stencil(dx, [[1], [2], [1]], 1 / 4, stride=(2, 1)))
    ux = q("Ux", stencil(dy, [[1, 2, 1]], 1 / 4, expand=(1, 2)))
    q("Uy", stencil(ux, [[1], [2], [1]], 1 / 4, expand=(2, 1)))
    return out
'''

METRIC = '''
def read(run):
    return float(len(run.done_in_window()))
'''


def _write(root, rel, text):
    path = os.path.join(root, rel)
    with open(path, "w") as f:
        f.write(text)


def test_new_config_traffic_and_metric_from_files_alone(tiny_bench):
    root, spec = tiny_bench
    before = {rel: open(os.path.join(root, "bench", rel)).read()
              for rel in ("harness.py", "traffic.py", "check.py", "run.py")}
    config = {
        "pipeline": "repro.pipelines.dus:build", "params": {},
        "frame": [64, 128], "plan": {"passes": ["interval"], "beta": 4},
        "batch_size": 2, "backends": ["lowered"],
        "reference": "bench/refs/dus_tiny.py",
        "types": {n: "u8" for n in ("img", "Dx", "Dy", "Ux", "Uy")},
        "outputs": {"Uy": [64, 128]},
        "source": "arXiv:1803.02660 Sec. VI-C", "assumed": {}, "reduced": []}
    _write(root, "bench/configs/dus-tiny.json", json.dumps(config))
    assert not os.path.exists(os.path.join(root, "bench/refs/dus_tiny.py"))
    _write(root, "bench/refs/dus_tiny.py", REF)
    _write(root, "bench/traffic/one_batch.json",
           json.dumps({"loop": "closed", "in_flight_batches": 1}))
    _write(root, "bench/metrics/frames_done.py", METRIC)
    spec["configs"].append({"name": "dus-tiny", "source": "arXiv:1803.02660",
                            "file": "bench/configs/dus-tiny.json",
                            "reduced": [], "why": "paper DUS chain"})
    spec["workloads"].append({"name": "dus-tiny.one_batch",
                              "config": "dus-tiny", "traffic": "one_batch",
                              "chips": 1, "why": "one batch in flight"})
    for m in spec["end_to_end"]:
        if m["name"] == "frames_per_s":
            m["workloads"].append("dus-tiny.one_batch")
    spec["per_layer"].append({"name": "frames_done", "unit": "frames",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving", "moves": "frames_per_s",
                              "workloads": ["dus-tiny.one_batch"]})
    _write(root, "BENCHMARK.json", json.dumps(spec))

    spec = harness.load_spec(root)
    cell = harness.Cell(spec, "dus-tiny.one_batch", root)
    assert cell.config["reference"] == "bench/refs/dus_tiny.py"
    assert cell.traffic["in_flight_batches"] == 1
    assert [m["name"] for m in cell.metrics(traced=True)] == ["frames_done"]
    res = harness.run_cell(cell, 17, 1.0, False, time.perf_counter(),
                           log=lambda m: None)
    assert res["correct"] is True, res["check"]
    assert set(res["metrics"]) == {"setup_s", "frames_per_s"}
    res = harness.run_cell(cell, 18, 1.0, True, time.perf_counter(),
                           log=lambda m: None)
    assert res["correct"] is True, res["check"]
    assert res["metrics"]["frames_done"]["value"] > 0
    for rel, text in before.items():
        assert open(os.path.join(root, "bench", rel)).read() == text
