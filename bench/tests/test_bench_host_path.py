"""The host-path readers (`bench/metrics/*_ms_per_frame.py` over
`bench.spans`) on synthetic spans, and in a whole traced CPU run."""
from __future__ import annotations

import os
import time

import pytest

from bench import harness
from bench.harness import ROOT

READERS = {                     # metric -> the span it sums
    "h2d_ms_per_frame": "exec.h2d",
    "device_wait_ms_per_frame": "exec.device_wait",
    "d2h_ms_per_frame": "exec.d2h",
    "dequant_ms_per_frame": "exec.dequant",
    "submit_ms_per_frame": "serve.submit",
    "batcher_wait_ms_per_frame": "serve.collect",
}


class _Span:
    def __init__(self, name, t0, t1, **attrs):
        self.name, self.t0, self.t1, self.attrs = name, t0, t1, attrs


class _Run:
    def __init__(self, spans, t0=10.0, t1=20.0):
        self.spans, self.t0, self.t1 = spans, t0, t1


def _read(metric, run):
    return harness.load_file(os.path.join(
        ROOT, "bench", "metrics", metric + ".py")).read(run)


def _batches():
    # 6 real frames start in the window; the batch at 9.5 and the one at
    # 20.5 started outside it and do not count
    return [_Span("serve.batch", 9.5, 10.5, size=4),
            _Span("serve.batch", 11.0, 12.0, size=4),
            _Span("serve.batch", 15.0, 16.0, size=2),
            _Span("serve.batch", 20.5, 21.0, size=4)]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_spans_started_in_the_window(metric):
    name = READERS[metric]
    spans = _batches() + [
        _Span(name, 9.9, 10.2),          # started before the window
        _Span(name, 11.0, 11.003),
        _Span(name, 15.0, 15.009),
        _Span(name, 19.99, 20.5),        # started in it: counts whole
        _Span(name, 20.6, 20.7),         # after it
        _Span("exec.other", 11.0, 12.0),
    ]
    want = 1e3 * (0.003 + 0.009 + 0.51) / 6
    assert _read(metric, _Run(spans)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_silent_without_its_spans(metric):
    assert _read(metric, _Run(None)) is None                 # untraced
    # a program without the host-path spans (only the executor's whole
    # span, as before they existed) reads nothing and does not raise
    older = _batches() + [_Span("exec.pallas", 11.0, 11.5)]
    assert _read(metric, _Run(older)) is None
    # spans but no served batch in the window
    only = [_Span(READERS[metric], 11.0, 11.1)]
    assert _read(metric, _Run(only)) is None


def test_dequant_reads_zero_where_the_device_dequantizes():
    spans = _batches() + [_Span("exec.d2h", 11.0, 11.2)]
    assert _read("dequant_ms_per_frame", _Run(spans)) == 0.0


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_spec(
    ROOT)["workloads"]])
def test_traced_run_reports_the_host_path(tiny_bench, cell):
    root, spec = tiny_bench
    c = harness.Cell(spec, cell, root)
    assert set(READERS) <= {m["name"] for m in c.metrics(traced=True)}
    res = harness.run_cell(c, 2**31 + 11, 1.0, True, time.perf_counter(),
                           log=lambda m: None)
    assert res["correct"] is True, res["check"]
    got = res["metrics"]
    assert set(READERS) <= set(got)
    for metric in READERS:
        assert got[metric]["unit"] == "ms" and got[metric]["value"] >= 0
    # the tiny copies serve through lowered, which dequantizes on device
    assert got["dequant_ms_per_frame"]["value"] == 0.0
    steps = sum(got[m]["value"] for m in ("h2d_ms_per_frame",
                                          "device_wait_ms_per_frame",
                                          "d2h_ms_per_frame"))
    assert 0 < steps <= got["exec_ms_per_frame"]["value"]
