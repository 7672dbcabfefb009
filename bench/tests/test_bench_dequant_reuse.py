"""The `dequant_reuse_pct` reader on synthetic ``exec.dequant`` spans."""
from __future__ import annotations

import os
import types

import pytest

from bench import harness
from bench.harness import ROOT


def _read(run):
    return harness.load_file(os.path.join(
        ROOT, "bench", "metrics", "dequant_reuse_pct.py")).read(run)


def _span(name, t0, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t0 + 0.1, attrs=attrs)


def _run(spans):
    return types.SimpleNamespace(spans=spans, t0=10.0, t1=20.0)


def test_silent_when_untraced():
    assert _read(_run(None)) is None


def test_share_of_reused_buffers_over_the_window():
    spans = [_span("exec.dequant", 11.0, reused=0, fresh=5),
             _span("exec.dequant", 12.0, reused=5, fresh=0),
             _span("exec.dequant", 19.9, reused=4, fresh=1),
             _span("exec.d2h", 12.0, reused=7, fresh=0),
             _span("serve.batch", 12.0, size=4)]
    assert _read(_run(spans)) == pytest.approx(100.0 * 9 / 15)


def test_counts_only_spans_started_in_the_window():
    spans = [_span("exec.dequant", 9.99, reused=0, fresh=12),
             _span("exec.dequant", 10.0, reused=3, fresh=1),
             _span("exec.dequant", 20.0, reused=1, fresh=0),
             _span("exec.dequant", 20.01, reused=0, fresh=12)]
    assert _read(_run(spans)) == pytest.approx(80.0)


def test_silent_without_the_attributes_or_the_spans():
    # an executor that widens into fresh arrays and counts nothing
    assert _read(_run([_span("exec.dequant", 11.0),
                       _span("serve.batch", 11.0, size=4)])) is None
    assert _read(_run([_span("serve.batch", 11.0, size=4)])) is None
    assert _read(_run([_span("exec.dequant", 5.0, reused=1,
                             fresh=0)])) is None
