"""The trace reduction (`bench.trace`) on a small synthetic xplane."""
from __future__ import annotations

import pytest

from bench import trace as T


def _xspace(device_events, host_events):
    """Text proto of an XSpace: events as (name, start_ns, dur_ns)."""
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}\n')
    return (plane(1, "/device:TPU:0", "XLA Ops", device_events)
            + plane(2, "/host:CPU", "python", host_events))


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    device = [("%fused_band_island = (s16[4]) custom-call(u16[4] %p)", 100,
               50),                              # inside the window
              ("%copy.1 = s16[4] copy(s16[4] %q)", 140, 30),   # overlaps
              ("fused_band_island", 400, 100),
              ("early", 0, 50),                  # before the window
              ("late", 950, 100)]                # runs past the window
    host = [("bench.window", 100, 900), ("bench.result", 150, 300),
            ("bench.sleep", 600, 200),
            ("$backends.py:281 dequant_host", 520, 50),
            ("$traffic.py:83 measure", 100, 900)]
    return ProfileData.from_text_proto(_xspace(device, host))


def test_merge_unions_and_clips():
    assert T.merge([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25) == \
        [(1, 3), (5, 12), (20, 25)]


def test_gaps_fill_the_rest_of_the_window():
    assert T.gaps([(1, 3), (5, 12)], 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert T.gaps([], 0, 4) == [(0, 4)]


def test_summary_busy_idle_and_top_ops(profile):
    # window [100, 1000) ns: busy = [100, 170) + [400, 500) + [950, 1000)
    s = T.summarize(profile)
    assert s.window_s == pytest.approx(900e-9)
    assert s.busy_s == pytest.approx(220e-9)
    ops = dict(s.device_ops)
    assert ops["fused_band_island"] == pytest.approx(150e-9)
    assert ops["copy.1"] == pytest.approx(30e-9)
    assert ops["late"] == pytest.approx(50e-9)
    assert "early" not in ops
    assert s.device_ops[0][0] == "fused_band_island"
    idle = dict(s.idle_gaps)
    # gaps: [170, 400) in bench.result; [500, 950): bench.sleep covers
    # [600, 800), nothing [500, 600) and [800, 950).  No program files
    # given, so the Python events name nothing.
    assert idle == pytest.approx({"bench.result": 230e-9,
                                  "bench.sleep": 200e-9,
                                  "host (no span)": 250e-9})
    assert sum(idle.values()) + s.busy_s == pytest.approx(s.window_s)


def test_program_functions_name_the_gaps_first(profile):
    s = T.summarize(profile, program_files={"backends.py"})
    idle = dict(s.idle_gaps)
    assert idle == pytest.approx({"bench.result": 230e-9,
                                  "dequant_host (backends.py)": 50e-9,
                                  "bench.sleep": 200e-9,
                                  "host (no span)": 200e-9})


def test_program_spans_name_the_gaps_they_cover(profile):
    class Span:
        def __init__(self, name, t0, t1):
            self.name, self.t0, self.t1 = name, t0, t1

    # perf_counter marks: window opens at 10.0 s; spans 1 ns = 1e-9 s
    spans = [Span("serve.batch", 10.0 + 60e-9, 10.0 + 320e-9),
             Span("exec.pallas", 10.0 + 65e-9, 10.0 + 310e-9)]
    s = T.summarize(profile, spans, (10.0, 10.0 + 900e-9))
    idle = dict(s.idle_gaps)
    # [170, 400) ns lies inside both spans ([160, 420) and [165, 410)):
    # the innermost one names it, ahead of the harness's bench.result
    assert idle["exec.pallas"] == pytest.approx(230e-9)
    assert "serve.batch" not in idle and "bench.result" not in idle


def test_summary_needs_the_window_annotation():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(_xspace([("op", 0, 5)],
                                             [("other", 0, 10)]))
    with pytest.raises(ValueError, match="bench.window"):
        T.summarize(pd)
