"""From a JAX profiler trace to device busy time, idle gaps and ops.

`load` reads the ``.xplane.pb`` that `jax.profiler.start_trace` wrote
(`jax.profiler.ProfileData`).  The device's operations are the events
on the ``"XLA Ops"`` line of each ``/device:`` plane.  `summarize`
clips them to the measured window — located by the harness's
``bench.window`` host annotation in the same trace — and reduces them
to:

  * ``busy_s``: the union of the operations' intervals, averaged over
    the devices that ran any;
  * ``window_s``: the window's length;
  * ``device_ops``: the operations that took most time, by name;
  * ``idle_gaps``: the device's idle time in the window, by what the
    host was doing.  Each instant of a gap goes to the innermost host
    interval that covers it, taken first from the profiler's Python
    events of the program's own functions (``"dequant_host
    (backends.py)"``; the file names are the program's, passed in),
    then from the program's `repro.obs` spans (put on the trace's clock
    through the window's two marks), then from the harness's
    annotations (``bench.submit``, ``bench.result``, ``bench.sleep``);
    an instant none covers is ``"host (no span)"``.

Device operations are named by the HLO instruction their event names
start with (``fused_band_island.1`` for the fused kernel).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OP_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_MARKS = ("bench.submit", "bench.result", "bench.sleep")
TOP = 10

Interval = Tuple[float, float]          # (start_ns, end_ns)
# a Python function event of the profiler: "$backends.py:281 dequant_host"
_PY_EVENT = re.compile(r"^\$([\w.-]+\.py):\d+ (\S+)$")


def load(log_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


def op_name(event_name: str) -> str:
    """``"%copy.8 = u16[...] copy(...)"`` -> ``"copy.8"``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(pd) -> Dict[str, List[Tuple[float, float, str]]]:
    """{device plane: [(start_ns, end_ns, op name)]} from the op lines."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        evs = [(e.start_ns, e.end_ns, op_name(e.name))
               for line in plane.lines if line.name == OP_LINE
               for e in line.events]
        if evs:
            out[plane.name] = evs
    return out


def host_events(pd, names: Iterable[str]) -> List[Tuple[float, float, str]]:
    names = set(names)
    return [(e.start_ns, e.end_ns, e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in names]


def program_functions(pd, files: Iterable[str]
                      ) -> List[Tuple[float, float, str]]:
    """Python function events of the given source files (basenames)."""
    files = set(files)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                m = _PY_EVENT.match(e.name)
                if m and m.group(1) in files:
                    out.append((e.start_ns, e.end_ns,
                                f"{m.group(2)} ({m.group(1)})"))
    return out


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gaps: Sequence[Interval],
              layers: Sequence[Sequence[Tuple[float, float, str]]]
              ) -> Dict[str, float]:
    """Seconds of the sorted disjoint ``gaps`` by host activity: each
    instant goes to the innermost interval of the first of ``layers``
    that covers it (see the module docstring)."""
    cands = sorted((s, e, name, rank) for rank, layer in enumerate(layers)
                   for s, e, name in layer)
    out: Dict[str, float] = defaultdict(float)
    active: list = []
    i = 0
    for g0, g1 in gaps:
        while i < len(cands) and cands[i][0] < g1:
            active.append(cands[i])
            i += 1
        active = [c for c in active if c[1] > g0]
        cuts = sorted({g0, g1} | {t for c in active for t in c[:2]
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            here = [c for c in active if c[0] <= a and c[1] >= b]
            best = min(here, key=lambda c: (c[3], c[1] - c[0]),
                       default=None)
            out[best[2] if best else "host (no span)"] += (b - a) * 1e-9
    return out


def top(items: Iterable[Tuple[str, float]], n: int = TOP
        ) -> List[List]:
    acc: Dict[str, float] = defaultdict(float)
    for name, v in items:
        acc[name] += v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


class Summary:
    def __init__(self, busy_s: float, window_s: float,
                 ops: List[List], idle: List[List]):
        self.busy_s = busy_s
        self.window_s = window_s
        self.device_ops = ops
        self.idle_gaps = idle

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def summarize(pd, spans: Optional[list] = None,
              window_marks: Tuple[float, float] = (0.0, 0.0),
              program_files: Iterable[str] = ()) -> Summary:
    """Reduce a trace to the window's busy time, top ops and idle gaps.

    ``spans`` are `repro.obs` spans (``name``, ``t0``, ``t1`` on
    `time.perf_counter`); ``window_marks`` the perf_counter times at
    which the window opened and closed; ``program_files`` the basenames
    of the program's source files."""
    windows = host_events(pd, [WINDOW])
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0_ns = windows[0][0]
    pc0, pc1 = window_marks
    w1_ns = w0_ns + (pc1 - pc0) * 1e9 if pc1 > pc0 else windows[0][1]
    per_device = device_ops(pd)
    busy_ns, all_gaps, op_time = [], [], []
    for evs in per_device.values():
        busy = merge(((s, e) for s, e, _ in evs), w0_ns, w1_ns)
        busy_ns.append(sum(e - s for s, e in busy))
        all_gaps.extend(gaps(busy, w0_ns, w1_ns))
        op_time.extend((name, _overlap((s, e), (w0_ns, w1_ns)) * 1e-9)
                       for s, e, name in evs)
    prog = [(w0_ns + (s.t0 - pc0) * 1e9, w0_ns + (s.t1 - pc0) * 1e9, s.name)
            for s in (spans or [])]
    idle = attribute(sorted(all_gaps),
                     [program_functions(pd, program_files), prog,
                      host_events(pd, HOST_MARKS)])
    n_dev = max(len(per_device), 1)
    return Summary(busy_s=sum(busy_ns) * 1e-9 / n_dev,
                   window_s=(w1_ns - w0_ns) * 1e-9,
                   ops=top((k, v) for k, v in op_time if v > 0),
                   idle=top(idle.items()))
