"""Per-frame time of one `repro.obs` span, as the ``program_span``
readers of the executor and serving layers take it.

The denominator is `exec_ms_per_frame`'s: the real frames (``size``) of
the ``serve.batch`` spans that started in the window.  The numerator is
the summed durations of the named spans that started in it, on whatever
thread they ran.  None when the run is untraced, served no batch in the
window, or the program emits no such span."""
from __future__ import annotations

from typing import Optional


def ms_per_frame(run, name: str) -> Optional[float]:
    if run.spans is None:
        return None
    inside = [s for s in run.spans if run.t0 <= s.t0 <= run.t1]
    frames = sum(s.attrs["size"] for s in inside if s.name == "serve.batch")
    spans = [s for s in inside if s.name == name]
    if not frames or not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / frames
