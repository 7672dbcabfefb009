"""dequant_ms_per_frame (executor layer,
`lowering/backends.py::run_on_device`): the host's widening of fetched
output containers to f64 per frame, from the ``exec.dequant`` spans
(`bench.spans.ms_per_frame`).  0.0 where the executor's host-path spans
exist (``exec.d2h``) but this one does not: the lowered program
dequantizes on the device.  Reads the program's `repro.obs` spans
(traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    ms = ms_per_frame(run, "exec.dequant")
    if ms is None and ms_per_frame(run, "exec.d2h") is not None:
        return 0.0
    return ms
