"""exec_ms_per_frame (executor layer, `lowering/pallas_backend.py`,
`lowering/backends.py`): the summed durations of the executor spans
(``exec.pallas``, ``exec.lowered``, ``exec.sharded``) that started in
the window, over the real frames of the ``serve.batch`` spans that
started in it.  Reads the program's `repro.obs` spans (traced runs)."""

EXEC_SPANS = ("exec.pallas", "exec.lowered", "exec.sharded")


def read(run):
    if run.spans is None:
        return None
    inside = [s for s in run.spans if run.t0 <= s.t0 <= run.t1]
    busy = sum(s.t1 - s.t0 for s in inside if s.name in EXEC_SPANS)
    frames = sum(s.attrs["size"] for s in inside if s.name == "serve.batch")
    if not frames or not busy:
        return None
    return 1e3 * busy / frames
