"""d2h_ms_per_frame (executor layer, `lowering/backends.py::run_on_device`):
the device-to-host copies of a served batch's outputs per frame, from
the ``exec.d2h`` spans (one `np.asarray` per output, after the device
wait; `bench.spans.ms_per_frame`).  Reads the program's `repro.obs`
spans (traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    return ms_per_frame(run, "exec.d2h")
