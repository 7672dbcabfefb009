"""h2d_ms_per_frame (executor layer, `lowering/backends.py::run_on_device`):
the host-to-device step of a served batch per frame, from the
``exec.h2d`` spans: host ingest of the inputs into their stored
containers and the `jnp.asarray` copies to the device, up to their
landing there (`bench.spans.ms_per_frame`).  Reads the program's
`repro.obs` spans (traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    return ms_per_frame(run, "exec.h2d")
