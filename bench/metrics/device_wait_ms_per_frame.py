"""device_wait_ms_per_frame (executor layer,
`lowering/backends.py::run_on_device`): the host's wait for a served
batch's outputs per frame, from the ``exec.device_wait`` spans
(`jax.block_until_ready` after the device program was enqueued on
inputs already on the device; `bench.spans.ms_per_frame`).  Reads the program's `repro.obs` spans
(traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    return ms_per_frame(run, "exec.device_wait")
