"""batcher_wait_ms_per_frame (serving layer, `serve/pipeline_server.py`):
the batcher thread's wait for requests per frame, from the
``serve.collect`` spans (blocking on an empty queue, then filling the
batch up to its timeout; `bench.spans.ms_per_frame`).  Reads the
program's `repro.obs` spans (traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    return ms_per_frame(run, "serve.collect")
