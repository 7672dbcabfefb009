"""submit_ms_per_frame (serving layer, `serve/pipeline_server.py`): the
caller's side of `PipelineServer.submit` per frame, from the
``serve.submit`` spans (the submit-side quantize into the input
container and the enqueue, on the client's thread;
`bench.spans.ms_per_frame`).  Reads the program's `repro.obs` spans
(traced runs)."""
from bench.spans import ms_per_frame


def read(run):
    return ms_per_frame(run, "serve.submit")
