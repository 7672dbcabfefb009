"""queue_wait_ms (serving layer, `serve/pipeline_server.py`): mean over
the window's frames of the start of the ``serve.batch`` span that served
the frame minus the frame's due time.  The batcher takes frames in FIFO
order, so frames map onto the batches in span order by each span's
``size``.  Reads the program's `repro.obs` spans (traced runs)."""


def read(run):
    if run.spans is None:
        return None
    batches = [s for s in run.spans if s.name == "serve.batch"]
    frames = iter(run.frames)
    waits = []
    for b in batches:
        for f in (next(frames, None) for _ in range(b.attrs["size"])):
            if f is not None and run.t0 <= f.due < run.t1:
                waits.append(b.t0 - f.due)
    return 1e3 * sum(waits) / len(waits) if waits else None
