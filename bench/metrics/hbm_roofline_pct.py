"""hbm_roofline_pct (device program, fused kernel or XLA program): the
least time the chip's HBM needs for the window's frames over the
device's busy time in the window, in percent:

    (bytes_per_frame / peak HBM bytes/s) / (busy_s / frames)

``bytes_per_frame`` comes from the configuration alone (`bench.work`),
the peak from `bench/peaks.json` by device kind, busy time from the
device trace, frames from those whose results returned in the window.
It is bounded by HBM bytes (the pipelines do a few integer operations
per byte).  Silent when the trace holds no device operation."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    frames = len(run.done_in_window())
    if not frames:
        return None
    floor_s = run.work_bytes_per_frame() / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * floor_s / (run.trace.busy_s / frames)
