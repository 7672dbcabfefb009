"""device_idle_pct (device): 100 x (1 - the union of the device's
operation intervals in the window / the window), from the device trace
(traced runs).  Silent when the trace holds no device operation."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
