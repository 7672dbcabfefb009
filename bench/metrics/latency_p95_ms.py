"""latency_p95_ms: 95th percentile (numpy's linear interpolation) of the
same latencies as latency_p50_ms."""
import numpy as np


def read(run):
    lat = [(f.t_done - f.due) * 1e3 for f in run.due_in_window()
           if not f.failed and f.t_done is not None]
    return float(np.percentile(lat, 95)) if lat else None
