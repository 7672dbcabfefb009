"""latency_p50_ms: median over every frame due in the window of its due
time to its result (host clock), frames finished after the window
included.  Lost or failed frames make the run incorrect instead."""
import numpy as np


def read(run):
    lat = [(f.t_done - f.due) * 1e3 for f in run.due_in_window()
           if not f.failed and f.t_done is not None]
    return float(np.percentile(lat, 50)) if lat else None
