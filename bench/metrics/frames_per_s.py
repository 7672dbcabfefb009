"""frames_per_s: frames whose results returned inside the window, over
the window's seconds (host clock)."""


def read(run):
    return len(run.done_in_window()) / (run.t1 - run.t0)
