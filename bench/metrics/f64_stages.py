"""f64_stages (device program, `lowering/ir.py` elections): the number
of stages of the served device program that evaluate in f64, the
largest ``f64_stages`` attribute of the executor spans (``exec.pallas``,
``exec.lowered``, ``exec.sharded``) that started in the window.  The
chip emulates f64 and does not round it as IEEE-754 does, so each such
stage costs device time and may cost exactness.  Reads the program's
`repro.obs` spans (traced runs); None when untraced or when the program
sets no such attribute."""

EXEC_SPANS = ("exec.pallas", "exec.lowered", "exec.sharded")


def read(run):
    if run.spans is None:
        return None
    seen = [s.attrs["f64_stages"] for s in run.spans
            if s.name in EXEC_SPANS and run.t0 <= s.t0 <= run.t1
            and "f64_stages" in s.attrs]
    return max(seen) if seen else None
