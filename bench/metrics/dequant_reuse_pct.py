"""dequant_reuse_pct (executor layer,
`lowering/backends.py::run_on_device`): the share of the f64 output
buffers the host widening took from the executor's recycled pool
(`HostBuffers`) instead of allocating, 100 × Σ``reused`` ÷
Σ(``reused`` + ``fresh``) over the ``exec.dequant`` spans that started
in the window.  A fresh buffer costs a page fault on each of its pages,
so the share moves ``dequant_ms_per_frame``.  Reads the program's
`repro.obs` spans (traced runs); None when untraced, when no such span
started in the window, or when the program sets no such attributes."""


def read(run):
    if run.spans is None:
        return None
    spans = [s for s in run.spans
             if s.name == "exec.dequant" and run.t0 <= s.t0 <= run.t1
             and "reused" in s.attrs]
    reused = sum(s.attrs["reused"] for s in spans)
    total = reused + sum(s.attrs["fresh"] for s in spans)
    return 100.0 * reused / total if total else None
