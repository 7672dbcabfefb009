"""The one traffic generator; each mix is a data file it reads.

``bench/traffic/<mix>.json`` holds the mix's parameters:

  * ``"loop": "closed"`` — a batch job over a photo library: one client
    keeps ``in_flight_batches`` x batch size frames submitted and sends
    the next frame as each result returns.
  * ``"loop": "open"`` — ``streams`` cameras, together sending
    ``rate_fps`` frames per second: each stream sends one frame every
    ``streams / rate_fps`` seconds whatever the server does.  Each
    stream's phase is drawn from the seed, uniform over one period, as
    independent cameras start when they start.

Both loops first push ``warm_batches`` batches of real frames through
the server, untimed (set-up).  A frame's latency runs from its due time
(its submission in the closed loop) to its result; ``Frame.t_submit -
Frame.due`` is how late the generator ran.  All times are
`time.perf_counter` seconds.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import contextlib
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

# seconds the drain may run past the window before a frame counts as lost
DRAIN_S = 60.0
# the open loop's first arrival is at least this far after the window opens
_LEAD_S = 0.01


class Frame:
    __slots__ = ("idx", "pool_idx", "due", "t_submit", "t_done", "failed",
                 "done_by_close", "future")

    def __init__(self, idx: int, pool_idx: int, due: float):
        self.idx = idx
        self.pool_idx = pool_idx
        self.due = due
        self.t_submit: Optional[float] = None
        self.t_done: Optional[float] = None
        self.failed = False
        self.done_by_close = False      # served when the window closed
        self.future: Optional[cf.Future] = None


def _annotation(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Generator:
    """Drives one server with one mix; see the module docstring."""

    def __init__(self, mix: dict, batch_size: int, seed: int, pool_size: int):
        self.mix = mix
        self.batch_size = batch_size
        self.pool_size = pool_size
        self.rng = np.random.default_rng([seed, 1])
        self.annotate = False
        # (perf_counter at window open, at window close): the trace
        # reduction maps the window's host annotation onto these
        self.window_marks: Tuple[float, float] = (0.0, 0.0)

    # -- set-up -----------------------------------------------------------

    def warm(self, srv, pool: np.ndarray) -> None:
        n = self.mix.get("warm_batches", 2) * self.batch_size
        futs = [srv.submit(pool[i % len(pool)]) for i in range(n)]
        for f in futs:
            f.result()

    # -- the window -------------------------------------------------------

    def measure(self, srv, pool: np.ndarray, seconds: float,
                offer: Callable, annotate: bool = False
                ) -> Tuple[float, float, List[Frame]]:
        """Run the window; returns (t0, t1, frames in submission order).

        The open loop's window is ``seconds`` long.  The closed loop
        stops submitting at ``seconds`` and closes the window when the
        batch running then returns, so that the window holds whole
        batches.  ``offer(frame, outputs)`` receives each completed
        frame's served outputs, in submission order, and may keep them
        for the check.
        """
        self.annotate = annotate
        loop = {"closed": self._closed, "open": self._open}[self.mix["loop"]]
        frames: List[Frame] = []
        pending: collections.deque = collections.deque()
        t0 = time.perf_counter()
        with _annotation(annotate, "bench.window"):
            loop(srv, pool, t0, t0 + seconds, frames, pending, offer)
            delay = t0 + seconds - time.perf_counter()
            if delay > 0:
                with _annotation(annotate, "bench.sleep"):
                    time.sleep(delay)
            t1 = time.perf_counter()
        for f in frames:
            f.done_by_close = (not f.failed) if f.future is None else (
                f.future.done() and f.future.exception() is None)
        self.window_marks = (t0, t1)
        self._drain(pending, offer, t1 + DRAIN_S)
        return t0, t1, frames

    def _closed(self, srv, pool, t0, t1, frames, pending, offer):
        in_flight = self.mix["in_flight_batches"] * self.batch_size
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            while len(pending) < in_flight:
                f = self._submit(srv, pool, len(frames), time.perf_counter())
                frames.append(f)
                pending.append(f)
            with _annotation(self.annotate, "bench.result"):
                cf.wait([pending[0].future], timeout=t1 - now)
            self._reap(pending, offer)
        # the batch running at t1 holds the oldest pending frames
        with _annotation(self.annotate, "bench.result"):
            cf.wait([f.future for f in list(pending)[:self.batch_size]],
                    timeout=DRAIN_S)

    def _open(self, srv, pool, t0, t1, frames, pending, offer):
        for due in self.schedule(t0, t1):
            self._reap(pending, offer)
            delay = due - time.perf_counter()
            if delay > 0:
                with _annotation(self.annotate, "bench.sleep"):
                    time.sleep(delay)
            f = self._submit(srv, pool, len(frames), due)
            frames.append(f)
            pending.append(f)

    def schedule(self, t0: float, t1: float) -> List[float]:
        """Due times of the open loop's frames in [t0, t1), sorted."""
        streams = self.mix["streams"]
        period = streams / self.mix["rate_fps"]
        due = []
        for phase in self.rng.random(streams) * period:
            t = t0 + _LEAD_S + phase
            while t < t1:
                due.append(t)
                t += period
        return sorted(due)

    # -- plumbing ---------------------------------------------------------

    def _submit(self, srv, pool, idx: int, due: float) -> Frame:
        f = Frame(idx, idx % self.pool_size, due)
        f.t_submit = time.perf_counter()
        with _annotation(self.annotate, "bench.submit"):
            fut = srv.submit(pool[f.pool_idx])
        fut.add_done_callback(
            lambda _, f=f: setattr(f, "t_done", time.perf_counter()))
        f.future = fut
        return f

    @staticmethod
    def _settle(f: Frame, offer: Callable) -> None:
        if f.future.exception() is not None:
            f.failed = True
        else:
            offer(f, f.future.result())
        f.future = None

    def _reap(self, pending: collections.deque, offer: Callable) -> None:
        while pending and pending[0].future.done():
            self._settle(pending.popleft(), offer)

    def _drain(self, pending: collections.deque, offer: Callable,
               deadline: float) -> None:
        while pending:
            f = pending.popleft()
            cf.wait([f.future], timeout=max(deadline - time.perf_counter(),
                                            0.0))
            if f.future.done():
                self._settle(f, offer)
            else:                       # never came: lost, not late
                f.failed = True
                f.t_done = None
                f.future = None

    def lateness_ms(self, frames: List[Frame], t0: float, t1: float
                    ) -> Tuple[float, float]:
        """(median, max) of submit - due over the window's frames, ms."""
        late = [(f.t_submit - f.due) * 1e3 for f in frames
                if t0 <= f.due < t1 and f.t_submit is not None]
        if not late:
            return 0.0, 0.0
        return float(np.median(late)), float(max(late))
