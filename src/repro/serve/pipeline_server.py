"""Continuous batched pipeline inference — the serving harness.

The execution backends (`repro.lowering`) compile a pipeline + bitwidth
plan into shape-specialized executors that accept a leading batch
dimension; this module puts a *server* in front of one: requests enter a
queue, a background thread packs them into fixed-size batches, and every
batch runs through ONE warmup-compiled batched program — the shape of
maxtext's ``OfflineInference`` (PAPERS.md / SNIPPETS.md), adapted from
token decode to image pipelines.

Design points (docs/serving.md):

  * **fixed batch shape** — partial batches are padded with zero frames
    up to ``batch_size``, so exactly one batched program per
    (pipeline, plan, batch shape, backend, datapath) ever compiles; pad
    frames are dropped before results are delivered.  Padding is pure
    overhead, never a semantics change: the batched programs are
    bit-for-bit per-frame independent (tests/test_serving.py).
  * **warmup** — `warmup(shapes)` drives zero batches through the
    executor so jit/pallas compilation happens before traffic; serving a
    cold shape still works, it just pays the compile on the first batch.
  * **compile caching** — the executor comes from the process-wide
    content-keyed memo (`dsl.exec`), which compiles under its lock:
    concurrent servers (or threads inside one) racing on the same key
    produce exactly one compile.
  * **drain** — `close()` serves every queued request (final partial
    batch padded), then joins the worker; `submit` after close raises.

Telemetry (`repro.obs`, docs/observability.md): `serve.submit` on the
caller's thread (the submit-side quantize and enqueue); on the batcher
thread `serve.collect` (waiting for the queue to fill a batch) and
`serve.batch` per served batch, with children `serve.stack` (stacking
and padding), the executor's `exec.*` spans and `serve.deliver`
(resolving the futures).  While tracing is on, `serve.batch` carries its
requests' submit times (`t_submit`, on the tracer's
`time.perf_counter` clock).  The process-wide `SERVE_STATS` counter
group tracks frames / batches / padded frames.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.dsl import exec as _exec

__all__ = ["PipelineServer", "SERVE_STATS", "serve_offline"]

SERVE_STATS = obs.CounterGroup("serve.pipeline_server",
                               frames=0, batches=0, padded=0)

_SENTINEL = object()


class _Request:
    __slots__ = ("images", "future", "t_submit")

    def __init__(self, images: List[np.ndarray]):
        self.images = images
        self.future: Future = Future()
        self.t_submit = time.perf_counter()     # the tracer's clock


class PipelineServer:
    """Batched serving front-end over one compiled pipeline executor.

    ``backend`` is a `run_fixed` lowered backend name — ``"lowered"``
    (fused jnp + vmap), ``"pallas"`` (fused line-buffer kernels, batch
    as the outer grid axis) or ``"sharded"`` (band-sharded shard_map
    program).  Usable as a context manager; `close()` drains.

    ``batch_timeout_s`` bounds how long the batcher holds a partial
    batch open waiting for more requests (the classic throughput vs
    tail-latency knob); 0 serves whatever is immediately queued.

    A result's arrays are views of its batch's f64 buffers, which the
    executor recycles once no result of the batch (nor any view of one)
    is alive; a held result is never overwritten.  Holding one makes the
    executor widen a later batch into fresh buffers, which costs a page
    fault per page (about 16 ms a 1080p map a frame on a TPU v5e host):
    copy what you keep and drop the result.
    """

    def __init__(self, pipeline, types, params: Optional[dict] = None,
                 *, backend: str = "lowered", batch_size: int = 4,
                 column: Optional[str] = None, datapath: str = "exact",
                 batch_timeout_s: float = 0.002,
                 max_queue: int = 4096):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.pipeline = pipeline
        self.batch_size = int(batch_size)
        self.batch_timeout_s = float(batch_timeout_s)
        self.backend = backend
        self.cache_key = _exec.executor_cache_key(
            pipeline, types, dict(params or {}), backend, column, datapath)
        # the process-wide memo compiles under its lock: many servers on
        # one key -> one compile (pinned in tests/test_serving.py)
        self._executor = _exec._lowered_executor(
            pipeline, types, dict(params or {}), backend, column,
            datapath=datapath)
        self._input_names = pipeline.input_stages()
        # zero-copy ingestion: quantize each frame ONCE at submit into
        # its input stage's legalized container (identity for uint8
        # beta-0 full-range sources), so queued frames, pad frames and
        # the stacked batch all carry the narrow stored representation
        # and the executor skips the f64 round-trip (`B.ingest_input`)
        from repro.lowering import backends as _B
        lowered = getattr(self._executor, "lowered", None)
        self._ingest = []
        for n in self._input_names:
            ls = lowered.stages[n] if lowered is not None else None
            if ls is None or ls.t is None:
                self._ingest.append(None)
            else:
                self._ingest.append(
                    (ls.t, np.dtype(_B.store_dtype(ls))))
        self._q: "queue.Queue" = queue.Queue(maxsize=max_queue)
        self._closed = False
        self._warm: set = set()
        self._worker = threading.Thread(
            target=self._loop, name=f"serve-{pipeline.name}", daemon=True)
        self._worker.start()

    # -- request side -----------------------------------------------------

    def _quantize(self, a: np.ndarray, slot: int) -> np.ndarray:
        """Frame -> container tile: zero-copy when already container-
        dtype (pre-quantized), one numpy snap otherwise."""
        ing = self._ingest[slot]
        if ing is None:
            return np.asarray(a, dtype=np.float64)
        t, dt = ing
        a = np.asarray(a)
        if a.dtype == dt:              # pre-quantized: ship as-is
            return a
        from repro.lowering.backends import quantize_input
        return quantize_input(a.astype(np.float64), t, dt, np)

    def _normalize(self, image) -> List[np.ndarray]:
        if isinstance(image, dict):
            arrs = [np.asarray(image[n]) for n in self._input_names]
        elif isinstance(image, (tuple, list)):
            arrs = [np.asarray(a) for a in image]
        else:
            arrs = [np.asarray(image)]
        if len(arrs) != len(self._input_names):
            raise ValueError(
                f"pipeline {self.pipeline.name!r} takes "
                f"{len(self._input_names)} inputs, got {len(arrs)}")
        for a in arrs:
            if a.ndim != 2:
                raise ValueError(
                    f"submit() takes single (H, W) frames; got {a.shape}")
        return [self._quantize(a, i) for i, a in enumerate(arrs)]

    def submit(self, image) -> Future:
        """Enqueue one frame (run_fixed input convention: array / tuple /
        dict of (H, W) arrays); resolves to ``{output: (H', W') f64}``."""
        if self._closed:
            raise RuntimeError("PipelineServer is closed")
        with obs.span("serve.submit"):
            req = _Request(self._normalize(image))
            self._q.put(req)
        return req.future

    def warmup(self, shapes: Iterable[Tuple[int, int]]) -> List[tuple]:
        """Compile the batched program for each (H, W) ahead of traffic.

        Runs one zero batch of the fixed batch shape through the
        executor per shape (and the per-shape island/kernel builds it
        implies).  Returns the warmed (batch, H, W) keys.
        """
        warmed = []
        for shape in shapes:
            h, w = shape
            key = (self.batch_size, int(h), int(w))
            if key in self._warm:
                continue
            # container-dtype zeros: compile the same narrow-ingest
            # program the quantized traffic will hit
            zeros = [np.zeros(key) if ing is None
                     else np.zeros(key, dtype=ing[1])
                     for ing in self._ingest]
            with obs.span("serve.warmup", pipeline=self.pipeline.name,
                          backend=self.backend, batch=self.batch_size,
                          h=int(h), w=int(w)):
                self._executor(dict(zip(self._input_names, zeros)))
            self._warm.add(key)
            warmed.append(key)
        return warmed

    # -- batcher side -----------------------------------------------------

    def _collect(self) -> Tuple[List[_Request], bool]:
        """Block for one request, then fill the batch until the timeout
        or the close sentinel.  Returns (requests, saw_sentinel)."""
        item = self._q.get()
        if item is _SENTINEL:
            return [], True
        reqs = [item]
        deadline = time.monotonic() + self.batch_timeout_s
        while len(reqs) < self.batch_size:
            try:
                nxt = self._q.get(timeout=max(deadline - time.monotonic(),
                                              0.0))
            except queue.Empty:
                break
            if nxt is _SENTINEL:
                return reqs, True
            reqs.append(nxt)
        return reqs, False

    def _serve_batch(self, reqs: List[_Request]) -> None:
        n = len(reqs)
        pad = self.batch_size - n
        with obs.span("serve.batch", pipeline=self.pipeline.name,
                      backend=self.backend, size=n, padded=pad) as sp:
            if obs.is_enabled():
                sp.set(t_submit=[r.t_submit for r in reqs])
            try:
                with obs.span("serve.stack"):
                    batch = {}
                    for slot, name in enumerate(self._input_names):
                        frames = [r.images[slot] for r in reqs]
                        frames += [np.zeros_like(frames[0])] * pad
                        batch[name] = np.stack(frames)
                out = self._executor(batch)
                key = (self.batch_size,) + tuple(
                    batch[self._input_names[0]].shape[1:])
                self._warm.add(key)
            except BaseException as e:          # deliver, don't kill the loop
                for r in reqs:
                    r.future.set_exception(e)
                return
            SERVE_STATS.add("frames", n)
            SERVE_STATS.add("batches")
            SERVE_STATS.add("padded", pad)
            with obs.span("serve.deliver"):
                for b, r in enumerate(reqs):
                    r.future.set_result({k: v[b] for k, v in out.items()})

    def _loop(self) -> None:
        while True:
            with obs.span("serve.collect"):
                reqs, stop = self._collect()
            if reqs:
                self._serve_batch(reqs)
            if stop:
                return

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        """Drain: serve everything queued (padding the final partial
        batch), then stop the worker."""
        if self._closed:
            return
        self._closed = True
        self._q.put(_SENTINEL)
        self._worker.join()

    def __enter__(self) -> "PipelineServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve_offline(server: PipelineServer, images: Sequence
                  ) -> List[Dict[str, np.ndarray]]:
    """Offline inference: submit every frame, gather in order.

    The `OfflineInference` entry point: maximal queue pressure, so the
    batcher runs full batches end to end (only the final one pads).
    """
    futures = [server.submit(im) for im in images]
    return [f.result() for f in futures]
