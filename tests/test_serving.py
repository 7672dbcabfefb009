"""Serving layer: batched/sharded execution + the concurrent compile cache.

Three contracts under test (docs/serving.md):

  * **batched bit-exactness** — every lowered backend accepts a leading
    batch dimension and is bit-for-bit the per-image numpy-oracle loop,
    on every benchmark pipeline, including deliberately-saturating
    phase-split residue plans;
  * **executor cache** — the `dsl.exec` memo is a locked LRU: concurrent
    `run_fixed` calls for one key produce EXACTLY ONE compile, hits
    refresh recency, shrinking the cap evicts;
  * **PipelineServer** — fixed-batch padding, drain-on-close, and
    end-to-end oracle equality through the background batcher.
"""
import collections
import contextlib
import threading
import warnings
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs
from repro.core.fixedpoint import FixedPointType
from repro.core.interval import Interval
from repro.core.range_analysis import StageRange
from repro.analysis import run_plan
from repro.dsl.exec import (EXEC_CACHE_STATS, clear_executor_cache,
                            run_fixed, set_executor_cache_cap)
from repro.lowering import compile_backend, lower
from repro.pipelines import dus, hcd, optical_flow, usm
from repro.pipelines import workflows as W

RNG = np.random.default_rng(777)


def _types_for(pipe, beta=4):
    alphas, signed = W.static_alphas(pipe)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return W.types_from_alpha(pipe, alphas, signed,
                                  {n: beta for n in pipe.stages})


def _phase_plan(pipe, betas=3):
    """Deliberately-saturating residue plan (test_lowering's dus_ext
    story): residue ranges tighter than true, so per-residue saturation
    engages on random data."""
    plan = run_plan(pipe, ["interval"],
                    betas={n: betas for n in pipe.stages})
    plan.phases["interval"] = {
        "resS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(-50.0, 50.0))}),
        "UyS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(0.0, 150.0)),
            (1, 0): StageRange.from_interval(Interval(0.0, 250.0))}),
        "band": ((2, 2), {(0, 0): StageRange.from_interval(
            Interval(-30.0, 30.0))}),
    }
    return plan


def _batch(n_in, B, shape, seed):
    rng = np.random.default_rng(seed)
    arrs = tuple(rng.integers(0, 256, (B,) + shape).astype(np.float64)
                 for _ in range(n_in))
    return arrs if n_in > 1 else arrs[0]


BENCHES = [
    ("usm", usm.build, dict(usm.DEFAULT_PARAMS), 1, (48, 48)),
    ("hcd", hcd.build, {}, 1, (48, 48)),
    ("dus_ext", dus.build_extended, {}, 1, (48, 48)),
    ("of_pyramid", lambda: optical_flow.build_pyramid(1), {}, 2, (40, 40)),
]


# ---------------------------------------------------------------------------
# batched differential battery: every backend vs the per-image oracle loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,build,params,n_in,shape",
                         BENCHES, ids=[b[0] for b in BENCHES])
@pytest.mark.parametrize("backend", ["lowered", "pallas", "sharded"])
def test_batched_backends_bit_exact(name, build, params, n_in, shape,
                                    backend):
    pipe = build()
    types = _types_for(pipe)
    arg = _batch(n_in, 3, shape, seed=5)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        oracle = run_fixed(pipe, arg, types, params)   # per-image loop
        out = run_fixed(pipe, arg, types, params, backend=backend)
        for k in out:
            np.testing.assert_array_equal(
                np.asarray(oracle[k]), np.asarray(out[k]),
                err_msg=f"{name}/{backend}/{k}")
        # the same executor still takes single images afterwards
        single_arg = tuple(a[0] for a in arg) if n_in > 1 else arg[0]
        one = run_fixed(pipe, single_arg, types, params, backend=backend)
        for k in one:
            np.testing.assert_array_equal(
                np.asarray(oracle[k])[0], np.asarray(one[k]),
                err_msg=f"{name}/{backend}/{k}/single")


@pytest.mark.parametrize("backend", ["lowered", "pallas", "sharded"])
def test_batched_phase_split_saturating_plan_bit_exact(backend):
    """Batched residue datapaths: per-residue saturation engages and the
    batched program still matches the per-image oracle bit-for-bit."""
    pipe = dus.build_extended()
    plan = _phase_plan(pipe)
    imgs = _batch(1, 3, (48, 48), seed=9)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        lp = lower(pipe, plan)
        assert lp.stages["resS"].phase is not None
        oracle = run_fixed(pipe, imgs, plan)
        out = run_fixed(pipe, imgs, plan, backend=backend)
    for k in out:
        np.testing.assert_array_equal(np.asarray(oracle[k]),
                                      np.asarray(out[k]), err_msg=k)
    # the tightened (0,0)-residue rail must actually clip somewhere,
    # else this proved nothing
    t_res = lp.stages["resS"].phase.types[(0, 0)]
    q = np.rint(np.asarray(oracle["resS"])[:, 0::2, :] * 2.0 ** t_res.beta)
    assert (np.count_nonzero(q >= t_res.int_max)
            + np.count_nonzero(q <= t_res.int_min)) > 0


def test_sharded_explicit_mesh_and_fallback():
    """compile_backend(..., "sharded", mesh=...): the 1-device band mesh
    runs the shard_map program; a rate-inexact height partitions into
    single-tile islands that take the warned serial fallback — both
    bit-exact."""
    from repro.launch.mesh import make_band_mesh
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    img = _batch(1, 2, (48, 48), seed=13)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        lp = lower(pipe, types, params=params)
        run = compile_backend(lp, "sharded", mesh=make_band_mesh(1))
        oracle = run_fixed(pipe, img, types, params)
        out = run(img)
    for k in out:
        np.testing.assert_array_equal(np.asarray(oracle[k]),
                                      np.asarray(out[k]), err_msg=k)

    pyr = dus.build()                  # 47 rows: rate-inexact heights
    ptypes = _types_for(pyr)
    pimg = _batch(1, 2, (47, 48), seed=14)
    obs.reset_warn_once()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        o2 = run_fixed(pyr, pimg, ptypes, {})
        s2 = run_fixed(pyr, pimg, ptypes, {}, backend="sharded")
    caught = [w for w in rec if "serial band walk" in str(w.message)]
    for k in s2:
        np.testing.assert_array_equal(np.asarray(o2[k]),
                                      np.asarray(s2[k]), err_msg=k)
    assert caught, "expected the sharded fallback RuntimeWarning"


# ---------------------------------------------------------------------------
# batched runtime telemetry
# ---------------------------------------------------------------------------

def test_batched_telemetry_matches_per_image_sums():
    """`record_stage` on a (B, H, W) array: min/max join and rail counts
    sum over the per-image planes (the 2-D-only assumption is gone)."""
    from repro.core.fixedpoint import FixedPointType
    from repro.obs.runtime import record_stage
    t = FixedPointType(6, 2, signed=True)
    phase = ((2, 1), {(0, 0): FixedPointType(4, 2, signed=True)})
    rng = np.random.default_rng(21)
    batched = rng.uniform(-9, 9, (3, 8, 8)).round(1)
    with obs.tracing(runtime_ranges=True):
        whole = record_stage("s", batched, t, phase, backend="test")
        per = [record_stage("s", batched[b], t, phase, backend="test")
               for b in range(3)]
    assert whole["min"] == min(p["min"] for p in per)
    assert whole["max"] == max(p["max"] for p in per)
    assert whole["n"] == sum(p["n"] for p in per)
    for key in ("sat", "sat_lo", "sat_hi"):
        assert whole[key] == sum(p[key] for p in per), key
    assert whole["alpha_obs"] == max(p["alpha_obs"] for p in per)


# ---------------------------------------------------------------------------
# executor cache: locked LRU, one compile per key under contention
# ---------------------------------------------------------------------------

def test_concurrent_run_fixed_compiles_exactly_once():
    """The hammer: many threads, one (pipeline, plan, backend) key ->
    exactly one compile (miss), the rest hits, all outputs exact."""
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    img = _batch(1, 1, (32, 32), seed=2)[0]
    clear_executor_cache()
    EXEC_CACHE_STATS.reset()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        oracle = run_fixed(pipe, img, types, params)
        results, errors = [None] * 8, []
        barrier = threading.Barrier(8)

        def work(i):
            try:
                barrier.wait()
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", RuntimeWarning)
                    results[i] = run_fixed(pipe, img, types, params,
                                           backend="lowered")
            except BaseException as e:       # surface, don't deadlock
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors, errors
    assert EXEC_CACHE_STATS["misses"] == 1
    assert EXEC_CACHE_STATS["hits"] == 7
    for r in results:
        for k in r:
            np.testing.assert_array_equal(np.asarray(oracle[k]),
                                          np.asarray(r[k]), err_msg=k)


def test_executor_cache_lru_and_cap():
    """Hits refresh recency (LRU, not FIFO) and the cap is enforced with
    eviction counters; `set_executor_cache_cap` shrinks immediately."""
    pipes = {b: _types_for(usm.build(), beta=b) for b in (3, 4, 5)}
    pipe = usm.build()
    params = dict(usm.DEFAULT_PARAMS)
    img = _batch(1, 1, (32, 32), seed=4)[0]
    clear_executor_cache()
    EXEC_CACHE_STATS.reset()
    prev = set_executor_cache_cap(2)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            run_fixed(pipe, img, pipes[3], params, backend="lowered")  # A
            run_fixed(pipe, img, pipes[4], params, backend="lowered")  # B
            run_fixed(pipe, img, pipes[3], params, backend="lowered")  # hit A
            assert EXEC_CACHE_STATS["hits"] == 1
            # C evicts the LRU entry — B, because the hit refreshed A
            run_fixed(pipe, img, pipes[5], params, backend="lowered")
            assert EXEC_CACHE_STATS["evictions"] == 1
            run_fixed(pipe, img, pipes[3], params, backend="lowered")
            assert EXEC_CACHE_STATS["hits"] == 2          # A survived
            run_fixed(pipe, img, pipes[4], params, backend="lowered")
            assert EXEC_CACHE_STATS["misses"] == 4        # B recompiled
            # shrinking the cap evicts down to size right away
            set_executor_cache_cap(1)
            assert EXEC_CACHE_STATS["evictions"] >= 2
    finally:
        set_executor_cache_cap(prev)
        clear_executor_cache()


# ---------------------------------------------------------------------------
# PipelineServer: padding, drain, oracle equality through the batcher
# ---------------------------------------------------------------------------

def test_pipeline_server_end_to_end_exact():
    from repro.serve import PipelineServer, serve_offline
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    frames = [_batch(1, 1, (32, 32), seed=100 + i)[0] for i in range(7)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with PipelineServer(pipe, types, params, backend="lowered",
                            batch_size=4) as srv:
            assert srv.warmup([(32, 32)]) == [(4, 32, 32)]
            assert srv.warmup([(32, 32)]) == []      # already warm
            outs = serve_offline(srv, frames)
        for f, o in zip(frames, outs):
            ref = run_fixed(pipe, f, types, params)
            for k in o:
                np.testing.assert_array_equal(np.asarray(ref[k]), o[k],
                                              err_msg=k)
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(frames[0])


def test_pipeline_server_pads_partial_batches_and_drains():
    from repro.serve import SERVE_STATS, PipelineServer
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    SERVE_STATS.reset()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        srv = PipelineServer(pipe, types, params, backend="lowered",
                             batch_size=4, batch_timeout_s=0.05)
        fut = srv.submit(_batch(1, 1, (32, 32), seed=7)[0])
        fut.result(timeout=60)        # lone request: padded 1 -> 4
        srv.close()
        srv.close()                   # idempotent
    assert SERVE_STATS["frames"] == 1
    assert SERVE_STATS["batches"] == 1
    assert SERVE_STATS["padded"] == 3


def test_pipeline_server_concurrent_producers_share_one_compile():
    """Multi-threaded submitters + the memo: one compile for the server's
    key even with producers racing the warmup."""
    from repro.serve import PipelineServer
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    clear_executor_cache()
    EXEC_CACHE_STATS.reset()
    frames = [_batch(1, 1, (32, 32), seed=200 + i)[0] for i in range(12)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ref = run_fixed(pipe, frames[0], types, params)
        EXEC_CACHE_STATS.reset()      # count only the server's traffic
        clear_executor_cache()
        with PipelineServer(pipe, types, params, backend="lowered",
                            batch_size=4) as srv:
            futs = [None] * len(frames)

            def produce(lo, hi):
                for i in range(lo, hi):
                    futs[i] = srv.submit(frames[i])

            threads = [threading.Thread(target=produce,
                                        args=(j * 4, (j + 1) * 4))
                       for j in range(3)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            outs = [f.result(timeout=120) for f in futs]
    assert EXEC_CACHE_STATS["misses"] == 1     # the server's own compile
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]), outs[0][k],
                                      err_msg=k)


def test_pipeline_server_zero_copy_uint8_ingestion():
    """uint8 frames on a beta-0 design are ingested zero-copy (quantized
    once at submit, stored tile == the raw pixel buffer) and produce
    byte-identical results to the same frames submitted as f64."""
    from repro.lowering import backends as B
    from repro.serve import PipelineServer, serve_offline
    pipe = usm.build()
    types = _types_for(pipe, beta=0)
    params = dict(usm.DEFAULT_PARAMS)
    lp = lower(pipe, types, params=params)
    assert np.dtype(B.store_dtype(lp.stages["img"])) == np.uint8
    f64 = [_batch(1, 1, (32, 32), seed=200 + i)[0] for i in range(5)]
    u8 = [f.astype(np.uint8) for f in f64]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with PipelineServer(pipe, types, params, backend="lowered",
                            batch_size=4) as srv:
            srv.warmup([(32, 32)])
            outs_u8 = serve_offline(srv, u8)
        with PipelineServer(pipe, types, params, backend="lowered",
                            batch_size=4) as srv:
            outs_f64 = serve_offline(srv, f64)
    for f, a, b in zip(f64, outs_u8, outs_f64):
        ref = run_fixed(pipe, f, types, params)
        for k in a:
            np.testing.assert_array_equal(np.asarray(ref[k]), a[k],
                                          err_msg=f"uint8/{k}")
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"uint8 vs f64/{k}")


# ---------------------------------------------------------------------------
# host-path spans of a served batch (docs/observability.md)
# ---------------------------------------------------------------------------

HOST_PATH = [
    ("pallas", dus.build, {}, "exec.pallas",
     ["exec.h2d", "exec.dispatch", "exec.device_wait", "exec.d2h",
      "exec.dequant"]),
    ("lowered", usm.build, dict(usm.DEFAULT_PARAMS), "exec.lowered",
     ["exec.h2d", "exec.dispatch", "exec.device_wait", "exec.d2h",
      "exec.dequant"]),
]


@pytest.mark.parametrize("backend,build,params,exec_span,steps", HOST_PATH,
                         ids=[h[0] for h in HOST_PATH])
def test_served_batch_spans_cover_the_host_path(backend, build, params,
                                                 exec_span, steps):
    from repro.serve import PipelineServer, serve_offline
    pipe = build()
    types = _types_for(pipe)
    shape = (512, 512)          # large enough that span overhead is noise
    frames = list(_batch(1, 8, shape, seed=31).astype(np.uint8))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with PipelineServer(pipe, types, params, backend=backend,
                            batch_size=4) as srv:
            srv.warmup([shape])
            with obs.tracing() as tr:
                outs = serve_offline(srv, frames)
    ref = run_fixed(pipe, frames[5], types, params)
    for k in outs[5]:
        np.testing.assert_array_equal(np.asarray(ref[k]), outs[5][k])

    spans = tr.spans()

    def children(p):
        return [s for s in spans if s.parent_id == p.span_id]

    batches = tr.spans("serve.batch")
    assert sum(b.attrs["size"] for b in batches) == 8
    for b in batches:
        assert [c.name for c in children(b)] == \
            ["serve.stack", exec_span, "serve.deliver"]
    parents = tr.spans(exec_span)
    assert len(parents) == len(batches)
    covered = 0.0
    for p in parents:
        kids = children(p)
        assert [k.name for k in kids] == steps
        covered += sum(k.t1 - k.t0 for k in kids)
    assert covered >= 0.95 * sum(p.t1 - p.t0 for p in parents)

    # submit on the caller's thread, collect and batch on the batcher's
    submits = tr.spans("serve.submit")
    assert len(submits) == 8
    batcher = {b.thread_id for b in batches}
    assert len(batcher) == 1
    assert {s.thread_id for s in submits}.isdisjoint(batcher)
    assert {c.thread_id for c in tr.spans("serve.collect")} == batcher
    # each batch carries its requests' submit times, on the span clock
    for b in batches:
        ts = b.attrs["t_submit"]
        assert len(ts) == b.attrs["size"] and max(ts) <= b.t0
        for t in ts:
            assert any(s.t0 <= t <= s.t1 for s in submits)


@pytest.mark.parametrize("traced", [False, True], ids=["off", "traced"])
def test_h2d_waits_for_the_inputs_to_land(monkeypatch, traced):
    """The input copies are asynchronous: `exec.h2d` blocks on them, so
    their transfer is not charged to `exec.device_wait`, traced or not."""
    import jax
    import jax.numpy as jnp
    from repro.lowering import backends as B
    waited = []
    real = jax.block_until_ready

    def spy(x):
        tr = obs.active_tracer()
        waited.append((tr._stack()[-1].name if tr else None, x))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    args = {"a": jnp.arange(4)}
    lp = SimpleNamespace(stages={"y": SimpleNamespace(
        store_float=False, t=FixedPointType(alpha=4, beta=1))})
    with (obs.tracing() if traced else contextlib.nullcontext()):
        res = B.run_on_device(lp, ["y"], lambda: args,
                              lambda a: {"y": a["a"] + 1}, B.HostBuffers())
    np.testing.assert_array_equal(res["y"], np.arange(1, 5) / 2)
    assert len(waited) == 2 and waited[0][1] is args
    assert set(waited[1][1]) == {"y"}
    if traced:
        assert [n for n, _ in waited] == ["exec.h2d", "exec.device_wait"]


def test_untraced_serving_computes_no_island_attributes(monkeypatch):
    """The island spans' attributes are computed when the island is built
    (at warm-up), never per served batch, traced or not."""
    from repro.lowering.islands import Island
    from repro.serve import PipelineServer, serve_offline
    pipe = dus.build()
    types = _types_for(pipe)
    shape = (47, 48)            # rate-inexact: several islands
    frames = list(_batch(1, 4, shape, seed=41).astype(np.uint8))

    def refuse(self, *a):
        raise AssertionError("island attribute computed while serving")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with PipelineServer(pipe, types, {}, backend="pallas",
                            batch_size=4) as srv:
            srv.warmup([shape])
            for attr in ("stored_mix", "carrier_mix", "boundary_bytes"):
                monkeypatch.setattr(Island, attr, refuse)
            plain = serve_offline(srv, frames)
            with obs.tracing() as tr:
                traced = serve_offline(srv, frames)
    for a, b, f in zip(plain, traced, frames):
        ref = run_fixed(pipe, f, types, {})
        for k in a:
            np.testing.assert_array_equal(np.asarray(ref[k]), a[k])
            np.testing.assert_array_equal(a[k], b[k])
    isl = tr.spans("exec.pallas.island")
    assert len(isl) > 1 and all(s.attrs["containers"] for s in isl)


# ---------------------------------------------------------------------------
# recycled output buffers: a served result is never overwritten while
# its client holds it, and results the client drops are widened into
# again
# ---------------------------------------------------------------------------

RECYCLE = [("lowered", usm.build, dict(usm.DEFAULT_PARAMS)),
           ("pallas", dus.build, {})]


def _closed_loop(srv, frames, keep):
    """A batch client: two batches in flight, each result reaped as it
    returns and kept only where ``keep(i)``; returns {i: kept result}."""
    pending: collections.deque = collections.deque()
    kept = {}

    def reap():
        i, fut = pending.popleft()
        res = fut.result(timeout=120)
        if keep(i):
            kept[i] = res

    for i, f in enumerate(frames):
        pending.append((i, srv.submit(f)))
        while len(pending) > 2 * srv.batch_size:
            reap()
    while pending:
        reap()
    return kept


def _serve_recycling(backend, build, params, keep, n_batches=30):
    from repro.lowering import backends as B
    from repro.serve import PipelineServer
    pipe = build()
    types = _types_for(pipe)
    shape = (32, 32)
    frames = list(_batch(1, 2 * n_batches, shape, seed=61).astype(
        np.uint8))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with PipelineServer(pipe, types, params, backend=backend,
                            batch_size=2) as srv:
            srv.warmup([shape])
            B.HOST_BUFFER_STATS.reset()
            kept = _closed_loop(srv, frames, keep)
    return pipe, types, frames, kept, dict(B.HOST_BUFFER_STATS)


@pytest.mark.parametrize("backend,build,params", RECYCLE,
                         ids=[r[0] for r in RECYCLE])
def test_served_results_are_never_overwritten(backend, build, params):
    """Every third frame's result is kept to the end, pinning its batch's
    buffers, while the batches with no kept frame are recycled; every
    kept result still equals the oracle after the last batch."""
    pipe, types, frames, kept, stats = _serve_recycling(
        backend, build, params, keep=lambda i: i % 3 == 0)
    assert sorted(kept) == list(range(0, len(frames), 3))
    assert stats["reused"] > 0 and stats["fresh"] > 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for i, res in kept.items():
            ref = run_fixed(pipe, frames[i], types, params)
            for k, v in res.items():
                np.testing.assert_array_equal(np.asarray(ref[k]), v,
                                              err_msg=f"frame {i}/{k}")


@pytest.mark.parametrize("backend,build,params", RECYCLE,
                         ids=[r[0] for r in RECYCLE])
def test_dropped_served_results_are_recycled(backend, build, params):
    *_, stats = _serve_recycling(backend, build, params,
                                 keep=lambda i: False)
    assert stats["reused"] > 0
    assert stats["fresh"] < stats["reused"] / 4
