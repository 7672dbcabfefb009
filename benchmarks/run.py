"""Benchmark harness — one function per paper table/figure + extensions.

    PYTHONPATH=src python -m benchmarks.run             # all benchmarks
    PYTHONPATH=src python -m benchmarks.run --only table2_hcd_ranges,kernels
    PYTHONPATH=src python -m benchmarks.run --only pipeline_throughput \
        --trace trace_out                               # traced run

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time of the
benchmark body; derived = the benchmark's headline result).  Detailed rows
go to benchmarks/results/<name>.json.

``--trace <dir>`` runs every selected benchmark under a fresh `repro.obs`
tracer with runtime range telemetry on, and writes two artifacts per
benchmark into <dir>: ``<name>.trace.json`` (Chrome trace-event JSON —
load in ui.perfetto.dev or chrome://tracing) and ``<name>.jsonl`` (the
event stream ``python -m repro.obs.report`` summarizes).  Tracing changes
no benchmark outputs (telemetry is read-only post-processing) but does
add measurement overhead — don't compare traced timings against untraced
ones.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _kernel_bench():
    """Pallas kernels: interpret-mode correctness + jitted-oracle timing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixedpoint import FixedPointType
    from repro.kernels.qdq import ops as qdq_ops
    from repro.kernels.qmatmul.ops import matmul_quantized
    from repro.kernels.stencil.ops import stencil_fixed

    rng = np.random.default_rng(0)
    rows = []

    img = jnp.asarray(rng.integers(0, 256, (64, 64)).astype(np.float32))
    t_in = FixedPointType(8, 0, signed=False)
    t_out = FixedPointType(9, 4, signed=True)
    f = lambda: stencil_fixed(img, [[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]],
                              1 / 12, t_in, t_out, use_ref=True)
    f().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f().block_until_ready()
    rows.append(("stencil_ref_64x64", (time.perf_counter() - t0) / 20 * 1e6))

    a = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    f = lambda: matmul_quantized(a, b, use_ref=True)
    f().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f().block_until_ready()
    rows.append(("qmatmul_ref_256", (time.perf_counter() - t0) / 20 * 1e6))

    x = jnp.asarray(rng.normal(size=(64, 256)).astype(np.float32))
    f = lambda: qdq_ops.fake_quant(x, use_ref=True)
    f().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f().block_until_ready()
    rows.append(("qdq_ref_16k", (time.perf_counter() - t0) / 20 * 1e6))
    return rows, "jitted oracle paths (Pallas kernels validated in tests)"


def _lm_quant_bench():
    """Beyond-paper: AutoQuant on LM smoke models (token agreement)."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data.batches import make_batch
    from repro.models.registry import get_model
    from repro.quant.autoquant import autoquant

    rows = []
    for arch in ("qwen3-4b", "rwkv6-3b", "mixtral-8x7b"):
        cfg = get_smoke_config(arch)
        m = get_model(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        batches = [make_batch(cfg, 2, 16, seed=s) for s in range(2)]
        res = autoquant(m, params, batches, target_agreement=0.95)
        rows.append((arch, res.bits, round(res.quality, 4),
                     res.profile_passes, round(res.bytes_ratio, 3)))
    return rows, "per-class weight bits via the paper's beta-search loop"


def _lm_beta_sweep():
    """Paper Fig. 6, LM edition: token agreement vs uniform weight bits."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data.batches import make_batch
    from repro.models.registry import get_model
    from repro.quant.autoquant import fake_quant_params, token_agreement
    from repro.quant.calibrate import REVERSE_TOPO_CLASSES

    cfg = get_smoke_config("qwen3-4b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 32, seed=0)
    ref = m.forward(params, batch)
    rows = []
    for bits in (8, 6, 4, 3, 2):
        qp = fake_quant_params(params,
                               {c: bits for c in REVERSE_TOPO_CLASSES})
        agree = token_agreement(ref, m.forward(qp, batch))
        rows.append((bits, round(agree, 4), round(bits / 16, 3)))
    knee = next((b for b, a, _ in rows if a < 0.9), 2)
    return rows, (f"agreement degrades gracefully to ~{knee} bits "
                  f"(paper Fig.6: HCD accuracy flat until beta floor)")


def _smt_throughput():
    """Solver-throughput smoke: boxes/sec on a fixed HCD decide workload.

    Runs the batched engine and the scalar reference oracle on the same
    query — "can HCD's det exceed 2^30?" — at their production node budgets
    and reports boxes/sec for each plus the speedup.  CI prints this line
    so hot-loop regressions in the branch-and-prune core are visible.
    """
    import time as _t
    from repro.core.range_analysis import analyze
    from repro.pipelines import hcd
    from repro.smt import solver as S
    from repro.smt.encoder import encode_stage

    p = hcd.build()
    bounds = {n: r.range for n, r in analyze(p).items()}
    csp, root = encode_stage(p, "det", bounds)
    threshold = 2.0 ** 30        # deep in UNKNOWN territory: forces search
    rows = []
    rates = {}
    for name, fn, nodes in (("batched", S.decide, 4096),
                            ("scalar", S.decide_scalar, 256)):
        t0 = _t.perf_counter()
        v = fn(csp, root, "ge", threshold, S.BPBudget(nodes, 6))
        dt = _t.perf_counter() - t0
        rates[name] = v.nodes / dt
        rows.append((name, v.status, v.nodes, round(dt, 3),
                     round(rates[name], 1)))
    speedup = rates["batched"] / max(rates["scalar"], 1e-9)
    return rows, (f"HCD det decide: batched {rates['batched']:.0f} boxes/s "
                  f"vs scalar {rates['scalar']:.0f} boxes/s "
                  f"({speedup:.1f}x)")


def _pipeline_throughput():
    """End-to-end fixed-pipeline throughput: interpreter vs plan-lowered.

    Runs USM / HCD / DUS-ext through the three executors of the
    plan-driven compile path (docs/execution_backends.md):

      * interpreter — per-stage `run_fixed` numpy oracle
      * lowered-jnp — one fused jit program (`repro.lowering`, backend
        "jnp"); the acceptance bar is >=3x over the interpreter on at
        least one benchmark
      * pallas      — the fused line-buffer kernel; *interpret mode* on
        CPU (a pure-python emulation, reported for completeness but not a
        performance number; on a real TPU pass interpret=False)

    Every backend's outputs are checked bit-for-bit against the oracle
    before timing.  Emits BENCH_pipeline_throughput.json at the repo root
    (uploaded as a CI artifact) in addition to the harness row JSON.

    Env knobs: REPRO_BENCH_ROWS (default 512 — small sizes measure jax
    dispatch overhead, not the datapath), REPRO_BENCH_REPS (default 8),
    REPRO_BENCH_PALLAS=0 to skip pallas timing (it is interpret-mode
    slow; correctness is still checked at a small size).

    Each entry also records its rate-island count and an HBM roofline
    record (`benchmarks.roofline.pipeline_roofline`): cost-model
    bytes/pixel, the frame-time floor those bytes imply, and the
    achieved GB/s of the lowered-jnp executor.
    """
    import warnings

    import numpy as np

    from benchmarks.roofline import pipeline_roofline
    from repro.dsl.exec import run_fixed
    from repro.core.cost_model import lowered_datapaths
    from repro.lowering import compile_pipeline, partition_islands
    from repro.pipelines import dus, hcd, usm
    from repro.pipelines import workflows as W

    DEFAULT_ROWS = 512
    rows_n = int(os.environ.get("REPRO_BENCH_ROWS", DEFAULT_ROWS))
    reps = int(os.environ.get("REPRO_BENCH_REPS", 8))
    time_pallas = os.environ.get("REPRO_BENCH_PALLAS", "1") != "0"
    shape = (rows_n, rows_n)
    rows, blob = [], {"shape": list(shape), "reps": reps, "benchmarks": {}}
    if rows_n < DEFAULT_ROWS:
        # sub-default shapes time dispatch overhead, not the datapath —
        # keep the artifact honest about it
        warnings.warn(
            f"pipeline_throughput at {rows_n}x{rows_n} (default "
            f"{DEFAULT_ROWS}x{DEFAULT_ROWS}): timings measure jax "
            f"dispatch overhead, not the datapath; the emitted JSON is "
            f"marked debug_shape", RuntimeWarning, stacklevel=2)
        blob["debug_shape"] = True
    for name, pipe, params in (
            ("usm", usm.build(), dict(usm.DEFAULT_PARAMS)),
            ("hcd", hcd.build(), {}),
            ("dus_ext", dus.build_extended(), {})):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            alphas, signed = W.static_alphas(pipe)
            types = W.types_from_alpha(pipe, alphas, signed,
                                       {n: 4 for n in pipe.stages})
        img = np.random.default_rng(0).integers(
            0, 256, shape).astype(np.float64)
        oracle = run_fixed(pipe, img, types, params)

        def bench(fn, n):
            fn()                       # warm (compile included, untimed)
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            return (time.perf_counter() - t0) / n

        t_int = bench(lambda: run_fixed(pipe, img, types, params),
                      max(reps // 3, 2))
        entry = {"interp_ms": t_int * 1e3}

        run_jnp = compile_pipeline(pipe, types, params=params, backend="jnp")
        got = run_jnp(img)
        exact = all(np.array_equal(np.asarray(oracle[k]), got[k])
                    for k in got)
        entry["lowered_jnp_ms"] = bench(lambda: run_jnp(img), reps) * 1e3
        entry["lowered_exact"] = bool(exact)
        entry["speedup_lowered"] = t_int * 1e3 / entry["lowered_jnp_ms"]

        # every DAG now lowers to fused pallas islands (no LoweringError
        # fallback left) — a failure here is a real bug and should raise
        run_pl = compile_pipeline(pipe, types, params=params,
                                  backend="pallas")
        small = img[:32, :32]
        o_small = run_fixed(pipe, small, types, params)
        g_small = run_pl(small)
        entry["pallas_exact"] = bool(all(
            np.array_equal(np.asarray(o_small[k]), g_small[k])
            for k in g_small))
        entry["islands"] = len(
            partition_islands(run_pl.lowered, shape).islands)
        if time_pallas:
            entry["pallas_interpret_ms"] = bench(
                lambda: run_pl(img), max(reps // 5, 1)) * 1e3

        entry["roofline"] = pipeline_roofline(
            pipe, types, entry["lowered_jnp_ms"], shape,
            datapaths=lowered_datapaths(run_jnp.lowered),
            lowered=run_jnp.lowered)
        blob["benchmarks"][name] = entry
        rows.append((name, round(entry["interp_ms"], 2),
                     round(entry["lowered_jnp_ms"], 2),
                     round(entry.get("pallas_interpret_ms", float("nan")), 2),
                     round(entry["speedup_lowered"], 2),
                     entry["lowered_exact"], entry["pallas_exact"]))

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(os.path.dirname(here),
                            "BENCH_pipeline_throughput.json")
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1)
    best = max(blob["benchmarks"].items(),
               key=lambda kv: kv[1]["speedup_lowered"])
    broken = [n for n, e in blob["benchmarks"].items()
              if not (e["lowered_exact"] and e["pallas_exact"] is True)]
    if broken:
        # a perf number for a wrong answer is worthless — fail the run
        # (and the CI step) outright
        raise AssertionError(
            f"lowered/pallas outputs diverged from the run_fixed oracle on "
            f"{broken}; see {out_path}")
    _check_throughput_baseline(blob, os.path.dirname(here))
    return rows, (f"lowered-jnp best {best[1]['speedup_lowered']:.1f}x over "
                  f"interpreter on {best[0]} at {rows_n}x{rows_n} "
                  f"(bit-exact); pallas interpret-mode checked")


def _check_throughput_baseline(blob, root, tol: float = 0.20):
    """Perf-regression gate vs BENCH_pipeline_throughput.baseline.json.

    Measured bytes/pixel is deterministic (store-dtype x stage-shape
    arithmetic), so a >`tol` regression **fails** the run.  Wall-clock is
    machine-noisy, so a >`tol` `lowered_jnp_ms` regression *warns* by
    default and fails only under ``REPRO_BENCH_STRICT_MS=1`` (set on
    runners with stable hardware).  Debug shapes skip the gate — the
    baseline speaks for the default geometry only.
    """
    import warnings

    base_path = os.path.join(root, "BENCH_pipeline_throughput.baseline.json")
    if blob.get("debug_shape") or not os.path.exists(base_path):
        return
    with open(base_path) as f:
        base = json.load(f)
    if base.get("shape") != blob.get("shape"):
        warnings.warn(
            f"throughput baseline shape {base.get('shape')} != run shape "
            f"{blob.get('shape')}; skipping the regression gate",
            RuntimeWarning, stacklevel=2)
        return
    strict_ms = os.environ.get("REPRO_BENCH_STRICT_MS", "0") == "1"
    failures = []
    for name, be in base.get("benchmarks", {}).items():
        e = blob["benchmarks"].get(name)
        if e is None:
            continue
        b_bytes = be.get("roofline", {}).get("measured_bytes_per_pixel")
        n_bytes = e.get("roofline", {}).get("measured_bytes_per_pixel")
        if b_bytes and n_bytes and n_bytes > b_bytes * (1 + tol):
            failures.append(
                f"{name}: measured bytes/pixel {n_bytes:.1f} vs baseline "
                f"{b_bytes:.1f} (>{tol:.0%} regression)")
        b_ms, n_ms = be.get("lowered_jnp_ms"), e.get("lowered_jnp_ms")
        if b_ms and n_ms and n_ms > b_ms * (1 + tol):
            msg = (f"{name}: lowered_jnp_ms {n_ms:.2f} vs baseline "
                   f"{b_ms:.2f} (>{tol:.0%} regression)")
            if strict_ms:
                failures.append(msg)
            else:
                warnings.warn(f"throughput regression (non-strict): {msg}",
                              RuntimeWarning, stacklevel=2)
    if failures:
        raise AssertionError(
            "pipeline_throughput regressed vs the committed baseline:\n  "
            + "\n  ".join(failures))


def _serving_throughput():
    """Sustained serving throughput through `repro.serve.PipelineServer`.

    Drives USM frame streams through the batched serving harness
    (docs/serving.md) at batch sizes 1/4/16 and reports frames/sec plus
    p50/p99 per-frame latency (submit -> result, queueing included) per
    shape.  Shapes: ``smoke`` (64x64 — dispatch-overhead regime, where
    batching shows its >=2x win), ``1080p`` (1080x1920) and ``4k``
    (2160x3840) full-frame rates.

    Bit-exactness: EVERY (shape, batch) cell is verified against the
    per-image `run_fixed` numpy oracle and the run fails loudly on any
    mismatch — all served frames at the smoke shape, a sampled frame at
    the large shapes (the batched program is shape-generic; the sample
    proves this process's compile, while tests/test_serving.py pins the
    full cross-shape/plan battery).

    Emits BENCH_serving_throughput.json at the repo root (CI artifact +
    job summary).  Env knobs: REPRO_SERVE_SHAPES (comma list of smoke /
    1080p / 4k / HxW; default "smoke,1080p,4k" — CI smoke runs set
    "smoke"), REPRO_SERVE_BATCHES (default "1,4,16"),
    REPRO_SERVE_BACKEND (default "lowered"; also "pallas"/"sharded"),
    REPRO_SERVE_FRAMES (frames per measurement, default 2*batch,
    min 8).
    """
    import warnings

    import numpy as np

    from repro.dsl.exec import run_fixed
    from repro.pipelines import usm
    from repro.pipelines import workflows as W
    from repro.serve import PipelineServer

    NAMED = {"smoke": (64, 64), "1080p": (1080, 1920),
             "4k": (2160, 3840)}

    def parse_shape(s):
        if s in NAMED:
            return s, NAMED[s]
        h, w = s.lower().split("x")
        return s, (int(h), int(w))

    shapes = [parse_shape(s) for s in os.environ.get(
        "REPRO_SERVE_SHAPES", "smoke,1080p,4k").split(",") if s]
    batches = [int(b) for b in os.environ.get(
        "REPRO_SERVE_BATCHES", "1,4,16").split(",") if b]
    backend = os.environ.get("REPRO_SERVE_BACKEND", "lowered")
    frames_env = os.environ.get("REPRO_SERVE_FRAMES", "")

    pipe = usm.build()
    params = dict(usm.DEFAULT_PARAMS)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        alphas, signed = W.static_alphas(pipe)
        types = W.types_from_alpha(pipe, alphas, signed,
                                   {n: 4 for n in pipe.stages})

    rows = []
    blob = {"pipeline": "usm", "backend": backend, "shapes": {}}
    rng = np.random.default_rng(0)
    for label, (h, w) in shapes:
        n_frames_of = lambda b: int(frames_env) if frames_env \
            else max(2 * b, 8)
        imgs = [rng.integers(0, 256, (h, w)).astype(np.float64)
                for _ in range(max(n_frames_of(b) for b in batches))]
        # oracle reference frames: every frame at the smoke shape, a
        # sampled frame at the big shapes — so every (shape, batch)
        # cell below is verified (no silent verified:false rows)
        sample = range(len(imgs)) if label == "smoke" else range(1)
        oracle = {i: run_fixed(pipe, imgs[i], types, params)
                  for i in sample}
        shape_entry = {"h": h, "w": w, "batch": {}}
        for b in batches:
            n = n_frames_of(b)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                with PipelineServer(pipe, types, params, backend=backend,
                                    batch_size=b) as srv:
                    srv.warmup([(h, w)])
                    t_done = [None] * n
                    futs = []
                    t0 = time.perf_counter()
                    for i in range(n):
                        fut = srv.submit(imgs[i])
                        fut.add_done_callback(
                            lambda f, i=i: t_done.__setitem__(
                                i, time.perf_counter()))
                        futs.append((time.perf_counter(), fut))
                    outs = [f.result() for _, f in futs]
                    t1 = max(t_done)
            checked = 0
            for i, out in enumerate(outs):
                ref = oracle.get(i)
                if ref is None:
                    continue
                for k in out:
                    if not np.array_equal(out[k], np.asarray(ref[k])):
                        raise AssertionError(
                            f"serving output diverged from the oracle "
                            f"(usm/{label}, batch={b}, frame {i}, "
                            f"stage {k!r})")
                checked += 1
            if checked == 0:       # a cell nobody verified is a harness bug
                raise AssertionError(
                    f"serving benchmark verified zero frames at "
                    f"usm/{label} batch={b}")
            lat_ms = [(t_done[i] - futs[i][0]) * 1e3 for i in range(n)]
            fps = n / (t1 - t0)
            entry = {"fps": fps, "frames": n,
                     "p50_ms": float(np.percentile(lat_ms, 50)),
                     "p99_ms": float(np.percentile(lat_ms, 99)),
                     "verified": True, "verified_frames": checked}
            shape_entry["batch"][str(b)] = entry
            rows.append((f"usm/{label}", b, round(fps, 2),
                         round(entry["p50_ms"], 2),
                         round(entry["p99_ms"], 2)))
        if "1" in shape_entry["batch"] and "16" in shape_entry["batch"]:
            shape_entry["speedup_b16_vs_b1"] = (
                shape_entry["batch"]["16"]["fps"]
                / shape_entry["batch"]["1"]["fps"])
        blob["shapes"][label] = shape_entry

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(os.path.dirname(here),
                            "BENCH_serving_throughput.json")
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1)
    best = max((e for e in blob["shapes"].values()
                if "speedup_b16_vs_b1" in e),
               key=lambda e: e["speedup_b16_vs_b1"], default=None)
    head = "" if best is None else (
        f"; batch-16 {best['speedup_b16_vs_b1']:.1f}x batch-1 fps at "
        f"{best['h']}x{best['w']}")
    return rows, (f"usm serving via {backend} across "
                  f"{len(shapes)} shapes x batches {batches}{head}")


def _design_search():
    """Closed-loop bitwidth DSE (`repro.dse`) on USM / HCD / DUS-ext.

    Runs `run_design_search` — plan-seeded §V-B beta sweep, §IV
    homogeneity-cluster alpha descent, and the annealing controller —
    under per-benchmark PSNR budgets and emits
    ``BENCH_design_search.json`` at the repo root (CI artifact + job
    summary).  Every number on the frontier is *measured*: candidates
    are specialized and executed through the lowered backend against
    the f64 oracle, and the run re-verifies each returned point
    (`Evaluator.verify` — bit-exact lowered re-score + numpy oracle
    cross-check) before reporting.  Hard gates, asserted here:

      * every frontier has >= 5 points and every point is `verified`;
      * the chosen design beats the all-float design on BOTH modeled
        power and area while meeting its error budget.

    Also reported: ratios vs the plan's default mapping (sound alphas +
    §V-B uniform beta) — what the closed loop buys over just reading
    the plan off.  Env knobs: REPRO_DSE_SHAPE (default 32x32),
    REPRO_DSE_IMAGES (calibration images, default 2), REPRO_DSE_ITERS
    (anneal steps, default 24), REPRO_DSE_SEED (default 0),
    REPRO_DSE_BACKEND (default "lowered").
    """
    import warnings

    from repro.core import cost_model
    from repro.dse import DSE_STATS, ErrorBudget, run_design_search
    from repro.pipelines import workflows as W

    h, w = (int(x) for x in os.environ.get(
        "REPRO_DSE_SHAPE", "32x32").lower().split("x"))
    n_img = int(os.environ.get("REPRO_DSE_IMAGES", 2))
    iters = int(os.environ.get("REPRO_DSE_ITERS", 24))
    seed = int(os.environ.get("REPRO_DSE_SEED", 0))
    backend = os.environ.get("REPRO_DSE_BACKEND", "lowered")

    # budgets track each pipeline's quality plateau (saturation at the
    # profile-seeded alphas caps PSNR well before the beta floor does):
    # USM is exact-friendly, HCD's downstream consumer is a thresholded
    # corner mask (tolerates saturation on `harris`), DUS-ext plateaus
    # just under 48 dB at its profile alphas
    cases = (("usm", W.make_usm, 50.0), ("hcd", W.make_hcd, 40.0),
             ("dus_ext", W.make_dus_ext, 45.0))
    rows = []
    blob = {"shape": [h, w], "images": n_img, "anneal_iters": iters,
            "seed": seed, "backend": backend, "benchmarks": {}}
    for name, make, min_psnr in cases:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            setup = make(n_train=n_img, n_test=n_img, shape=(h, w))
            plan = setup.plan()
            t0 = time.perf_counter()
            res = run_design_search(
                setup.pipeline, plan, setup.train_images,
                ErrorBudget(min_psnr=min_psnr), params=setup.params,
                seed=seed, anneal_iters=iters, backend=backend,
                verify=True)
        dt = time.perf_counter() - t0
        pts = res.frontier.points()
        ch = res.chosen

        # the two hard gates of this benchmark: a real frontier, every
        # point re-scored bit-exactly through the lowered backend
        assert len(pts) >= 5, \
            f"{name}: frontier has {len(pts)} points (< 5)"
        unverified = [p.strategy for p in pts if not p.verified]
        assert not unverified, \
            f"{name}: unverified frontier points from {unverified}"
        assert ch is not None and ch.meets_budget

        flt = cost_model.design_cost(
            setup.pipeline, cost_model.float_design(setup.pipeline))
        flt_area = flt.lut_bits + flt.dsp_bits
        assert ch.power < flt.power_proxy and ch.area < flt_area, \
            (f"{name}: chosen design does not beat float on both axes "
             f"(power {ch.power} vs {flt.power_proxy}, "
             f"area {ch.area} vs {flt_area})")

        # the plan's default mapping: sound alphas + §V-B uniform beta
        plan_types = W.types_from_alpha(
            setup.pipeline, plan.alphas(None), plan.signed(None),
            {n: res.beta_result.uniform_beta
             for n in setup.pipeline.stages})
        pl = cost_model.design_cost(setup.pipeline, plan_types)
        entry = {
            "budget_min_psnr": min_psnr, "seconds": dt,
            "evaluations": res.evaluations,
            "clusters": [list(c) for c in res.clusters],
            "frontier": res.frontier.to_json_dict(),
            "chosen": ch.to_json_dict(),
            "float": {"power": flt.power_proxy, "area": flt_area},
            "plan_default": {"power": pl.power_proxy,
                             "area": pl.lut_bits + pl.dsp_bits},
            "ratio_vs_float": {"power": flt.power_proxy / ch.power,
                               "area": flt_area / ch.area},
            "ratio_vs_plan": {"power": pl.power_proxy / ch.power,
                              "area": (pl.lut_bits + pl.dsp_bits)
                                      / ch.area},
        }
        blob["benchmarks"][name] = entry
        rows.append((name, len(pts), res.evaluations,
                     round(ch.psnr, 2),
                     round(entry["ratio_vs_float"]["power"], 2),
                     round(entry["ratio_vs_float"]["area"], 2),
                     round(entry["ratio_vs_plan"]["power"], 2),
                     round(dt, 1)))
    blob["stats"] = dict(DSE_STATS)

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(os.path.dirname(here),
                            "BENCH_design_search.json")
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    best = max(blob["benchmarks"].items(),
               key=lambda kv: kv[1]["ratio_vs_float"]["power"])
    sizes = "/".join(str(len(e["frontier"]["points"]))
                     for e in blob["benchmarks"].values())
    return rows, (f"frontiers {sizes} pts (all verified bit-exact); "
                  f"every chosen design beats float on power AND area "
                  f"(best {best[1]['ratio_vs_float']['power']:.1f}x power "
                  f"on {best[0]})")


BENCHES = {}


def _register():
    from benchmarks import paper_tables as T
    BENCHES.update({
        "table2_hcd_ranges": T.table2_hcd_ranges,
        "table3_hcd_power": T.table3_hcd_power,
        "table4_hcd_bitwidths": T.table4_hcd_bitwidths,
        "table5_usm_bitwidths": T.table5_usm_bitwidths,
        "table6_usm_power": T.table6_usm_power,
        "table7_dus_power": T.table7_dus_power,
        "table8_dus_bitwidths": T.table8_dus_bitwidths,
        "table9_of_bitwidths": T.table9_of_bitwidths,
        "table10_of_power": T.table10_of_power,
        "table11_smt_alphas": T.table11_smt_alphas,
        "fig5_cdf": T.fig5_cdf,
        "fig6_beta_sweep": T.fig6_beta_sweep,
        "kernels": _kernel_bench,
        "lm_quant": _lm_quant_bench,
        "lm_beta_sweep": _lm_beta_sweep,
        "smt_throughput": _smt_throughput,
        "pipeline_throughput": _pipeline_throughput,
        "serving_throughput": _serving_throughput,
        "design_search": _design_search,
        "table12_design_frontier": T.table12_design_frontier,
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    ap.add_argument("--trace", default="", metavar="DIR",
                    help="run each benchmark under a repro.obs tracer "
                         "(runtime range telemetry on) and write "
                         "DIR/<name>.trace.json + DIR/<name>.jsonl")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    _register()
    names = [n for n in args.only.split(",") if n] or list(BENCHES)

    here = os.path.dirname(os.path.abspath(__file__))
    outdir = os.path.join(here, "results")
    os.makedirs(outdir, exist_ok=True)
    if args.trace:
        from repro import obs
        os.makedirs(args.trace, exist_ok=True)

    print("name,us_per_call,derived")
    for name in names:
        fn = BENCHES[name]
        t0 = time.perf_counter()
        if args.trace:
            with obs.tracing(runtime_ranges=True) as tr:
                rows, derived = fn()
            obs.write_jsonl(tr, os.path.join(args.trace, f"{name}.jsonl"))
            obs.write_chrome_trace(
                tr, os.path.join(args.trace, f"{name}.trace.json"),
                process_name=f"repro:{name}")
        else:
            rows, derived = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},\"{derived}\"", flush=True)
        with open(os.path.join(outdir, f"{name}.json"), "w") as f:
            json.dump({"rows": [list(map(str, r)) for r in rows],
                       "derived": derived, "us_per_call": us}, f, indent=1)


if __name__ == "__main__":
    main()
