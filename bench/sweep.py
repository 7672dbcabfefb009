#!/usr/bin/env python3
"""Find the highest open-loop rate a cell's server sustains (on the chip).

    python3 bench/sweep.py --workload dus-1080p.live --seed 7 \\
        --seconds 30 --rates 10,12,14,16

One process sets up the cell's server once, then offers each rate for
``--seconds`` seconds with the cell's traffic mix (its ``rate_fps``
replaced) and drains the queue before the next rate.  Per rate it prints
one JSON line: frames offered and completed in the window, the backlog
(frames sent but not served) when the window closed, latency p50/p95
from due time, and the growth of latency from the window's first third
to its last.  A rate is sustained when the backlog at the close stays
within two batches and latency grows by no more than a tenth of its
median.  The cell's rate is then set, once, to 0.8 x the highest rate
sustained, taken no higher than the rate the closed loop of the same
configuration completes (its offline cell's ``frames_per_s``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np
    from bench import harness, traffic
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    from repro import compile_cache
    compile_cache.enable()
    cell = harness.Cell(harness.load_spec(ROOT), args.workload, ROOT)
    cfg = cell.config
    pipe, types, params = harness.build_plan(cfg)
    srv, backend = harness.open_server(pipe, types, params, cfg, print)
    pool = harness.frame_pool(args.seed, cfg["frame"])
    batch = cfg["batch_size"]
    with srv:
        traffic.Generator(cell.traffic, batch, args.seed, len(pool)).warm(
            srv, pool)
        print(f"sweep cell={cell.name} backend={backend} "
              f"setup_s={time.perf_counter() - T_START:.2f}", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic, rate_fps=rate)
            gen = traffic.Generator(mix, batch, args.seed, len(pool))
            t0, t1, frames = gen.measure(srv, pool, args.seconds,
                                         lambda f, out: None)
            due = [f for f in frames if t0 <= f.due < t1]
            lat = np.array([(f.t_done - f.due) * 1e3 for f in due
                            if f.t_done is not None])
            backlog = sum(f.t_done is None or f.t_done > t1 for f in due)
            third = max(len(lat) // 3, 1)
            growth = float(lat[-third:].mean() - lat[:third].mean())
            p50 = float(np.percentile(lat, 50))
            print(json.dumps({
                "rate_fps": rate, "offered": len(due),
                "done_in_window": sum(1 for f in due if f.t_done is not None
                                      and f.t_done <= t1),
                "backlog_at_close": backlog,
                "latency_p50_ms": p50,
                "latency_p95_ms": float(np.percentile(lat, 95)),
                "latency_growth_ms": growth,
                "sustained": backlog <= 2 * batch and growth <= 0.1 * p50,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
