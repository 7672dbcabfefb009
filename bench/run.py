#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  The run sets
up the cell's server (`bench.harness`), measures ``--seconds`` seconds
of its traffic, checks a seeded sample of the served frames against the
plain reference, and prints, last on standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device`` and, traced,
``breakdown``; its last key, ``check``, holds each number compared
beside its limit, which are also the last lines on standard error.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, and when the checkout holds no program
(``src/repro``).  It never falls back to the CPU.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import check, harness
    cell = harness.Cell(harness.load_spec(ROOT), args.workload, ROOT)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX finds no device: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < cell.entry["chips"]:
        print(f"bench: {cell.name} needs {cell.entry['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              log=lambda m: print(m, flush=True))
    sys.stdout.flush()
    for line in check.lines(result["check"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
