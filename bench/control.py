#!/usr/bin/env python3
"""Readings of the control: the plain reference computed with one
fractional bit fewer on every stage (beta - 1), put in the program's
place and compared exactly as a run's served frames are.

    python3 bench/control.py --workload dus-1080p.offline --seeds 1 2 3

For each seed it draws the cell's frame pool and prints one JSON line
with the control's ``mismatched_px`` over `check.SAMPLE_FRAMES` frames
at the cell's own frame size.  The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import check, harness
    cell = harness.Cell(harness.load_spec(ROOT), args.workload, ROOT)
    for seed in args.seeds:
        pool = harness.frame_pool(seed, cell.config["frame"])
        n = check.control_readings(pool, cell.reference(), cell.config)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_mismatched_px": n,
                          "frames": check.SAMPLE_FRAMES}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
