"""Work per frame, from the configuration alone, and the peak table.

`bytes_per_frame` is the least HBM traffic any implementation of the
configured pipeline needs per frame: the frame as submitted (8-bit, one
byte a pixel) read once, and each declared output written once at its
own shape in the least whole number of bytes that holds its type
(alpha + beta bits).  It does not depend on how the program splits the
work into kernels, stages or islands, so it cannot go stale when they
change.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def bytes_per_frame(config: dict) -> int:
    h, w = config["frame"]
    beta = config["plan"]["beta"]
    total = h * w
    for name, (oh, ow) in config["outputs"].items():
        bits = int(config["types"][name][1:]) + beta
        total += -(-bits // 8) * oh * ow
    return total


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device
    is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]
