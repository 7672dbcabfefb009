"""Plain numpy fixed-point arithmetic shared by the pipeline references.

The references restate each benchmarked pipeline stage by stage from
its published definition (arXiv:1803.02660, Sec. VI) and import nothing
of the program under test.  Every stage value is a float64 array on the
grid of its fixed-point type: the exact result of the stage's arithmetic
on its (already snapped) inputs, rounded half-to-even onto multiples of
2**-beta and saturated to the type's range.

A type is written ``"u8"`` or ``"s9"``: signedness and integral bits
(alpha); the fractional bits (beta) are given separately.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def int_range(ty: str, beta: int) -> Tuple[int, int]:
    """Scaled-integer bounds of type ``ty`` with ``beta`` fractional bits."""
    signed, alpha = ty[0] == "s", int(ty[1:])
    width = alpha + beta
    if signed:
        return -(1 << (width - 1)), (1 << (width - 1)) - 1
    return 0, (1 << width) - 1


def snap(x: np.ndarray, ty: str, beta: int) -> np.ndarray:
    """Round half-to-even onto the 2**-beta grid, saturating to ``ty``."""
    lo, hi = int_range(ty, beta)
    step = 2.0 ** beta
    return np.clip(np.rint(x * step), lo, hi) / step


def stencil(a: np.ndarray, weights: Sequence[Sequence[float]], scale: float,
            stride: Tuple[int, int] = (1, 1),
            expand: Tuple[int, int] = (1, 1)) -> np.ndarray:
    """``scale * sum_k w_k * a[y + dy_k, x + dx_k]`` on the centred taps.

    The input is first nearest-expanded by ``expand`` (each pixel
    repeated), borders replicate the edge pixel, and the output keeps
    every ``stride``-th row and column starting at 0.
    """
    uy, ux = expand
    if uy > 1 or ux > 1:
        a = np.repeat(np.repeat(a, uy, axis=0), ux, axis=1)
    rows, cols = len(weights), len(weights[0])
    cy, cx = rows // 2, cols // 2
    h, w = a.shape
    p = np.pad(a, ((cy, cy), (cx, cx)), mode="edge")
    sy, sx = stride
    acc = None
    for r, row in enumerate(weights):
        for c, wt in enumerate(row):
            tap = p[r: r + h: sy, c: c + w: sx]
            term = tap if wt == 1 else wt * tap
            acc = term if acc is None else acc + term
    return scale * acc
