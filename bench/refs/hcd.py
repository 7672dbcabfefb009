"""Reference: Harris Corner Detection (arXiv:1803.02660, Sec. VI-B,
Fig. 3, Table I).

    Ix, Iy        = Sobel x / y derivatives of img, scaled by 1/12
    Ixx, Ixy, Iyy = Ix * Ix, Ix * Iy, Iy * Iy
    Sxx, Sxy, Syy = 3x3 box sums of Ixx, Ixy, Iyy
    det           = Sxx * Syy - Sxy * Sxy
    trace         = Sxx + Syy
    harris        = det - 0.04 * trace * trace

Each stage is IEEE float64 arithmetic on its snapped inputs, then
snapped onto its own grid; ``0.04 * trace^2`` is one rounded double
multiply and ``det - .`` one rounded double subtract, as written.
Output: harris, at the frame's size.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.refs.fixed import snap, stencil

SOBEL_X = [[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]]
SOBEL_Y = [[-1, -2, -1], [0, 0, 0], [1, 2, 1]]
BOX3 = [[1, 1, 1], [1, 1, 1], [1, 1, 1]]
K = 0.04


def run(frame: np.ndarray, types: Dict[str, str], beta: int,
        params: Dict[str, float]) -> Dict[str, np.ndarray]:
    """Every stage of one frame, as float64 values on its type's grid."""
    def q(name, x):
        out[name] = snap(x, types[name], beta)
        return out[name]

    out: Dict[str, np.ndarray] = {}
    img = q("img", np.asarray(frame, dtype=np.float64))
    ix = q("Ix", stencil(img, SOBEL_X, 1 / 12))
    iy = q("Iy", stencil(img, SOBEL_Y, 1 / 12))
    sxx = q("Sxx", stencil(q("Ixx", ix * ix), BOX3, 1.0))
    sxy = q("Sxy", stencil(q("Ixy", ix * iy), BOX3, 1.0))
    syy = q("Syy", stencil(q("Iyy", iy * iy), BOX3, 1.0))
    det = q("det", sxx * syy - sxy * sxy)
    trace = q("trace", sxx + syy)
    q("harris", det - K * (trace * trace))
    return out
