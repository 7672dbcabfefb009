"""Reference: Down-Up Sampling (arXiv:1803.02660, Sec. VI-C, Fig. 7).

    Dx = [1, 2, 1] / 4 on img, keeping every 2nd column
    Dy = [1, 2, 1]^T / 4 on Dx, keeping every 2nd row
    Ux = [1, 2, 1] / 4 on Dy with each column repeated twice
    Uy = [1, 2, 1]^T / 4 on Ux with each row repeated twice

Output: Uy, at the frame's size.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.refs.fixed import snap, stencil

BIN3 = [1, 2, 1]


def run(frame: np.ndarray, types: Dict[str, str], beta: int,
        params: Dict[str, float]) -> Dict[str, np.ndarray]:
    """Every stage of one frame, as float64 values on its type's grid."""
    def q(name, x):
        out[name] = snap(x, types[name], beta)
        return out[name]

    out: Dict[str, np.ndarray] = {}
    img = q("img", np.asarray(frame, dtype=np.float64))
    col3 = [[w] for w in BIN3]
    dx = q("Dx", stencil(img, [BIN3], 1 / 4, stride=(1, 2)))
    dy = q("Dy", stencil(dx, col3, 1 / 4, stride=(2, 1)))
    ux = q("Ux", stencil(dy, [BIN3], 1 / 4, expand=(1, 2)))
    q("Uy", stencil(ux, col3, 1 / 4, expand=(2, 1)))
    return out
