"""Reference: Unsharp Mask (arXiv:1803.02660, Listing 1, Fig. 1).

    blurx   = [1, 4, 6, 4, 1]^T / 16 on img (vertical)
    blury   = [1, 4, 6, 4, 1] / 16 on blurx (horizontal)
    sharpen = img * (1 + weight) - blury * weight
    masked  = max(img if |img - blury| < thresh else sharpen, 0)
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.refs.fixed import snap, stencil

BIN5 = [1, 4, 6, 4, 1]


def run(frame: np.ndarray, types: Dict[str, str], beta: int,
        params: Dict[str, float]) -> Dict[str, np.ndarray]:
    """Every stage of one frame, as float64 values on its type's grid."""
    def q(name, x):
        out[name] = snap(x, types[name], beta)
        return out[name]

    out: Dict[str, np.ndarray] = {}
    weight, thresh = params["weight"], params["thresh"]
    img = q("img", np.asarray(frame, dtype=np.float64))
    blurx = q("blurx", stencil(img, [[w] for w in BIN5], 1 / 16))
    blury = q("blury", stencil(blurx, [BIN5], 1 / 16))
    sharpen = q("sharpen", img * (1 + weight) + blury * (-weight))
    q("masked", np.maximum(
        np.where(np.abs(img - blury) < thresh, img, sharpen), 0.0))
    return out
