"""How `correct` is decided: served frames against the plain reference.

During the window `Sample` keeps the served outputs of ``SAMPLE_FRAMES``
frames, drawn from the seed by reservoir sampling over every frame the
window completed, in submission order.  After the window `compare` runs
the configuration's plain reference (``bench/refs/*.py``) on each kept
frame's pool image and counts the output pixels that differ, over every
stage the server returned and every output the configuration declares.
The system's guarantee is bit-exactness against the fixed-point
definition, so the comparison is exact: a run is correct when no pixel
differs, no frame failed or was lost, and at least one frame was
compared.

The control (`control_readings`) puts the reference computed with one
fractional bit fewer on every stage (beta - 1, the nearest lower
precision of a fixed-point plan) in the program's place.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

SAMPLE_FRAMES = 8


class Sample:
    """Seeded reservoir of (pool index, served outputs)."""

    def __init__(self, seed: int, size: int = SAMPLE_FRAMES):
        self.rng = np.random.default_rng([seed, 2])
        self.size = size
        self.seen = 0
        self.kept: List[Tuple[int, Dict[str, np.ndarray]]] = []

    def offer(self, frame, outputs: Dict[str, np.ndarray]) -> None:
        i, self.seen = self.seen, self.seen + 1
        if i < self.size:
            self.kept.append((frame.pool_idx, outputs))
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.size:
            self.kept[j] = (frame.pool_idx, outputs)


class Verdict:
    def __init__(self, mismatched_px: int, failed: int, compared: int):
        self.mismatched_px = mismatched_px
        self.failed = failed
        self.compared = compared
        self.correct = mismatched_px == 0 and failed == 0 and compared >= 1

    def limits(self) -> Dict[str, Dict[str, int]]:
        """Each number compared beside its limit (``max`` or ``min``)."""
        return {"mismatched_px": {"value": self.mismatched_px, "max": 0},
                "failed_frames": {"value": self.failed, "max": 0},
                "frames_compared": {"value": self.compared, "min": 1}}



def lines(limits: Dict[str, Dict[str, int]]) -> List[str]:
    """``limits`` as the plain lines a run ends its standard error with."""
    return [f"check {k}={v['value']} "
            + " ".join(f"{b}={v[b]}" for b in ("max", "min") if b in v)
            for k, v in limits.items()]


def reference_outputs(reference: Callable, frame: np.ndarray, config: dict,
                      beta_delta: int = 0) -> Dict[str, np.ndarray]:
    return reference(frame, config["types"],
                     config["plan"]["beta"] + beta_delta, config["params"])


def count_mismatches(served: Dict[str, np.ndarray],
                     expected: Dict[str, np.ndarray],
                     declared: List[str]) -> int:
    """Pixels of ``served`` that differ from ``expected``; a declared
    output that is missing, or a stage of the wrong shape or unknown to
    the reference, counts in full."""
    n = sum(int(np.asarray(expected[k]).size) for k in declared
            if k not in served)
    for k, v in served.items():
        v = np.asarray(v)
        r = expected.get(k)
        if r is None or v.shape != r.shape:
            n += int(v.size)
        else:
            n += int(np.count_nonzero(v != r))
    return n


def compare(kept, pool: np.ndarray, reference: Callable, config: dict,
            failed: int) -> Verdict:
    refs: Dict[int, Dict[str, np.ndarray]] = {}
    mismatched = 0
    for pool_idx, served in kept:
        if pool_idx not in refs:
            refs[pool_idx] = reference_outputs(reference, pool[pool_idx],
                                               config)
        mismatched += count_mismatches(served, refs[pool_idx],
                                       list(config["outputs"]))
    return Verdict(mismatched, failed, len(kept))


def control_readings(pool: np.ndarray, reference: Callable, config: dict,
                     frames: int = SAMPLE_FRAMES) -> int:
    """mismatched_px of the control: the reference at beta - 1 served in
    the program's place, on the pool's first ``frames`` frames."""
    kept = [(i, reference_outputs(reference, pool[i], config, -1))
            for i in range(min(frames, len(pool)))]
    return compare(kept, pool, reference, config, 0).mismatched_px
