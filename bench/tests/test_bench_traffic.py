"""The open loop's arrivals: one frame per stream per period, each
stream's phase drawn from the seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import traffic

MIX = {"loop": "open", "streams": 4, "rate_fps": 8.0}


def _due(seed, seconds=10.0):
    return traffic.Generator(MIX, 4, seed, 32).schedule(0.0, seconds)


def test_each_stream_sends_one_frame_a_period():
    due = np.array(_due(2**31 + 9))
    period = MIX["streams"] / MIX["rate_fps"]
    # 20 periods in the window; a stream whose phase falls in the last
    # lead-in of the window sends one frame fewer
    assert 79 <= len(due) <= 80 and np.all(np.diff(due) >= 0)
    phases = np.sort(np.round(np.mod(due, period), 9))
    assert len(np.unique(phases)) == MIX["streams"]
    for p in np.unique(phases):
        steps = np.diff(due[np.isclose(np.mod(due, period), p)])
        assert np.allclose(steps, period)


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**33 + 1])
def test_phases_are_drawn_from_the_seed(seed):
    assert _due(seed) == _due(seed)
    assert _due(seed) != _due(seed + 1)
    period = MIX["streams"] / MIX["rate_fps"]
    first = np.array(_due(seed)[:MIX["streams"]])
    assert np.all((first >= 0) & (first < period + 0.011))
