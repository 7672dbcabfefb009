"""Bytes per frame from the configuration alone, and the peak table."""
from __future__ import annotations

import json
import os

import pytest

from bench import work

from bench.harness import ROOT


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_dus_1080p_bytes():
    # uint8 frame + Uy (u8.4 -> 2 B) at 1080x1920
    px = 1080 * 1920
    assert work.bytes_per_frame(_config("dus-1080p")) == px + 2 * px \
        == 6_220_800


def test_usm_1080p_bytes():
    # uint8 frame + masked (u9.4 -> 2 B) at 1080x1920
    px = 1080 * 1920
    assert work.bytes_per_frame(_config("usm-1080p")) == px + 2 * px


def test_bytes_follow_the_type_width():
    cfg = {"frame": [2, 4], "plan": {"beta": 4},
           "types": {"a": "u4", "b": "s13", "c": "u28"},
           "outputs": {"a": [2, 4], "b": [1, 2], "c": [1, 1]}}
    # 8 bits -> 1 B, 17 bits -> 3 B, 32 bits -> 4 B
    assert work.bytes_per_frame(cfg) == 8 + 8 + 3 * 2 + 4


def test_peaks_are_keyed_by_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
