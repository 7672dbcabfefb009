"""Fixtures of the benchmark's tests: the repo root on the path, and
tiny CPU copies of the benchmark for driving whole runs without a chip."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = (64, 128)


def tiny_copy(dst: str) -> dict:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst`` with every
    configuration cut to a ``TINY`` frame served by ``lowered``; returns
    the copied spec."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = os.path.join(dst, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        (h, w), (th, tw) = cfg["frame"], TINY
        cfg["frame"] = list(TINY)
        cfg["outputs"] = {k: [oh * th // h, ow * tw // w]
                          for k, (oh, ow) in cfg["outputs"].items()}
        cfg["backends"] = ["lowered"]
        with open(path, "w") as f:
            json.dump(cfg, f)
    return spec


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A tiny benchmark copy to run on the CPU, with the persistent
    compilation cache left off (runs here must not write one)."""
    import jax
    from repro import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: "off (test)")
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    spec = tiny_copy(str(tmp_path))
    yield str(tmp_path), spec
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
