"""The command refuses to run without a TPU or without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench.harness import ROOT

ARGS = ["--workload", "dus-1080p.offline", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _printed_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
