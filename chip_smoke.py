#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: plan -> lower -> fused kernel
-> `PipelineServer`, at deployment frame sizes, every frame checked
bit-exact against the numpy oracle (`run_fixed(backend="numpy")`).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # band-sharded path on a 4-chip mesh

Phases with no option:

  * kernel:  dus_ext through `PipelineServer(backend="pallas")`, batch 4,
             at 1920x1080 and 3840x2160 — the fused kernel runs natively
             (never in interpret mode);
  * lowered: usm through `PipelineServer(backend="lowered")`, batch 4,
             at 1920x1080 (its exact datapath keeps f64 stages, which the
             fused kernel does not take).  hcd is left out: XLA:TPU
             compiles its f64 datapath, but the f64 replay of its
             `harris` stage does not reproduce the numpy oracle bit for
             bit (the line printed before the phases says so).

With ``--chips 4`` only the sharded phase runs: dus_ext through
`PipelineServer(backend="sharded")` at 3840x2160 on a 4-device band
mesh, checked island-by-island sharded and bit-exact against the oracle
and against a 1-device mesh.

Frames are random 8-bit images drawn from ``--seed``.  The script exits
non-zero before any phase when JAX finds no TPU, and on any mismatch.
Frame rates it prints are wall-clock smoke readings, not benchmarks.
The last line of its output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
BATCH = 4
FRAMES = 8
SIZES = {"1080p": (1080, 1920), "4k": (2160, 3840)}


def _types(pipe):
    """The benchmark's bit widths: static-analysis alphas, beta = 4."""
    from repro.pipelines import workflows as W
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        alphas, signed = W.static_alphas(pipe)
        return W.types_from_alpha(pipe, alphas, signed,
                                  {n: 4 for n in pipe.stages})


def _frames(rng, n, shape):
    import numpy as np
    return [rng.integers(0, 256, shape).astype(np.float64)
            for _ in range(n)]


def _check(label, outs, frames, pipe, types, params):
    """Every served frame must equal the numpy oracle, stage by stage."""
    import numpy as np
    from repro.dsl.exec import run_fixed
    for i, (out, img) in enumerate(zip(outs, frames)):
        ref = run_fixed(pipe, img, types, params)
        for k in pipe.outputs:
            if not np.array_equal(np.asarray(ref[k]), out[k]):
                raise AssertionError(
                    f"{label}: frame {i} stage {k!r} differs from the "
                    f"oracle")
    return len(outs)


def _serve(label, pipe, params, backend, shape, frames):
    """Warm the (batch, H, W) program, then serve `frames` through
    submit/result and verify each; prints one line per phase."""
    from repro.serve import PipelineServer
    types = _types(pipe)
    with PipelineServer(pipe, types, params, backend=backend,
                        batch_size=BATCH) as srv:
        if getattr(srv._executor, "interpret", False):
            raise AssertionError(f"{label}: the kernel would run in "
                                 f"interpret mode")
        t0 = time.perf_counter()
        srv.warmup([shape])
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        futs = [srv.submit(f) for f in frames]
        outs = [f.result() for f in futs]
        wall = time.perf_counter() - t0
    verified = _check(label, outs, frames, pipe, types, params)
    print(f"phase={label} pipeline={pipe.name} backend={backend} "
          f"shape={shape[1]}x{shape[0]} batch={BATCH} "
          f"frames_verified={verified}/{len(frames)} "
          f"compile_s={compile_s:.2f} smoke_fps={len(frames) / wall:.2f}",
          flush=True)


def one_chip(seed: int) -> None:
    import numpy as np
    from repro.pipelines import dus, usm
    print("phase=lowered/hcd skipped: XLA:TPU's f64 replay of hcd's "
          "'harris' stage (det - 0.04*trace^2) differs from the numpy "
          "oracle; hcd waits for a fixed-point finish without f64",
          flush=True)
    rng = np.random.default_rng(seed)
    for size in ("1080p", "4k"):
        shape = SIZES[size]
        _serve(f"kernel/{size}", dus.build_extended(), {}, "pallas", shape,
               _frames(rng, FRAMES, shape))
    shape = SIZES["1080p"]
    _serve("lowered/usm", usm.build(), dict(usm.DEFAULT_PARAMS), "lowered",
           shape, _frames(rng, FRAMES, shape))


def four_chips(seed: int) -> None:
    """dus_ext band-sharded over 4 devices vs the oracle and 1 device."""
    import numpy as np

    from repro import obs
    from repro.launch.mesh import make_band_mesh
    from repro.lowering import compile_pipeline
    from repro.pipelines import dus
    from repro.serve import PipelineServer

    pipe = dus.build_extended()
    types = _types(pipe)
    shape = SIZES["4k"]
    frames = _frames(np.random.default_rng(seed), BATCH, shape)
    with obs.tracing() as tr:
        with PipelineServer(pipe, types, {}, backend="sharded",
                            batch_size=BATCH) as srv:
            t0 = time.perf_counter()
            srv.warmup([shape])
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            outs = [f.result() for f in [srv.submit(f) for f in frames]]
            wall = time.perf_counter() - t0
    spans = tr.spans("exec.sharded")
    if not spans or any(s.attrs.get("shards") != 4
                        or s.attrs.get("sharded_islands")
                        != s.attrs.get("islands") for s in spans):
        raise AssertionError(
            "sharded: not every island ran band-sharded over 4 devices: "
            f"{[dict(s.attrs) for s in spans]}")
    verified = _check("sharded/4k", outs, frames, pipe, types, {})
    single = compile_pipeline(pipe, types, backend="sharded",
                              mesh=make_band_mesh(1))(np.stack(frames))
    for k in pipe.outputs:
        for b, out in enumerate(outs):
            if not np.array_equal(single[k][b], out[k]):
                raise AssertionError(f"sharded/4k: frame {b} stage {k!r} "
                                     f"differs between 4 and 1 devices")
    a = spans[-1].attrs
    print(f"phase=sharded/4k pipeline={pipe.name} backend=sharded "
          f"shape={shape[1]}x{shape[0]} batch={BATCH} shards=4 "
          f"islands={a['islands']} sharded_islands={a['sharded_islands']} "
          f"frames_verified={verified}/{len(frames)} "
          f"matches_1_device=True compile_s={compile_s:.2f} "
          f"smoke_fps={len(frames) / wall:.2f}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
