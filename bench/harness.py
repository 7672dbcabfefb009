"""One run of one benchmark cell, driven by data.

`BENCHMARK.json` names each cell's configuration, traffic mix and
metrics; everything particular to one of them sits in a file of its own
that this module finds by that name:

  * configuration: the JSON file the cell's config entry names (the
    pipeline builder, its params, frame size, plan recipe, batch size,
    backend order and the file of its plain reference);
  * traffic mix:   ``bench/traffic/<traffic>.json``, read by the one
    generator in `bench.traffic`;
  * metric:        ``bench/metrics/<metric>.py``, whose ``read(run)``
    returns the metric's value from the run record, or None when the
    run holds nothing it can read.

A run builds the system through its normal entry points (plan ->
`PipelineServer` -> `submit` / `Future.result`), warms its one
``(batch, H, W)`` shape, measures a window of ``seconds`` seconds, then
checks a seeded sample of the served frames against the plain
reference (`bench.check`).  `run_cell` returns the result line's
object; `bench/run.py` prints it.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import resource
import shutil
import tempfile
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import check, traffic as traffic_mod, work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# frames drawn per run from the seed and cycled through the window
POOL_FRAMES = 32


class Cell:
    """A workload entry resolved to its files (all paths under ``root``)."""

    def __init__(self, spec: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.root = root
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = _load_json(os.path.join(
            root, "bench", "traffic", self.traffic_name + ".json"))

    def metrics(self, traced: bool) -> List[dict]:
        """This cell's end-to-end metrics (untraced) or per-layer ones."""
        e2e = [m for m in self.spec["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        if not traced:
            return e2e
        mine = {m["name"] for m in e2e}

        def applies(m):
            if "workloads" in m:
                return self.name in m["workloads"]
            return m["moves"] in mine

        return [m for m in self.spec["per_layer"] if applies(m)]

    def reader(self, metric: str) -> Callable:
        return load_file(os.path.join(self.root, "bench", "metrics",
                                      metric + ".py")).read

    def reference(self) -> Callable:
        return load_file(os.path.join(self.root,
                                      self.config["reference"])).run


def load_spec(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file(path: str):
    """Import a Python file of the benchmark by its path."""
    name = "bench_file_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(target: str):
    """``"package.module:attr"`` -> the attribute."""
    mod, _, attr = target.partition(":")
    return getattr(importlib.import_module(mod), attr)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_plan(config: dict):
    """Pipeline, per-stage types and params, by the config's recipe."""
    from repro.analysis import run_plan
    pipe = resolve(config["pipeline"])()
    plan_cfg = config["plan"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        plan = run_plan(pipe, plan_cfg["passes"],
                        betas={n: plan_cfg["beta"] for n in pipe.stages})
        types = plan.types()
    return pipe, types, dict(config["params"])


def open_server(pipe, types, params, config: dict, log: Callable):
    """The first backend in the config's order whose warm-up lowers.

    A config lists more than one only where its plan may move onto the
    kernel later (usm is 64-bit today); the result line's ``device``
    names the backend that served."""
    from repro.lowering import LoweringError
    from repro.serve import PipelineServer
    shape = tuple(config["frame"])
    for backend in config["backends"]:
        srv = None
        try:
            srv = PipelineServer(pipe, types, params, backend=backend,
                                 batch_size=config["batch_size"])
            srv.warmup([shape])
        except LoweringError as e:
            if srv is not None:
                srv.close()
            log(f"backend={backend} refused: {str(e).splitlines()[0]}")
            continue
        return srv, backend
    raise RuntimeError(f"no backend of {config['backends']} lowers "
                       f"{pipe.name!r}")


def program_files(root: str) -> set:
    """Basenames of the program's Python files (``src/repro``)."""
    out = set()
    for _, _, files in os.walk(os.path.join(root, "src", "repro")):
        out.update(f for f in files
                   if f.endswith(".py") and f != "__init__.py")
    return out


def frame_pool(seed: int, shape, n: int = POOL_FRAMES) -> np.ndarray:
    """``n`` random 8-bit frames from the seed (camera frames as sent)."""
    rng = np.random.default_rng([seed, 0])
    return rng.integers(0, 256, (n,) + tuple(shape), dtype=np.uint8)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    """What one run recorded; the metric readers' only input.

    ``frames`` holds every frame of the window in submission order
    (`traffic.Frame`: due, submitted and done times on
    `time.perf_counter`, and whether it failed).  ``spans`` is the
    program's `repro.obs` spans of the window (traced runs only),
    ``trace`` the device trace's `bench.trace.Summary` (traced runs
    only)."""

    def __init__(self, cell: Cell, device_kind: str):
        self.cell = cell
        self.config = cell.config
        self.device_kind = device_kind
        self.t0 = self.t1 = 0.0
        self.frames: List[traffic_mod.Frame] = []
        self.spans: Optional[list] = None
        self.trace = None
        self.setup_s = 0.0

    def done_in_window(self) -> List[traffic_mod.Frame]:
        """The frames whose results had returned when the window closed."""
        return [f for f in self.frames if f.done_by_close]

    def due_in_window(self) -> List[traffic_mod.Frame]:
        """The frames the window sent: every frame of an open loop due
        in it, every frame a closed loop submitted in it."""
        return [f for f in self.frames if self.t0 <= f.due < self.t1]

    def work_bytes_per_frame(self) -> int:
        return work.bytes_per_frame(self.config)

    def peaks(self) -> dict:
        return work.peaks(self.device_kind)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, log: Callable = print) -> Dict[str, Any]:
    """Set up, measure, check; returns the result line's object."""
    import jax
    from repro import compile_cache, obs
    from repro.dsl import exec as dsl_exec

    log(f"compile cache: {compile_cache.enable()}")
    # keep every program in the persistent cache, however quick it was to
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = jax.devices()[0]
    run = Run(cell, device.device_kind)
    config = cell.config
    shape = tuple(config["frame"])
    pipe, types, params = build_plan(config)
    srv, backend = open_server(pipe, types, params, config, log)
    log(f"cell={cell.name} pipeline={pipe.name} backend={backend} "
        f"frame={shape[1]}x{shape[0]} batch={config['batch_size']} "
        f"traffic={cell.traffic_name} seed={seed} seconds={seconds}")
    pool = frame_pool(seed, shape)
    sample = check.Sample(seed)
    gen = traffic_mod.Generator(cell.traffic, config["batch_size"], seed,
                                len(pool))
    compiles = _CompileCounter()
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        with srv:
            # real frames through the whole served path, untimed: first
            # transfers and host buffers belong to set-up
            gen.warm(srv, pool)
            with (obs.tracing() if traced
                  else contextlib.nullcontext()) as tracer:
                if traced:
                    # python_tracer_level=1: the program's Python
                    # functions name the device's idle gaps
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 1
                    jax.profiler.start_trace(tmp, profiler_options=opts)
                try:
                    run.setup_s = time.perf_counter() - t_start
                    ru0 = resource.getrusage(resource.RUSAGE_SELF)
                    with compiles:
                        run.t0, run.t1, run.frames = gen.measure(
                            srv, pool, seconds, sample.offer,
                            annotate=traced)
                    ru1 = resource.getrusage(resource.RUSAGE_SELF)
                finally:
                    if traced:
                        jax.profiler.stop_trace()
                if traced:
                    run.spans = tracer.spans()
            mem = device.memory_stats() or {}
        del srv
        dsl_exec.clear_executor_cache()
        if traced:
            from bench import trace as trace_mod
            run.trace = trace_mod.summarize(
                trace_mod.load(tmp), run.spans, gen.window_marks,
                program_files(cell.root))
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    attempted = run.due_in_window()
    failed = sum(f.failed for f in attempted)
    late = gen.lateness_ms(run.frames, run.t0, run.t1)
    log(f"window: attempted={len(attempted)} failed={failed} "
        f"done_in_window={len(run.done_in_window())} "
        f"compiles_in_window={compiles.count} "
        f"generator_late_ms p50={late[0]:.3f} max={late[1]:.3f}")
    # a stall of the whole process shows as a long wait between results
    # and, where the host took the CPU away, as involuntary switches
    done = sorted(f.t_done for f in run.frames
                  if f.t_done is not None and run.t0 <= f.t_done <= run.t1)
    gap = max(np.diff(done), default=0.0) * 1e3
    log(f"host: longest_wait_between_results_ms={gap:.1f} "
        f"cpu_user_s={ru1.ru_utime - ru0.ru_utime:.2f} "
        f"cpu_sys_s={ru1.ru_stime - ru0.ru_stime:.2f} "
        f"major_faults={ru1.ru_majflt - ru0.ru_majflt} "
        f"involuntary_switches={ru1.ru_nivcsw - ru0.ru_nivcsw}")
    verdict = check.compare(sample.kept, pool, cell.reference(), config,
                            failed)
    metrics = {}
    for m in cell.metrics(traced):
        v = cell.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
           "backend": backend}
    result: Dict[str, Any] = {
        "correct": verdict.correct, "attempted": len(attempted),
        "failed": failed, "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["check"] = verdict.limits()
    return result


class _CompileCounter:
    """Counts XLA compilations while active (there should be none)."""

    def __init__(self):
        self.count = 0
        self._on = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **_):
        if self._on and "backend_compile" in event:
            self.count += 1

    def __enter__(self):
        self._on = True

    def __exit__(self, *exc):
        self._on = False
        return False
