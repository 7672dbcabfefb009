"""`repro.obs` unit + integration battery (docs/observability.md).

Covers: span nesting / attributes / thread safety, the CounterGroup shim
behind the legacy stat dicts, both exporter schemas (JSONL round-trip and
Chrome trace-event JSON), runtime range telemetry on a deliberately
saturating synthetic residue plan, the tracing-on vs tracing-off
bit-exactness guarantee of the lowered backends, SMT budget-exhaustion
visibility (warning + event + plan provenance note), and one end-to-end
traced compile of HCD matching the acceptance trace content.
"""
import json
import threading
import warnings

import numpy as np
import pytest

from repro import obs
from repro.analysis import run_plan
from repro.analysis import driver as D
from repro.core.interval import Interval
from repro.core.range_analysis import StageRange
from repro.dsl.exec import run_fixed
from repro.obs import report
from repro.pipelines import dus, hcd, usm
from repro.smt import BudgetExhaustedWarning, SMTConfig, analyze_smt
from repro.smt import solver as S


# ---------------------------------------------------------------------------
# spans + counters
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    with obs.tracing() as tr:
        with obs.span("outer", k=1) as o:
            with obs.span("inner") as i:
                i.set(found=True)
            o.set(done=2)
    outer, = tr.spans("outer")
    inner, = tr.spans("inner")
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert outer.attrs == {"k": 1, "done": 2}
    assert inner.attrs == {"found": True}
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_span_exception_records_error_and_unwinds():
    with obs.tracing() as tr:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        assert tr.current_span() is None        # stack unwound
    sp, = tr.spans("boom")
    assert sp.attrs["error"] == "ValueError"


def test_event_attaches_to_current_span():
    with obs.tracing() as tr:
        with obs.span("parent") as p:
            obs.event("marker", reason="test")
        obs.event("orphan")
    ev, = tr.events("marker")
    assert ev["parent"] == p.span_id
    assert ev["attrs"] == {"reason": "test"}
    assert tr.events("orphan")[0]["parent"] is None


def test_disabled_tracing_is_shared_noop():
    assert not obs.is_enabled()
    s1, s2 = obs.span("a", x=1), obs.span("b")
    assert s1 is s2                             # one shared null object
    with s1 as sp:
        assert sp.set(k=2) is sp                # fully inert
    obs.event("nothing")                        # no-op, no error
    with obs.span("nothing") as sp:             # no tracer, nothing recorded
        obs.event("nothing.inside", v=1.0)
    assert sp is s1 and obs.active_tracer() is None
    assert obs.runtime.record_stage("x", np.zeros((2, 2))) is None


def test_span_thread_safety():
    with obs.tracing() as tr:
        def work(i):
            with obs.span("thread.outer", idx=i):
                for j in range(5):
                    with obs.span("thread.inner", idx=i, j=j):
                        pass
        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    outers = tr.spans("thread.outer")
    inners = tr.spans("thread.inner")
    assert len(outers) == 4 and len(inners) == 20
    ids = [s.span_id for s in outers + inners]
    assert len(set(ids)) == len(ids)            # unique ids across threads
    # every inner's parent is its own worker's outer, never another worker's
    # (key by the idx attr: OS thread idents can be reused across workers)
    outer_of = {s.attrs["idx"]: s.span_id for s in outers}
    for s in inners:
        assert s.parent_id == outer_of[s.attrs["idx"]]


def test_spans_are_profiler_host_events(tmp_path):
    """A served batch's spans land in a running JAX profiler's trace as
    ``/host:`` events under their own names: one clock with the device."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from repro.pipelines import workflows as W
    from repro.serve import PipelineServer, serve_offline
    pipe = usm.build()
    alphas, signed = W.static_alphas(pipe)
    frames = list(np.random.default_rng(5).integers(
        0, 256, (4, 32, 32)).astype(np.uint8))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        types = W.types_from_alpha(pipe, alphas, signed,
                                   {n: 4 for n in pipe.stages})
        with PipelineServer(pipe, types, dict(usm.DEFAULT_PARAMS),
                            backend="lowered", batch_size=4) as srv:
            srv.warmup([(32, 32)])
            with obs.tracing() as tr:
                jax.profiler.start_trace(str(tmp_path))
                try:
                    serve_offline(srv, frames)
                finally:
                    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    want = {"serve.submit", "serve.batch", "exec.lowered", "exec.h2d",
            "exec.dispatch", "exec.device_wait", "exec.d2h"}
    assert want <= host
    assert want <= {s.name for s in tr.spans()}


def test_tracing_without_jax_profiler(monkeypatch):
    # repro.obs needs no JAX: with none to import, spans are plain spans
    import sys
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    with obs.tracing() as tr:
        with obs.span("plain"):
            pass
    assert tr.annotation is None
    assert [s.name for s in tr.spans()] == ["plain"]


def test_counter_group_semantics():
    g = obs.CounterGroup("test.group", hits=0, secs=0.0)
    assert isinstance(g, dict) and g["hits"] == 0   # dict-compatible reads
    g.add("hits")
    g.add("secs", 0.5)
    g.add("extra", 3)
    assert g["hits"] == 1 and g["secs"] == 0.5 and g["extra"] == 3
    assert obs.all_counters()["test.group"] == dict(g)
    g.reset()
    assert dict(g) == {"hits": 0, "secs": 0.0}      # extras dropped
    assert g.snapshot() == {"hits": 0, "secs": 0.0}


def test_legacy_stat_dicts_are_counter_groups():
    # the three legacy module globals are byte-compatible CounterGroup shims
    for shim, name in [(D.MEMO_STATS, "analysis.memo"),
                       (D.DISK_CACHE_STATS, "analysis.disk_cache"),
                       (S.STATS, "smt.solver")]:
        assert isinstance(shim, obs.CounterGroup)
        assert shim.name == name
        assert obs.all_counters()[name] == dict(shim)
    assert set(S.STATS) == {"boxes", "secs"}
    boxes0 = S.STATS["boxes"]
    S.STATS.add("boxes", 0)                     # locked mutation available
    assert S.STATS["boxes"] == boxes0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _tiny_trace():
    with obs.tracing(runtime_ranges=True) as tr:
        with obs.span("a.outer", k=1):
            with obs.span("a.inner", iv=Interval(0.0, 1.0)):
                obs.event("a.mark", note="hi")
            obs.event("a.sample", value=2.5)
    return tr


def test_jsonl_round_trip(tmp_path):
    tr = _tiny_trace()
    path = tmp_path / "t.jsonl"
    obs.write_jsonl(tr, path)
    recs = obs.load_jsonl(path)
    assert recs[0]["kind"] == "meta" and recs[0]["runtime_ranges"] is True
    assert recs[-1]["kind"] == "counters"
    assert "smt.solver" in recs[-1]["values"]
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert set(spans) == {"a.outer", "a.inner"}
    inner = spans["a.inner"]
    assert inner["parent"] == spans["a.outer"]["id"]
    assert inner["dur_us"] >= 0 and inner["ts_us"] >= 0
    assert isinstance(inner["attrs"]["iv"], str)    # repr-sanitized Interval
    ev, sample = [r for r in recs if r["kind"] == "event"]
    assert ev["name"] == "a.mark" and ev["parent"] == inner["id"]
    assert sample["name"] == "a.sample"
    assert sample["parent"] == spans["a.outer"]["id"]
    assert sample["attrs"] == {"value": 2.5}


def test_chrome_trace_schema(tmp_path):
    tr = _tiny_trace()
    path = tmp_path / "t.trace.json"
    obs.write_chrome_trace(tr, path, process_name="repro-test")
    with open(path) as f:
        doc = json.load(f)                      # valid JSON document
    ev = doc["traceEvents"]
    assert ev[0] == {"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
                     "args": {"name": "repro-test"}}
    phs = {e["ph"] for e in ev}
    assert phs == {"M", "X", "i"}
    xs = [e for e in ev if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a.outer", "a.inner"}
    for e in xs:                                # perfetto-required fields
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["cat"] == "a"
    assert any(e["ph"] == "i" and e["name"] == "a.mark" for e in ev)
    assert any(e["ph"] == "i" and e["args"] == {"value": 2.5} for e in ev)
    assert doc["otherData"]["counters"].keys() >= {"smt.solver"}


def test_jsonable_handles_numpy_and_nonfinite():
    from repro.obs.exporters import _jsonable
    assert _jsonable(np.int64(3)) == 3
    assert _jsonable(np.float64(0.5)) == 0.5
    assert _jsonable(float("inf")) == "inf"
    assert _jsonable((1, 2)) == [1, 2]
    assert _jsonable({1: np.int32(2)}) == {"1": 2}


# ---------------------------------------------------------------------------
# runtime range telemetry
# ---------------------------------------------------------------------------

def test_record_stage_ranges_saturation_headroom():
    from repro.core.fixedpoint import FixedPointType
    t = FixedPointType(8, 0, True)
    v = np.array([[t.max_value, t.min_value, 0.0, 1.0]])
    with obs.tracing(runtime_ranges=True) as tr:
        attrs = obs.runtime.record_stage("s", v, t, backend="test")
    assert attrs["min"] == t.min_value and attrs["max"] == t.max_value
    assert attrs["sat_hi"] == 1 and attrs["sat_lo"] == 1 and attrs["sat"] == 2
    assert attrs["alpha_plan"] == 8
    assert attrs["headroom"] == attrs["alpha_plan"] - attrs["alpha_obs"]
    ev, = tr.events("rt.range")
    assert ev["attrs"] == attrs


def test_record_stage_unsigned_zero_not_saturation():
    from repro.core.fixedpoint import FixedPointType
    t = FixedPointType(8, 0, False)
    with obs.tracing(runtime_ranges=True):
        attrs = obs.runtime.record_stage("s", np.zeros((4, 4)), t)
    # unsigned lower rail is 0: legitimate zero pixels must not count
    assert attrs["sat_lo"] == 0 and attrs["sat_hi"] == 0


def _saturating_phase_plan(pipe, betas=3):
    """The tests/test_lowering.py synthetic residue plan: per-phase ranges
    deliberately tighter than true so per-residue saturation engages."""
    plan = run_plan(pipe, ["interval"],
                    betas={n: betas for n in pipe.stages})
    plan.phases["interval"] = {
        "resS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(-50.0, 50.0))}),
        "UyS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(0.0, 150.0)),
            (1, 0): StageRange.from_interval(Interval(0.0, 250.0))}),
        "band": ((2, 2), {(0, 0): StageRange.from_interval(
            Interval(-30.0, 30.0))}),
    }
    return plan


def test_saturation_telemetry_on_residue_plan():
    pipe = dus.build_extended()
    plan = _saturating_phase_plan(pipe)
    rng = np.random.default_rng(3)
    img = rng.integers(0, 256, (48, 48)).astype(np.float64)
    with obs.tracing(runtime_ranges=True) as tr:
        run_fixed(pipe, img, plan, backend="lowered")
    by_stage = {e["attrs"]["stage"]: e["attrs"] for e in tr.events("rt.range")}
    assert set(by_stage) == set(pipe.stages)    # every stage measured
    res = by_stage["resS"]
    # the narrow aligned residue must clip on this data, and the counts must
    # be attributed per residue against that residue's own rails
    assert res["sat"] > 0
    assert res["sat_phases"] and all(k == "0,0" for k in res["sat_phases"])
    assert res["sat"] == res["sat_lo"] + res["sat_hi"]
    for a in by_stage.values():
        assert a["min"] <= a["max"]
        assert a["headroom"] == a["alpha_plan"] - a["alpha_obs"]


def test_tracing_does_not_change_lowered_outputs():
    pipe = dus.build_extended()
    plan = _saturating_phase_plan(pipe)
    rng = np.random.default_rng(9)
    img = rng.integers(0, 256, (48, 48)).astype(np.float64)
    assert not obs.is_enabled()
    plain = run_fixed(pipe, img, plan, backend="lowered")
    with obs.tracing(runtime_ranges=True):
        traced = run_fixed(pipe, img, plan, backend="lowered")
    assert sorted(plain) == sorted(traced)
    for stage in plain:
        np.testing.assert_array_equal(
            np.asarray(plain[stage]), np.asarray(traced[stage]),
            err_msg=f"{stage}: tracing changed lowered execution")


# ---------------------------------------------------------------------------
# SMT budget-exhaustion visibility
# ---------------------------------------------------------------------------

def test_budget_exhaustion_warns_events_and_diagnostics():
    p = usm.build()
    diag = {}
    with obs.tracing() as tr:
        with pytest.warns(BudgetExhaustedWarning, match="kept its interval"):
            res = analyze_smt(p, config=SMTConfig(time_budget_s=0.0),
                              diagnostics=diag)
    starved = diag["budget_exhausted"]
    assert starved                              # zero budget: all stages starve
    assert {e["attrs"]["stage"]
            for e in tr.events("smt.budget_exhausted")} == set(starved)
    asp, = tr.spans("smt.analyze")
    assert asp.attrs["budget_exhausted"] == starved
    # starved stages keep the sound interval seed (never missing/looser)
    from repro.core.range_analysis import analyze
    seed = analyze(p, "interval")
    for n in starved:
        assert res[n].range.lo >= seed[n].range.lo
        assert res[n].range.hi <= seed[n].range.hi


def test_budget_exhaustion_note_lands_in_plan_provenance():
    from repro.analysis.passes import SmtPass
    p = usm.build()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BudgetExhaustedWarning)
        plan = run_plan(p, ["interval",
                            SmtPass(config=SMTConfig(time_budget_s=0.0))])
    notes = plan.provenance["smt"].notes
    note = [n for n in notes if n.startswith("budget-exhausted (seed kept):")]
    assert note, notes
    # ... and survives serialization, where benchmarks/alpha_delta.py reads it
    blob = json.loads(json.dumps(plan.to_json_dict()))
    assert note[0] in blob["provenance"]["smt"]["notes"]


# ---------------------------------------------------------------------------
# end-to-end acceptance: traced HCD compile + report
# ---------------------------------------------------------------------------

def test_traced_hcd_compile_end_to_end(tmp_path):
    from repro.analysis.passes import SmtPass
    pipe = hcd.build()
    rng = np.random.default_rng(17)
    img = rng.integers(0, 256, (32, 32)).astype(np.float64)
    with obs.tracing(runtime_ranges=True) as tr:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", BudgetExhaustedWarning)
            plan = run_plan(pipe, [
                "interval", SmtPass(config=SMTConfig(time_budget_s=5.0))])
        env = run_fixed(pipe, img, plan, backend="lowered")
    oracle = run_fixed(pipe, img, plan)
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage])

    # per-pass spans with memo disposition
    passes = tr.spans("analysis.pass")
    assert {s.attrs["pass"] for s in passes} >= {"interval", "smt"}
    assert all(s.attrs["memo"] in ("hit", "miss") for s in passes)
    # per-stage smt spans with boxes / budget / verdict
    stage_spans = tr.spans("smt.stage")
    assert stage_spans
    for s in stage_spans:
        assert s.attrs["verdict"] in ("seed", "tightened")
        assert s.attrs["boxes"] >= 0 and s.attrs["budget_s"] > 0
        assert "deadline_exhausted" in s.attrs
    # runtime telemetry for every executed stage
    rt = {e["attrs"]["stage"] for e in tr.events("rt.range")}
    assert rt == set(pipe.stages)
    # both exporters produce loadable artifacts, and the report summarizes
    obs.write_jsonl(tr, tmp_path / "hcd.jsonl")
    obs.write_chrome_trace(tr, tmp_path / "hcd.trace.json")
    with open(tmp_path / "hcd.trace.json") as f:
        assert json.load(f)["traceEvents"]
    recs = obs.load_jsonl(tmp_path / "hcd.jsonl")
    summary = report.summarize(recs)
    assert summary["passes"] and summary["smt_stages"] and summary["runtime"]
    text = report.render(summary)
    md = report.render(summary, markdown=True)
    assert "smt stages" in text and "| stage |" in md


# ---------------------------------------------------------------------------
# pallas island execution spans
# ---------------------------------------------------------------------------

def test_pallas_island_spans_and_report_breakdown(tmp_path):
    # dus at an odd height is rate-inexact: the pallas executor stitches
    # several islands, and every island call must emit one
    # `exec.pallas.island` span nested under the `exec.pallas` run span
    pipe = dus.build()
    plan = run_plan(pipe, ["interval"])
    rng = np.random.default_rng(23)
    img = rng.integers(0, 256, (47, 48)).astype(np.float64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # cpu interpret note
        with obs.tracing() as tr:
            run_fixed(pipe, img, plan, backend="pallas")
    outer, = tr.spans("exec.pallas")
    dispatch, = tr.spans("exec.dispatch")
    isl = tr.spans("exec.pallas.island")
    assert outer.attrs["islands"] == len(isl) > 1
    assert dispatch.parent_id == outer.span_id
    for s in isl:
        assert s.parent_id == dispatch.span_id
        assert s.attrs["stages"] >= 1 and s.attrs["grid"] >= 1
        assert "/" in s.attrs["rate"] or s.attrs["rate"].isdigit()
        assert s.attrs["carriers"]                  # non-empty datapath census
    assert any(s.attrs["single_tile"] for s in isl)

    # the report joins the spans into a per-island breakdown table
    obs.write_jsonl(tr, tmp_path / "p.jsonl")
    summary = report.summarize(obs.load_jsonl(tmp_path / "p.jsonl"))
    rows = summary["islands"]
    assert {r["island"] for r in rows} == {s.attrs["island"] for s in isl}
    for r in rows:
        assert r["calls"] == 1 and r["dispatch_ms"] >= 0
    md = report.render(summary, markdown=True)
    assert "pallas islands" in md and "single_tile" in md
    assert "dispatch_ms" in md
