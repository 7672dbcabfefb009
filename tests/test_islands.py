"""Rate-island partitioning + narrow datapath re-election.

Two contracts land here (docs/execution_backends.md):

  * **Rate islands** — `partition_islands` cuts any lowered DAG into
    maximal band-schedulable subgraphs; each island runs fully fused
    through the pallas line-buffer kernel and islands stitch through
    materialized HBM boundary buffers.  Every benchmark (of_pyramid
    included) must lower this way with ZERO jnp fallbacks, bit-for-bit
    against the `run_fixed` numpy oracle — including rate-inexact shapes
    the old whole-DAG scheduler rejected with `LoweringError`.
  * **Narrow datapath re-election** — `lower(..., datapath="narrow")`
    re-elects int32/f32-first carriers; no int64 carrier or f64 expr
    stage may survive without a recorded justification, elections land
    in `BitwidthPlan` provenance, and the re-elected program stays
    bit-identical to the oracle on both lowered backends.
"""
import warnings
from fractions import Fraction

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core.cost_model import design_cost, lowered_datapaths
from repro.core.fixedpoint import FixedPointType
from repro.core.graph import Pipeline, Stage, stencil_expr
from repro.core.range_analysis import analyze
from repro.dsl.exec import run_fixed
from repro.lowering import (LoweringError, build_schedule, compile_backend,
                            lower, partition_islands)
from repro.lowering.islands import _ext_inputs
from repro.lowering.schedule import stage_shapes
from repro.pipelines import dus, hcd, optical_flow, usm
from repro.pipelines import workflows as W
from test_lowering import _gen_pipe, _img, _types_for

GATE = [
    ("usm", usm.build, dict(usm.DEFAULT_PARAMS), 1, (48, 48)),
    ("hcd", hcd.build, {}, 1, (48, 48)),
    ("dus_ext", dus.build_extended, {}, 1, (48, 48)),
    ("of_pyramid", lambda: optical_flow.build_pyramid(1), {}, 2, (40, 40)),
]


def _inputs_for(pipe, shape, seed, n_in):
    imgs = tuple(_img(shape, seed=seed + i) for i in range(n_in))
    return imgs[0] if n_in == 1 else imgs


# ---------------------------------------------------------------------------
# the island gate: every benchmark fuses, bit-exact, no fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,build,params,n_in,shape",
                         GATE, ids=[g[0] for g in GATE])
def test_island_gate_fused_and_bit_exact(name, build, params, n_in, shape):
    pipe = build()
    types = _types_for(pipe)
    lp = lower(pipe, types, params=params)
    plan = partition_islands(lp, shape)
    assert plan.fully_fused, f"{name}: jnp fallback crept back in"
    assert plan.islands, name
    covered = [s for isl in plan.islands for s in isl.stages]
    compute = [n for n in lp.order if not lp.stages[n].stage.is_input]
    assert sorted(covered) == sorted(compute)       # exact cover, no dupes
    img = _inputs_for(pipe, shape, 31, n_in)
    oracle = run_fixed(pipe, img, types, params)
    outs = compile_backend(lp, "pallas")(img)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(
            np.asarray(oracle[stage]), outs[stage],
            err_msg=f"{name}/{stage}: stitched pallas != oracle")


def test_rate_inexact_shape_partitions_and_matches_oracle():
    """dus at 47 rows: the whole-DAG scheduler rejects it (odd height
    under stride 2), the partitioner must cut islands instead — and the
    single-tile escape hatch must edge-replicate exactly like the oracle
    (regression: the tap gather used to read out of the parent band at
    the image edges)."""
    pipe = dus.build()
    types = _types_for(pipe)
    lp = lower(pipe, types)
    with pytest.raises(LoweringError):
        build_schedule(lp, (47, 48))
    plan = partition_islands(lp, (47, 48))
    assert len(plan.islands) > 1
    img = _img((47, 48), seed=3)
    oracle = run_fixed(pipe, img, types)
    outs = compile_backend(lp, "pallas")(img)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]),
                                      outs[stage], err_msg=stage)


def test_islands_false_keeps_the_raising_contract():
    pipe = dus.build()
    lp = lower(pipe, _types_for(pipe))
    run = compile_backend(lp, "pallas", islands=False)
    with pytest.raises(LoweringError):
        run(_img((47, 48), seed=4))


def test_multi_island_boundaries_are_oracle_exact():
    """Rate-inexact dus with every stage requested: boundary buffers the
    stitching materializes must hold exactly the oracle's stage values
    (stored-representation containers, not rounded copies)."""
    pipe = dus.build()
    types = _types_for(pipe)
    lp = lower(pipe, types)
    allstages = [n for n in pipe.topo_order()
                 if not pipe.stages[n].is_input]
    plan = partition_islands(lp, (47, 48), outputs=allstages)
    assert len(plan.islands) > 1
    assert any(i.single_tile for i in plan.islands)
    img = _img((47, 48), seed=19)
    oracle = run_fixed(pipe, img, types)
    outs = compile_backend(lp, "pallas", outputs=allstages)(img)
    for stage in allstages:
        np.testing.assert_array_equal(np.asarray(oracle[stage]),
                                      outs[stage], err_msg=stage)


def test_explicit_tile_rows_is_a_whole_program_contract():
    """`tile_rows` pins the historical whole-DAG schedule: honored when
    feasible, `LoweringError` (not a silent partition) when not."""
    pipe = hcd.build()
    lp = lower(pipe, _types_for(pipe))
    plan = partition_islands(lp, (48, 48), tile_rows=8)
    assert plan.fully_fused and plan.islands[0].schedule.grid == 6
    with pytest.raises(LoweringError):
        partition_islands(lp, (48, 48), tile_rows=5)    # 5 does not tile 48


# ---------------------------------------------------------------------------
# partitioner fuzz: coverage + schedule equivalence
# ---------------------------------------------------------------------------

@st.composite
def island_pipelines(draw):
    return _gen_pipe("fuzz_islands",
                     lambda n: draw(st.integers(0, n - 1)),
                     lambda lo, hi: draw(st.floats(lo, hi)))


def _fuzz_types(pipe):
    res = analyze(pipe)
    if any(np.isinf(r.range.hi) or r.alpha > 24 for r in res.values()):
        return None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return {n: FixedPointType(alpha=max(r.alpha, 1), beta=4,
                                  signed=r.signed)
                for n, r in res.items()}


# heights 18/22 are divisible by 2 but not 4+ (chained decimation goes
# rate-inexact) and 47 is odd (any decimation does), so the fuzz actually
# reaches multi-island partitions instead of only the whole-DAG fast path
FUZZ_HEIGHTS = (16, 18, 22, 24, 47)


@given(island_pipelines(), st.sampled_from(FUZZ_HEIGHTS),
       st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_F_partition_covers_with_rate_uniform_islands(pipe, rows, seed):
    types = _fuzz_types(pipe)
    if types is None:
        return
    shape = (rows, 16)
    lp = lower(pipe, types)
    plan = partition_islands(lp, shape)
    shapes = stage_shapes(lp, shape)
    compute = [n for n in lp.order if not lp.stages[n].stage.is_input]
    covered = [s for isl in plan.islands for s in isl.stages]
    assert sorted(covered) == sorted(compute)
    for isl in plan.islands:
        # contiguous in topo order, rate anchored at the first stage
        assert isl.rate == Fraction(shapes[isl.stages[0]][0], shape[0])
        assert isl.inputs == _ext_inputs(lp, isl.stages)
        sched = isl.schedule
        for n in isl.stages:
            ss = sched.stages[n]
            assert ss.H == shapes[n][0], n
            # row cover: the last band may be ragged, never empty
            assert (sched.grid - 1) * ss.step < ss.H <= sched.grid * ss.step, n
            assert ss.lo <= 0 < ss.hi, n
        # island outputs really are consumed outside (or pipeline outputs)
        inside = set(isl.stages)
        for out in isl.outputs:
            ext_use = any(out in lp.stages[c].stage.inputs
                          for c in compute if c not in inside)
            assert ext_use or out in plan.outputs


@given(island_pipelines(), st.sampled_from(FUZZ_HEIGHTS),
       st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_F_stitched_pallas_matches_jnp_and_oracle(pipe, rows, seed):
    types = _fuzz_types(pipe)
    if types is None:
        return
    img = _img((rows, 16), seed=seed)
    oracle = run_fixed(pipe, img, types)
    lp = lower(pipe, types)
    env = compile_backend(lp, "jnp", outputs=list(pipe.stages))(img)
    outs = compile_backend(lp, "pallas")(img)       # never raises now
    for stage in outs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]),
                                      outs[stage], err_msg=stage)
        np.testing.assert_array_equal(env[stage], outs[stage],
                                      err_msg=stage)


@given(island_pipelines())
@settings(max_examples=15, deadline=None)
def test_F_single_island_schedule_equals_build_schedule(pipe):
    """When the whole DAG band-schedules, the island path must reproduce
    the historical schedule exactly (same bands, same grid)."""
    types = _fuzz_types(pipe)
    if types is None:
        return
    lp = lower(pipe, types)
    try:
        whole = build_schedule(lp, (16, 16))
    except LoweringError:
        return
    plan = partition_islands(lp, (16, 16))
    assert len(plan.islands) == 1
    isl = plan.islands[0]
    sched = isl.schedule
    assert sched.grid == whole.grid
    for n, ss in whole.stages.items():
        got = sched.stages[n]
        assert (got.step, got.lo, got.hi, got.H, got.W) == \
            (ss.step, ss.lo, ss.hi, ss.H, ss.W), n


# ---------------------------------------------------------------------------
# narrow datapath re-election
# ---------------------------------------------------------------------------

NARROW = [(g[0], g[1], g[2], g[3], g[4]) for g in GATE]


@pytest.mark.parametrize("name,build,params,n_in,shape",
                         NARROW, ids=[g[0] for g in NARROW])
def test_narrow_elections_justified_and_bit_exact(name, build, params,
                                                  n_in, shape):
    pipe = build()
    types = _types_for(pipe)
    lp = lower(pipe, types, params=params, datapath="narrow")
    assert lp.datapath == "narrow"
    for n, ls in lp.stages.items():
        if ls.stage.is_input:
            continue
        if ls.kind == "intlinear" and ls.carrier == "int64":
            assert ls.election.startswith("int64 kept:"), \
                f"{name}/{n}: unjustified int64 carrier"
        if ls.kind == "expr" and ls.expr_dtype == "f64" \
                and not ls.store_float and ls.phase is None:
            assert ls.election.startswith("f64 kept:"), \
                f"{name}/{n}: unjustified f64 expr datapath"
    img = _inputs_for(pipe, shape, 41, n_in)
    oracle = run_fixed(pipe, img, types, params)
    for backend in ("jnp", "pallas"):
        run = compile_backend(lp, backend)
        outs = run(img)
        for stage in pipe.outputs:
            np.testing.assert_array_equal(
                np.asarray(oracle[stage]), outs[stage],
                err_msg=f"{name}/{stage}/{backend} (narrow)")


def test_narrow_demotes_to_f32_under_proof():
    """hcd's product stages fit the 24-bit-mantissa exactness proof at
    8-bit inputs — they must demote to f32 and still match the oracle."""
    pipe = hcd.build()
    types = _types_for(pipe)
    lp = lower(pipe, types, datapath="narrow")
    demoted = [n for n, ls in lp.stages.items()
               if ls.kind == "expr" and ls.expr_dtype == "f32"]
    assert demoted, "no stage demoted to f32 on hcd"
    assert all(lp.stages[n].election == "f32" for n in demoted)


def _wide_acc_pipe(taps: int):
    pipe = Pipeline("wideacc")
    pipe.add_stage(Stage(name="img", expr=None, is_input=True))
    pipe.add_stage(Stage(
        name="box",
        expr=stencil_expr("img", [[1.0]] * taps, scale=41.0 / 256.0),
        inputs=("img",)))
    pipe.mark_output("box")
    types = {"img": FixedPointType(alpha=27, beta=0, signed=False),
             "box": FixedPointType(alpha=27, beta=0, signed=False)}
    return pipe, types


def test_narrow_int32pair_split_is_bit_exact():
    """An accumulator bound past INT32_BUDGET splits into an int32 pair
    with one widening combine — bit-identical to the int64 carrier."""
    pipe, types = _wide_acc_pipe(taps=9)     # 9 * 2^27 > 2^30: real split
    img = np.random.default_rng(5).integers(
        0, 1 << 27, (48, 48)).astype(np.float64)
    oracle = run_fixed(pipe, img, types)
    exact = lower(pipe, types)
    narrow = lower(pipe, types, datapath="narrow")
    assert exact.stages["box"].carrier == "int64"
    ls = narrow.stages["box"]
    assert ls.carrier == "int32pair"
    assert ls.election.startswith("int32pair:")
    for lp in (exact, narrow):
        for backend in ("jnp", "pallas"):
            outs = compile_backend(lp, backend)(img)
            np.testing.assert_array_equal(
                np.asarray(oracle["box"]), outs["box"],
                err_msg=f"{lp.datapath}/{backend}")


def test_narrow_elections_recorded_in_plan_provenance():
    from repro.analysis import run_plan
    pipe = hcd.build()
    plan = run_plan(pipe, ["interval"],
                    betas={n: 4 for n in pipe.stages})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        lower(pipe, plan, datapath="narrow")
    notes = plan.provenance[plan.default_column].notes
    assert any(n.startswith("datapath[narrow]") for n in notes)
    kept = [n for n in notes if "kept:" in n]
    assert kept, "per-stage justification lines missing from provenance"
    # round-trips through the stable JSON form
    from repro.analysis import BitwidthPlan
    again = BitwidthPlan.from_json(plan.to_json())
    assert notes == again.provenance[again.default_column].notes


def test_narrow_prices_cheaper_in_cost_model():
    pipe = hcd.build()
    types = _types_for(pipe)
    base = design_cost(pipe, types)
    ce = design_cost(pipe, types,
                     datapaths=lowered_datapaths(lower(pipe, types)))
    cn = design_cost(
        pipe, types,
        datapaths=lowered_datapaths(lower(pipe, types, datapath="narrow")))
    assert cn.power_proxy < ce.power_proxy
    # defaults stay byte-identical to the historical model
    assert base.power_proxy == design_cost(pipe, types).power_proxy


def test_lower_rejects_unknown_datapath():
    pipe = usm.build()
    with pytest.raises(ValueError):
        lower(pipe, _types_for(pipe), datapath="int8")


# ---------------------------------------------------------------------------
# capability detection
# ---------------------------------------------------------------------------

def test_resolve_interpret_on_cpu_warns_once_and_interprets():
    from repro.lowering import pallas_backend as PB
    pipe = usm.build()
    lp = lower(pipe, _types_for(pipe), params=dict(usm.DEFAULT_PARAMS))
    PB._warned.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert PB.resolve_interpret(lp) is True      # no TPU/GPU here
        assert PB.resolve_interpret(lp) is True      # second call silent
    runtime = [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert len(runtime) == 1
    assert "interpret mode" in str(runtime[0].message)


def test_resolve_interpret_on_tpu_refuses_64bit_plans(monkeypatch):
    """On a TPU a plan that needs 64-bit datapaths never reaches the
    kernel or interpret mode: `LoweringError` names its stages."""
    import jax

    from repro.lowering import pallas_backend as PB
    pipe = usm.build()
    lp = lower(pipe, _types_for(pipe), params=dict(usm.DEFAULT_PARAMS))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(LoweringError) as exc:
        PB.resolve_interpret(lp)
    msg = str(exc.value)
    assert "sharpen" in msg and "masked" in msg
    assert PB.stages_needing_64bit(lp) == ["sharpen", "masked"]
    narrow = lower(pipe, _types_for(pipe), params=dict(usm.DEFAULT_PARAMS),
                   datapath="narrow")
    with pytest.raises(LoweringError, match="f64 kept"):
        PB.resolve_interpret(narrow)
    dus_lp = lower(dus.build_extended(), _types_for(dus.build_extended()))
    assert PB.resolve_interpret(dus_lp) is False     # 32-bit: native


def test_needs_64bit_tracks_the_election():
    pipe, types = _wide_acc_pipe(taps=9)
    from repro.lowering.pallas_backend import needs_64bit
    assert needs_64bit(lower(pipe, types))            # int64 carrier
    # the narrow election moves the datapath into int32-pair + one
    # widening combine — still 64-bit (the combine), so no change here;
    # but a plain int32 pipeline needs none
    p2 = usm.build()
    t2 = _types_for(p2)
    lp2 = lower(p2, t2, params=dict(usm.DEFAULT_PARAMS))
    # usm has f64 expr stages -> needs 64-bit
    assert needs_64bit(lp2)


# ---------------------------------------------------------------------------
# stored containers at island boundaries
# ---------------------------------------------------------------------------

def test_island_descriptors_carry_stored_containers():
    """The fused kernel's stage descriptors (shared by the pallas and
    shard_map executors) carry `backends.store_dtype` — the legalized
    narrow container, not the MAC carrier."""
    from repro.lowering import backends as B
    from repro.lowering.pallas_backend import island_program
    pipe = dus.build_extended()
    lp = lower(pipe, _types_for(pipe))
    plan = partition_islands(lp, (48, 48))
    for isl in plan.islands:
        for d in island_program(lp, isl):
            want = np.dtype(B.store_dtype(lp.stages[d["name"]]))
            assert np.dtype(d["dtype"]) == want, d["name"]
            assert want.itemsize <= 2, \
                f"{d['name']}: dus_ext tiles must all fit 16-bit containers"


def test_boundary_buffers_stitch_narrow_and_save_bytes():
    """Multi-island stitching materializes HBM boundaries in the stored
    container: every dus boundary is sub-int32, `boundary_bytes` prices
    real savings vs the uniform int32 baseline, and `stored_mix` shows
    no int64/f64 leakage."""
    from repro.lowering.backends import store_dtype
    pipe = dus.build()
    types = _types_for(pipe)
    lp = lower(pipe, types)
    plan = partition_islands(lp, (47, 48))
    assert len(plan.islands) > 1
    for isl in plan.islands:
        for out in isl.outputs:
            assert np.dtype(store_dtype(lp.stages[out])).itemsize <= 2, out
        stored, saved = isl.boundary_bytes(lp)
        assert stored > 0 and saved > 0
        mix = isl.stored_mix(lp)
        assert "int64" not in mix and "float64" not in mix, mix
    # and the stitched execution over those narrow boundaries is exact
    img = _img((47, 48), seed=23)
    oracle = run_fixed(pipe, img, types)
    outs = compile_backend(lp, "pallas")(img)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]),
                                      outs[stage], err_msg=stage)


def test_boundary_bytes_accounts_f64_as_negative_savings():
    """A float-stored boundary costs 8 B/px: `boundary_bytes` must report
    it as negative savings, not silently fold it into the narrow wins."""
    pipe = dus.build_extended()
    types = _types_for(pipe)
    phase_types = {"resS": ((2, 1), {(0, 0): FixedPointType(8, 1, True)})}

    class FakePlan:
        def phase_types(self, column=None):
            return phase_types

        def types(self, column=None):
            return types

    lp = lower(pipe, FakePlan())
    assert lp.stages["resS"].store_float
    iplan = partition_islands(lp, (48, 48), outputs=["resS"])
    isl = next(i for i in iplan.islands if "resS" in i.outputs)
    stored, saved = isl.boundary_bytes(lp)
    h, w = isl.schedule.stages["resS"].H, isl.schedule.stages["resS"].W
    assert stored >= h * w * 8
    assert saved <= -h * w * 4      # 4 - 8 bytes per resS pixel, at least
