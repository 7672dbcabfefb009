"""Plan-driven lowering: bit-exact differential battery + schedule units.

The contract under test (docs/execution_backends.md): every lowering
backend — the fused jnp program and the fused line-buffer pallas kernel —
is **bit-for-bit identical** to the per-pixel `run_fixed` numpy oracle, on
every benchmark pipeline, including per-phase-typed stages where sampling
lattice residues carry different datapaths.  Plus hypothesis fuzz over
random small pipelines with stride/upsample stages.
"""
import warnings

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.analysis import BitwidthPlan, run_plan
from repro.core.fixedpoint import FixedPointType
from repro.core.graph import Pow
from repro.core.interval import Interval
from repro.core.range_analysis import StageRange, analyze
from repro.dsl.builder import PipelineBuilder, absv, ite, maxv
from repro.dsl.exec import make_jitted_fixed, run_fixed
from repro.lowering import (LoweringError, build_schedule, compile_backend,
                            compile_pipeline, lower, match_linear)
from repro.lowering.schedule import row_rates, stage_shapes
from repro.pipelines import dus, hcd, optical_flow, usm
from repro.pipelines import workflows as W

RNG = np.random.default_rng(1234)


def _types_for(pipe, beta=4):
    alphas, signed = W.static_alphas(pipe)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return W.types_from_alpha(pipe, alphas, signed,
                                  {n: beta for n in pipe.stages})


def _img(shape=(48, 48), seed=None, lo=0, hi=256):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    return rng.integers(lo, hi, shape).astype(np.float64)


BENCHES = [
    ("usm", usm.build, dict(usm.DEFAULT_PARAMS), 1, (48, 48)),
    ("hcd", hcd.build, {}, 1, (48, 48)),
    ("dus", dus.build, {}, 1, (48, 48)),
    ("dus_ext", dus.build_extended, {}, 1, (48, 48)),
    ("of", optical_flow.build, {}, 2, (40, 40)),
    ("of_pyramid", lambda: optical_flow.build_pyramid(1), {}, 2, (40, 40)),
]


# ---------------------------------------------------------------------------
# the differential battery: lowered jnp + pallas vs the per-pixel oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,build,params,n_in,shape",
                         BENCHES, ids=[b[0] for b in BENCHES])
def test_lowered_jnp_bit_exact_all_stages(name, build, params, n_in, shape):
    pipe = build()
    types = _types_for(pipe)
    img = _img(shape, seed=7) if n_in == 1 else \
        tuple(_img(shape, seed=7 + i) for i in range(n_in))
    oracle = run_fixed(pipe, img, types, params)
    env = run_fixed(pipe, img, types, params, backend="lowered")
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(
            np.asarray(oracle[stage]), env[stage],
            err_msg=f"{name}/{stage}: lowered jnp != oracle")


@pytest.mark.parametrize("name,build,params,n_in,shape",
                         BENCHES, ids=[b[0] for b in BENCHES])
def test_pallas_bit_exact_outputs(name, build, params, n_in, shape):
    pipe = build()
    types = _types_for(pipe)
    img = _img(shape, seed=11) if n_in == 1 else \
        tuple(_img(shape, seed=11 + i) for i in range(n_in))
    oracle = run_fixed(pipe, img, types, params)
    outs = run_fixed(pipe, img, types, params, backend="pallas")
    assert sorted(outs) == sorted(pipe.outputs)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(
            np.asarray(oracle[stage]), outs[stage],
            err_msg=f"{name}/{stage}: pallas != oracle")


def _phase_plan(pipe, betas=3):
    """Interval plan with synthetic per-phase sub-columns whose residues
    carry different alphas (the dus_ext resS story, made cheap for CI).

    The residue ranges are deliberately *tighter than true* so the
    per-residue saturation engages on random data — this is an executor
    differential, not a soundness test."""
    plan = run_plan(pipe, ["interval"],
                    betas={n: betas for n in pipe.stages})
    phases = {
        "resS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(-50.0, 50.0))}),
        "UyS": ((2, 1), {(0, 0): StageRange.from_interval(
            Interval(0.0, 150.0)),
            (1, 0): StageRange.from_interval(Interval(0.0, 250.0))}),
        "band": ((2, 2), {(0, 0): StageRange.from_interval(
            Interval(-30.0, 30.0))}),
    }
    plan.phases["interval"] = phases
    return plan


def test_phase_split_stage_bit_exact_all_backends():
    """Residues with different alphas: one datapath per lattice residue,
    still bit-identical to the oracle's per-residue re-snap."""
    pipe = dus.build_extended()
    plan = _phase_plan(pipe)
    img = _img((48, 48), seed=3)
    oracle = run_fixed(pipe, img, plan)
    lp = lower(pipe, plan)
    assert lp.stages["resS"].phase is not None
    assert lp.stages["resS"].kind == "intlinear"
    env = run_fixed(pipe, img, plan, backend="lowered")
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)
    outs = run_fixed(pipe, img, plan, backend="pallas")
    for stage in pipe.outputs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]), outs[stage],
                                      err_msg=stage)
    # the narrow aligned residue must actually saturate somewhere on this
    # data — otherwise the phase path is not exercised
    t_u = plan.types()["resS"]
    raw = run_fixed(pipe, img, plan.types())  # union-only design
    assert not np.array_equal(np.asarray(raw["resS"]),
                              np.asarray(oracle["resS"]))


def test_phase_split_mixed_beta_falls_back_to_float_store():
    """Hand-built phase maps may change beta per residue; the lowering
    must take the float path and still match the oracle exactly."""
    pipe = dus.build_extended()
    plan = _phase_plan(pipe)
    # a residue type with a different beta than the union column
    types = plan.types()
    phase_types = {"resS": ((2, 1), {(0, 0): FixedPointType(8, 1, True)})}
    img = _img((48, 48), seed=5)
    from repro.dsl.exec import _run_concrete
    oracle = _run_concrete(pipe, img, {}, types, xp=np,
                           phase_types=phase_types)

    class FakePlan:
        def phase_types(self, column=None):
            return phase_types

        def types(self, column=None):
            return types

    lp = lower(pipe, FakePlan())
    assert lp.stages["resS"].store_float
    run = compile_backend(lp, "jnp", outputs=list(pipe.stages))
    env = run(img)
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)


def test_make_jitted_fixed_is_bit_exact_wrapper():
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    fn = make_jitted_fixed(pipe, types, params)
    img = _img((32, 32), seed=13)
    oracle = run_fixed(pipe, img, types, params)
    out = fn(img)
    assert sorted(out) == sorted(pipe.outputs)
    for k, v in out.items():
        np.testing.assert_array_equal(np.asarray(oracle[k]), v)


def test_executor_helper_and_repeat_calls():
    setup = W.make_usm(n_train=1, n_test=1, shape=(24, 24))
    types = _types_for(setup.pipeline)
    run = setup.executor(types, backend="jnp")
    a = run(setup.test_images[0])
    b = run(setup.test_images[0])
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# IR units
# ---------------------------------------------------------------------------

def test_match_linear_shapes():
    pipe = usm.build()
    taps, scale = match_linear(pipe.stages["blurx"].expr)
    assert scale == 1.0 / 16
    assert sorted((t.dy, t.dx, t.w) for t in taps) == \
        [(-2, 0, 1.0), (-1, 0, 4.0), (0, 0, 6.0), (1, 0, 4.0), (2, 0, 1.0)]
    # point-wise linear, multi-input, unit scale
    ext = dus.build_extended()
    taps, scale = match_linear(ext.stages["band"].expr)
    assert scale == 1.0
    assert sorted((t.stage, t.w) for t in taps) == \
        [("D5", -1.0), ("Dy", 1.0)]
    # non-linear stages don't match
    assert match_linear(hcd.build().stages["det"].expr) is None


def test_lowering_kind_selection():
    pipe = hcd.build()
    lp = lower(pipe, _types_for(pipe))
    kinds = lp.kinds()
    # box sums are dyadic-integer stencils; Sobel/12 is intlinear with a
    # proved integer rational finish; the products, det and harris are
    # integer polynomials (harris with the rational finish of 0.04*T)
    assert kinds["Sxx"] == "intlinear" and lp.stages["Sxx"].dyadic
    assert kinds["Ix"] == "intlinear" and not lp.stages["Ix"].dyadic
    assert lp.stages["Ix"].finish == "rational"
    assert lp.stages["Ix"].rat == (1, 12)
    assert kinds["Ixx"] == kinds["det"] == kinds["harris"] == "intpoly"
    assert lp.stages["det"].finish == "shift"
    assert lp.stages["harris"].finish == "rational"
    assert lp.stages["harris"].carrier == "int64"
    assert "expr" not in kinds.values()


def test_negative_shift_elects_wide_carrier():
    """beta_out deeper than the input grid left-shifts the finished value
    past the accumulator bound — the carrier election must account for the
    post-shift magnitude (regression: int32 wrap returned -0.0039 where
    the oracle returns 16777215.0)."""
    p = PipelineBuilder("negshift")
    a = p.image("a", 0, 2 ** 26 - 1)
    s = p.define("s0", 0.5 * (a + a))
    p.output(s)
    pipe = p.build()
    types = {"a": FixedPointType(26, 0, signed=False),
             "s0": FixedPointType(40, 8, signed=False)}
    lp = lower(pipe, types)
    ls = lp.stages["s0"]
    assert ls.kind == "intlinear" and ls.t_shift < 0
    assert ls.carrier == "int64"
    img = np.full((8, 8), 2 ** 26 - 2, dtype=np.float64)
    oracle = run_fixed(pipe, img, types)
    env = run_fixed(pipe, img, types, backend="lowered")
    np.testing.assert_array_equal(np.asarray(oracle["s0"]), env["s0"])


def test_per_axis_halo():
    pipe = usm.build()
    assert pipe.stages["blurx"].halo_yx() == (2, 0)
    assert pipe.stages["blury"].halo_yx() == (0, 2)
    assert pipe.stages["blurx"].halo() == 2


# ---------------------------------------------------------------------------
# schedule units
# ---------------------------------------------------------------------------

def test_schedule_rates_and_spans():
    pipe = dus.build_extended()
    lp = lower(pipe, _types_for(pipe))
    rates = row_rates(lp)
    assert rates["Dy"] == rates["D5"] == rates["DyS"]
    assert float(rates["Dy"]) == 0.5
    assert float(rates["Uy"]) == 1.0
    sched = build_schedule(lp, (48, 48))
    assert sched.grid * sched.tile_rows == 48
    for n, ss in sched.stages.items():
        assert ss.L <= ss.H, n
        assert ss.step >= 1, n
    # decimated stages advance half a tile per grid step
    assert sched.stages["Dy"].step * 2 == sched.stages["Uy"].step


def test_schedule_rejects_rate_inexact_heights():
    pipe = dus.build()
    lp = lower(pipe, _types_for(pipe))
    with pytest.raises(LoweringError):
        build_schedule(lp, (47, 48))       # odd height under stride 2


def test_stage_shapes_match_executor():
    pipe = dus.build_extended()
    lp = lower(pipe, _types_for(pipe))
    img = _img((48, 48), seed=17)
    env = run_fixed(pipe, img, lp.types)
    shapes = stage_shapes(lp, (48, 48))
    for n in pipe.topo_order():
        assert tuple(np.asarray(env[n]).shape) == shapes[n], n


# ---------------------------------------------------------------------------
# seeded + hypothesis fuzz: random sampled pipelines, all backends agree
# ---------------------------------------------------------------------------

KERNELS = [
    ([[1, 2, 1], [2, 4, 2], [1, 2, 1]], 1 / 16),
    ([[-1, 0, 1]], 1.0),
    ([[1, 1, 1], [1, 1, 1], [1, 1, 1]], 1.0),
    ([[1, 4, 6, 4, 1]], 1 / 16),
    ([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], 1 / 12),   # non-dyadic scale
]


def _gen_pipe(name: str, pick_int, pick_float):
    """Shared random-DAG builder; `pick_int(n)`/`pick_float(lo, hi)` are
    the randomness source (hypothesis draws or a seeded Generator).

    Combining stages only pairs handles at the SAME cumulative sampling
    rate — anything else is not a well-formed pipeline (the executors all
    reject mismatched grids)."""
    p = PipelineBuilder(name)
    handles = [(p.image("img", 0, 255), (1, 1))]    # (handle, rate)
    n_stages = 2 + pick_int(4)
    for i in range(n_stages):
        kind = ["stencil", "down", "up", "add", "sub", "mul_const",
                "square", "abs", "select"][pick_int(9)]
        a, ra = handles[pick_int(len(handles))]
        name_i = f"s{i}"
        if kind == "stencil":
            w, sc = KERNELS[pick_int(len(KERNELS))]
            h, r = p.stencil(name_i, a, w, scale=sc), ra
        elif kind == "down":
            w, sc = KERNELS[pick_int(2)]
            sy, sx = [(2, 1), (1, 2), (2, 2)][pick_int(3)]
            h = p.downsample(name_i, a, w, scale=sc, stride=(sy, sx))
            r = (ra[0] * sy, ra[1] * sx)
        elif kind == "up":
            w, sc = KERNELS[pick_int(2)]
            uy, ux = [(2, 1), (1, 2), (2, 2)][pick_int(3)]
            h = p.upsample(name_i, a, w, scale=sc, factor=(uy, ux))
            r = (ra[0] / uy, ra[1] / ux)
        elif kind in ("add", "sub", "abs", "select"):
            peers = [hb for hb, rb in handles if rb == ra]
            b = peers[pick_int(len(peers))]
            if kind == "add":
                h = p.define(name_i, a + b)
            elif kind == "sub":
                h = p.define(name_i, a - b)
            elif kind == "abs":
                h = p.define(name_i, absv(a - b))
            else:
                h = p.define(name_i, ite(absv(a - b) <
                                         pick_float(1.0, 200.0), a, b))
            r = ra
        elif kind == "mul_const":
            h = p.define(name_i, a * [0.25, 0.5, 2.0, -1.0, 1.5][pick_int(5)])
            r = ra
        else:
            h = p.define(name_i, Pow(a, 2) * (1.0 / 256))
            r = ra
        handles.append((h, r))
    return p.build()


@st.composite
def sampled_pipelines(draw):
    """Random DAGs over one 8-bit input with stride/upsample stages."""
    return _gen_pipe("fuzz_lower",
                     lambda n: draw(st.integers(0, n - 1)),
                     lambda lo, hi: draw(st.floats(lo, hi)))


def _rand_pipe(rng: np.random.Generator):
    """Seeded twin of `sampled_pipelines` (runs without hypothesis)."""
    return _gen_pipe("fuzz_lower_seeded",
                     lambda n: int(rng.integers(0, n)),
                     lambda lo, hi: float(rng.uniform(lo, hi)))


@pytest.mark.parametrize("seed", range(12))
def test_S1_seeded_random_pipelines_all_backends(seed):
    rng = np.random.default_rng(9000 + seed)
    pipe = _rand_pipe(rng)
    res = analyze(pipe)
    if any(np.isinf(r.range.hi) or r.alpha > 24 for r in res.values()):
        pytest.skip("range blow-up: executor would need >int32 carriers")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        types = {n: FixedPointType(alpha=max(r.alpha, 1),
                                   beta=int(rng.integers(0, 6)),
                                   signed=r.signed)
                 for n, r in res.items()}
    img = _img((16, 16), seed=seed)
    oracle = run_fixed(pipe, img, types)
    env = run_fixed(pipe, img, types, backend="lowered")
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)
    # every DAG partitions into fused islands now — no LoweringError escape
    outs = run_fixed(pipe, img, types, backend="pallas")
    for stage in outs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]), outs[stage],
                                      err_msg=f"pallas/{stage}")


@given(sampled_pipelines(), st.integers(0, 10_000), st.integers(0, 6))
@settings(max_examples=25, deadline=None)
def test_F1_lowered_jnp_matches_oracle_on_random_pipelines(pipe, seed, beta):
    res = analyze(pipe)
    if any(np.isinf(r.range.hi) or r.alpha > 24 for r in res.values()):
        return
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        types = {n: FixedPointType(alpha=max(r.alpha, 1), beta=beta,
                                   signed=r.signed)
                 for n, r in res.items()}
    img = _img((16, 16), seed=seed)
    oracle = run_fixed(pipe, img, types)
    env = run_fixed(pipe, img, types, backend="lowered")
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)


@given(sampled_pipelines(), st.integers(0, 10_000))
@settings(max_examples=12, deadline=None)
def test_F2_pallas_matches_oracle_on_random_pipelines(pipe, seed):
    res = analyze(pipe)
    if any(np.isinf(r.range.hi) or r.alpha > 24 for r in res.values()):
        return
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        types = {n: FixedPointType(alpha=max(r.alpha, 1), beta=4,
                                   signed=r.signed)
                 for n, r in res.items()}
    img = _img((16, 16), seed=seed)
    oracle = run_fixed(pipe, img, types)
    # island partitioning is total: every sampled DAG must lower to fused
    # pallas islands — a LoweringError here is a real regression
    outs = run_fixed(pipe, img, types, backend="pallas")
    for stage in outs:
        np.testing.assert_array_equal(np.asarray(oracle[stage]), outs[stage],
                                      err_msg=stage)


# ---------------------------------------------------------------------------
# stored containers: legalized narrow tiles end-to-end
# ---------------------------------------------------------------------------

from repro.core.policy import legalize
from repro.lowering import backends as B


@pytest.mark.parametrize("name,build,params,n_in,shape",
                         BENCHES, ids=[b[0] for b in BENCHES])
def test_store_dtype_is_the_legalized_container(name, build, params,
                                                n_in, shape):
    """Every integer-stored tile lives in `policy.legalize`'s smallest
    container; 33-52 exact-integer bits stay int64; float-stored stages
    stay f64 (docs/execution_backends.md, "Stored containers")."""
    pipe = build()
    lp = lower(pipe, _types_for(pipe), params=params)
    narrow = 0
    for n, ls in lp.stages.items():
        dt = np.dtype(B.store_dtype(ls))
        if ls.store_float:
            assert dt == np.float64, n
            continue
        if ls.t.width <= 32:
            lt = legalize(ls.t)
            assert lt.fp is not None and dt == np.dtype(lt.dtype), \
                f"{name}/{n}: stored {dt}, legalized {lt.container}"
        else:
            assert dt == np.int64, n
        narrow += dt.itemsize < 4
    # the beta-4 battery designs are 8-bit imaging pipelines: a plan
    # that elects zero sub-int32 containers means legalization regressed
    assert narrow, f"{name}: no stage elected a sub-int32 container"


def _narrow_pipe():
    """Handmade design whose plan elects int8 / uint8 / int16 / uint16 —
    every sub-int32 container at once."""
    p = PipelineBuilder("narrowpipe")
    a = p.image("img", 0, 15)
    d = p.define("diff", a - 7.0)
    s = p.stencil("blur", a, [[1.0, 2.0, 1.0]], scale=0.25)
    m = p.define("mix", s - d)
    p.output(m)
    pipe = p.build()
    types = {
        "img": FixedPointType(alpha=4, beta=0, signed=False),    # uint8
        "diff": FixedPointType(alpha=4, beta=0, signed=True),    # int8
        "blur": FixedPointType(alpha=4, beta=8, signed=False),   # uint16
        "mix": FixedPointType(alpha=5, beta=8, signed=True),     # int16
    }
    return pipe, types


def test_narrow_tiles_bit_exact_across_backends():
    pipe, types = _narrow_pipe()
    lp = lower(pipe, types)
    stored = {n: np.dtype(B.store_dtype(ls)) for n, ls in lp.stages.items()}
    assert stored == {"img": np.dtype(np.uint8), "diff": np.dtype(np.int8),
                      "blur": np.dtype(np.uint16), "mix": np.dtype(np.int16)}
    img = _img((24, 24), seed=13, hi=16)
    oracle = run_fixed(pipe, img, types)
    for backend in ("jnp", "pallas"):
        outs = compile_backend(lp, backend, outputs=list(pipe.stages))(img)
        for stage in pipe.topo_order():
            np.testing.assert_array_equal(
                np.asarray(oracle[stage]), outs[stage],
                err_msg=f"{backend}/{stage} (narrow containers)")


def test_saturating_phase_plan_stores_narrow_containers():
    """Per-residue saturation runs in the *union* container — which the
    plan still narrows below int32 — and stays oracle-exact."""
    pipe = dus.build_extended()
    plan = _phase_plan(pipe)
    lp = lower(pipe, plan)
    for n in ("resS", "UyS", "band"):
        ls = lp.stages[n]
        assert ls.phase is not None and not ls.store_float, n
        assert np.dtype(B.store_dtype(ls)).itemsize < 4, \
            f"{n}: saturating phase stage lost its narrow container"
    img = _img((48, 48), seed=17)
    oracle = run_fixed(pipe, img, plan)
    env = run_fixed(pipe, img, plan, backend="lowered")
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)


def test_narrow_equals_wide_equals_oracle(monkeypatch):
    """Storage narrowing is value-neutral: forcing the pre-legalization
    int32/int64/f64 containers (`wide_store_dtype`) produces byte-equal
    outputs on both lowered backends."""
    pipe = dus.build_extended()
    types = _types_for(pipe)
    lp = lower(pipe, types)
    img = _img((48, 48), seed=31)
    oracle = run_fixed(pipe, img, types)
    narrow = {b: compile_backend(lp, b)(img) for b in ("jnp", "pallas")}
    monkeypatch.setattr(B, "store_dtype", B.wide_store_dtype)
    wide = {b: compile_backend(lp, b)(img) for b in ("jnp", "pallas")}
    for b in ("jnp", "pallas"):
        for stage in pipe.outputs:
            np.testing.assert_array_equal(
                np.asarray(oracle[stage]), narrow[b][stage],
                err_msg=f"{b}/{stage} narrow != oracle")
            np.testing.assert_array_equal(
                narrow[b][stage], wide[b][stage],
                err_msg=f"{b}/{stage}: narrow != wide storage")


def test_container_dtype_input_is_zero_copy_and_bit_exact():
    """The zero-copy ingestion convention: an input already in its
    stage's container dtype is treated as pre-quantized scaled integers
    and must land byte-identical to the f64 path on every backend —
    for a beta-0 8-bit input the raw uint8 frame IS the stored tile."""
    pipe = usm.build()
    params = dict(usm.DEFAULT_PARAMS)
    types = _types_for(pipe, beta=0)
    lp = lower(pipe, types, params=params)
    ls = lp.stages["img"]
    assert np.dtype(B.store_dtype(ls)) == np.uint8
    img = _img((48, 48), seed=23)
    raw = img.astype(np.uint8)              # beta=0: values == scaled ints
    assert np.array_equal(
        raw, np.asarray(B.quantize_input(img, ls.t, np.uint8, np)))
    for backend in ("interp", "jnp", "pallas"):
        run = compile_backend(lp, backend)
        a, b = run(img), run(raw)
        for stage in pipe.outputs:
            np.testing.assert_array_equal(
                np.asarray(a[stage]), np.asarray(b[stage]),
                err_msg=f"{backend}/{stage}: uint8 ingest != f64 ingest")


def test_prequantized_fractional_input_matches_f64_path():
    """Same convention off the trivial grid: beta=4 scaled ints in the
    legalized uint16 container replace the f64 quantization exactly."""
    pipe = usm.build()
    params = dict(usm.DEFAULT_PARAMS)
    types = _types_for(pipe)                # beta=4 -> 12-bit -> uint16
    lp = lower(pipe, types, params=params)
    ls = lp.stages["img"]
    dt = np.dtype(B.store_dtype(ls))
    assert dt == np.uint16
    img = _img((48, 48), seed=24)
    q = np.asarray(B.quantize_input(img, ls.t, dt, np))
    assert q.dtype == dt
    for backend in ("jnp", "pallas"):
        run = compile_backend(lp, backend)
        a, b = run(img), run(q)
        for stage in pipe.outputs:
            np.testing.assert_array_equal(
                np.asarray(a[stage]), np.asarray(b[stage]),
                err_msg=f"{backend}/{stage}: pre-quantized != f64 ingest")


@pytest.mark.parametrize("name,build,params,n_in,shape",
                         [BENCHES[0], BENCHES[3]], ids=["usm", "dus_ext"])
def test_pallas_prefetch_double_buffer_bit_exact(name, build, params,
                                                 n_in, shape):
    """The double-buffered band DMA kernel (interpret mode emulates the
    DMA copies + semaphores) stays bit-identical to the numpy oracle,
    single-frame and batched."""
    pipe = build()
    types = _types_for(pipe)
    lp = lower(pipe, types, params=params)
    run = compile_backend(lp, "pallas", interpret=True)
    img = _img(shape, seed=29)
    oracle = run_fixed(pipe, img, types, params)
    outs = run(img)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(
            np.asarray(oracle[stage]), outs[stage],
            err_msg=f"{name}/{stage}: prefetch kernel != oracle")
    batch = np.stack([img, _img(shape, seed=30)])
    per = [run_fixed(pipe, batch[i], types, params) for i in range(2)]
    outs_b = run(batch)
    for stage in pipe.outputs:
        np.testing.assert_array_equal(
            np.stack([np.asarray(p[stage]) for p in per]), outs_b[stage],
            err_msg=f"{name}/{stage}: batched prefetch != oracle")


@given(sampled_pipelines(), st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_F3_containers_and_prequantized_ingest_on_random_pipelines(pipe,
                                                                   seed):
    """Random DAGs: every integer-stored stage lands in its legalized
    container, and a pre-quantized container-dtype input round-trips
    bit-exact through the lowered backend."""
    res = analyze(pipe)
    if any(np.isinf(r.range.hi) or r.alpha > 24 for r in res.values()):
        return
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        types = {n: FixedPointType(alpha=max(r.alpha, 1), beta=2,
                                   signed=r.signed)
                 for n, r in res.items()}
    lp = lower(pipe, types)
    for n, ls in lp.stages.items():
        if ls.store_float or ls.t is None:
            continue
        lt = legalize(ls.t)
        if lt.fp is not None:
            assert np.dtype(B.store_dtype(ls)) == np.dtype(lt.dtype), n
    img = _img((16, 16), seed=seed)
    oracle = run_fixed(pipe, img, types)
    ls_in = lp.stages["img"]
    q = np.asarray(B.quantize_input(
        img, ls_in.t, np.dtype(B.store_dtype(ls_in)), np))
    env = compile_backend(lp, "jnp", outputs=list(pipe.stages))(q)
    for stage in pipe.topo_order():
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=stage)


# ---------------------------------------------------------------------------
# the host widening: every executor ships containers, `dequant_host`
# widens them to the oracle's f64 in one pass
# ---------------------------------------------------------------------------

from types import SimpleNamespace


@pytest.mark.parametrize("container", [np.uint8, np.int8, np.uint16,
                                       np.int16, np.uint32, np.int32,
                                       np.int64],
                         ids=lambda d: np.dtype(d).name)
def test_dequant_host_widens_every_container_bit_exact(container):
    """One ufunc pass equals ``astype(float64) * 2**-beta`` bit for bit
    at the container's extremes and zero, into a new float64 array, and
    into a given one (`out=`, a recycled buffer) that it overwrites
    whole."""
    info = np.iinfo(container)
    a = np.array([[info.min, 0, info.max], [info.max, info.min, 0]],
                 dtype=container)
    for beta in (0, 4, 13):
        ls = SimpleNamespace(store_float=False,
                             t=FixedPointType(alpha=8, beta=beta))
        want = a.astype(np.float64) * 2.0 ** -beta
        stale = np.full(a.shape, np.nan)
        got = B.dequant_host(ls, a)
        into = B.dequant_host(ls, a, out=stale)
        assert into is stale
        for g in (got, into):
            assert g.dtype == np.float64 and g.shape == a.shape
            np.testing.assert_array_equal(g.view(np.int64),
                                          want.view(np.int64), err_msg=beta)
            assert not np.shares_memory(g, a)


def test_dequant_host_passes_float_stored_tiles_through():
    tile = _img((4, 5), seed=3) / 7.0
    ls = SimpleNamespace(store_float=True, t=None)
    assert B.dequant_host(ls, tile) is tile


LOWERED_CONTAINERS = [("usm", usm.build, dict(usm.DEFAULT_PARAMS)),
                      ("hcd", hcd.build, {})]


@pytest.mark.parametrize("name,build,params", LOWERED_CONTAINERS,
                         ids=[c[0] for c in LOWERED_CONTAINERS])
def test_lowered_program_returns_stored_containers(name, build, params):
    """The jitted `lowered` program returns each int-stored stage in its
    in-program container (`fused_store_dtype`; inputs in `store_dtype`),
    not f64; float-stored stages stay f64."""
    import jax
    pipe = build()
    lp = lower(pipe, _types_for(pipe), params=params)
    run = compile_backend(lp, "jnp", outputs=list(pipe.stages))
    with jax.enable_x64(True):
        out = jax.eval_shape(run.forward, *[
            jax.ShapeDtypeStruct((24, 32), np.float64)
            for _ in pipe.input_stages()])
    ints = 0
    for n, ls in lp.stages.items():
        if ls.store_float:
            want = np.float64
        elif ls.stage.is_input:
            want = B.store_dtype(ls)
        else:
            want = B.fused_store_dtype(ls)
        assert out[n].dtype == np.dtype(want), n
        ints += out[n].dtype.kind in "iu"
    assert ints, f"{name}: no stage is int-stored"


@pytest.mark.parametrize("batched", [False, True],
                         ids=["unbatched", "batched"])
@pytest.mark.parametrize("name,build,params", LOWERED_CONTAINERS,
                         ids=[c[0] for c in LOWERED_CONTAINERS])
def test_lowered_run_widens_containers_to_oracle_f64(name, build, params,
                                                     batched):
    pipe = build()
    types = _types_for(pipe)
    imgs = np.stack([_img((24, 32), seed=41 + b) for b in range(2)])
    img = imgs if batched else imgs[0]
    oracle = run_fixed(pipe, img, types, params)
    env = run_fixed(pipe, img, types, params, backend="lowered")
    assert sorted(env) == sorted(pipe.stages)
    for stage in pipe.stages:
        assert env[stage].dtype == np.float64, stage
        np.testing.assert_array_equal(np.asarray(oracle[stage]), env[stage],
                                      err_msg=f"{name}/{stage}")


# ---------------------------------------------------------------------------
# recycled host buffers (`HostBuffers`): a result is never overwritten
# while any reference to it, or to a view of it, is alive
# ---------------------------------------------------------------------------

import threading  # noqa: E402

from repro import obs  # noqa: E402


def _pooled_usm(n_batches):
    """A fresh `jnp` executor of usm (so a pool of its own), distinct
    (3, 24, 32) batches and their oracle outputs."""
    pipe = usm.build()
    types = _types_for(pipe)
    params = dict(usm.DEFAULT_PARAMS)
    run = compile_backend(lower(pipe, types, params=params), "jnp")
    imgs = [np.stack([_img((24, 32), seed=900 + 3 * i + b)
                      for b in range(3)]) for i in range(n_batches)]
    oracle = [run_fixed(pipe, im, types, params) for im in imgs]
    return run, imgs, oracle


def _ptr(a):
    return a.__array_interface__["data"][0]


def test_released_host_buffer_is_reused():
    run, imgs, oracle = _pooled_usm(2)
    first = run(imgs[0])
    ptrs = {n: _ptr(a) for n, a in first.items()}
    del first
    again = run(imgs[1])
    assert {n: _ptr(a) for n, a in again.items()} == ptrs
    for n, a in again.items():
        np.testing.assert_array_equal(np.asarray(oracle[1][n]), a)


HOLDS = {
    "whole": lambda a: a,
    "frame": lambda a: a[1],
    "slice_of_frame": lambda a: a[1][2:7, 3:],
    "memoryview": lambda a: memoryview(a[1]),
}


@pytest.mark.parametrize("hold", sorted(HOLDS))
def test_held_host_buffer_is_never_reused(hold):
    """However the caller holds a result, no later call widens into its
    buffer, and its values stay as they were; the calls between still
    recycle the buffers they drop."""
    run, imgs, oracle = _pooled_usm(11)
    res = run(imgs[0])
    held = {n: HOLDS[hold](a) for n, a in res.items()}
    ptrs = {n: _ptr(a) for n, a in res.items()}
    want = {n: np.array(h, copy=True) for n, h in held.items()}
    del res
    B.HOST_BUFFER_STATS.reset()
    for im in imgs[1:]:
        out = run(im)
        for n, a in out.items():
            assert _ptr(a) != ptrs[n], n
            assert not np.shares_memory(a, np.asarray(held[n])), n
        del out
    assert B.HOST_BUFFER_STATS["reused"] > 0
    for n, h in held.items():
        np.testing.assert_array_equal(np.asarray(h).view(np.int64),
                                      want[n].view(np.int64), err_msg=n)
        np.testing.assert_array_equal(
            np.asarray(h), np.asarray(HOLDS[hold](np.asarray(oracle[0][n]))),
            err_msg=n)


def test_concurrent_callers_never_share_a_host_buffer():
    """Two threads on one executor: each holds its last result through
    its next call, and neither sees it change; buffers are recycled
    meanwhile."""
    run, imgs, oracle = _pooled_usm(16)
    run(imgs[0])                          # compile before the race
    B.HOST_BUFFER_STATS.reset()
    errors = []
    start = threading.Barrier(2)

    def worker(t):
        start.wait()
        prev = None
        for i in range(t, len(imgs), 2):
            out = run(imgs[i])
            for j, res in [(i, out)] + ([prev] if prev else []):
                for n, a in res.items():
                    if not np.array_equal(a, np.asarray(oracle[j][n])):
                        errors.append((t, j, n))
            prev = (i, out)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert B.HOST_BUFFER_STATS["reused"] > 0


def test_host_buffers_hand_each_buffer_to_one_thread_at_a_time():
    """More threads than cores share one pool with a short switch
    interval; each writes its own mark into the buffer it holds and
    finds it unchanged, so no buffer is ever held by two at once."""
    import os
    import sys
    import time
    pool = B.HostBuffers()
    n = min((os.cpu_count() or 4) + 1, 65)
    start = threading.Barrier(n)
    errors, hits = [], []

    def worker(t):
        start.wait()
        for _ in range(300):
            buf, hit = pool.acquire("y", (64,))
            buf[:] = t
            time.sleep(0)
            if not (buf == t).all():
                errors.append(t)
            hits.append(hit)
            del buf

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not errors and len(hits) == 300 * n
    assert any(hits)
    assert 1 <= len(pool._slots[("y", (64,))]) <= B._SLOT_BUFFERS


def _one_output_pipeline():
    """`run_on_device`'s arguments for a stand-in program with one
    int-stored output ``y`` and one float-stored output ``f``."""
    import jax.numpy as jnp
    lp = SimpleNamespace(stages={
        "y": SimpleNamespace(store_float=False,
                             t=FixedPointType(alpha=4, beta=1)),
        "f": SimpleNamespace(store_float=True, t=None)})
    x = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)

    def call(pool, k=0):
        return B.run_on_device(lp, ["y", "f"], lambda: x,
                               lambda a: {"y": a + k,
                                          "f": a.astype(jnp.float32)},
                               pool)
    return call


def test_host_buffer_pool_stays_bounded_when_every_result_is_kept():
    call = _one_output_pipeline()
    pool = B.HostBuffers()
    B.HOST_BUFFER_STATS.reset()
    n = B._SLOT_BUFFERS + 3
    kept = [call(pool, k) for k in range(n)]
    assert [len(s) for s in pool._slots.values()] == [B._SLOT_BUFFERS]
    assert dict(B.HOST_BUFFER_STATS) == {"reused": 0, "fresh": n}
    assert len({_ptr(r["y"]) for r in kept}) == n
    for k, r in enumerate(kept):
        np.testing.assert_array_equal(r["y"], (np.arange(6) + k).reshape(
            2, 3) / 2)
    del kept, r
    call(pool)                   # every buffer released: one is reused
    assert B.HOST_BUFFER_STATS["reused"] == 1
    assert [len(s) for s in pool._slots.values()] == [B._SLOT_BUFFERS]


def test_dequant_span_and_counters_count_reused_and_fresh():
    call = _one_output_pipeline()
    pool = B.HostBuffers()
    B.HOST_BUFFER_STATS.reset()
    with obs.tracing() as tr:
        a = call(pool)           # empty pool: fresh
        b = call(pool)           # a is held: fresh
        del a
        c = call(pool)           # a's buffer is free again
        del b, c
        call(pool)
    got = [(s.attrs["reused"], s.attrs["fresh"])
           for s in tr.spans("exec.dequant")]
    assert got == [(0, 1), (0, 1), (1, 0), (1, 0)]
    assert dict(B.HOST_BUFFER_STATS) == {"reused": 2, "fresh": 2}
    call(pool)                   # untraced: counted all the same
    assert dict(B.HOST_BUFFER_STATS) == {"reused": 3, "fresh": 2}
