"""The all-integer datapath of Harris corner detection under the
benchmark's plan (interval analysis, beta 4): integer products
(``intpoly``), the rational finishes of Sobel/12 and of harris's 0.04,
their exhaustive and near-tie checks against the oracle's IEEE f64
arithmetic, and an f64-free device program."""
import warnings

import numpy as np
import pytest

from repro import obs
from repro.analysis import run_plan
from repro.core.cost_model import design_cost, lowered_datapaths
from repro.core.fixedpoint import FixedPointType
from repro.dsl.builder import PipelineBuilder
from repro.dsl.exec import run_fixed
from repro.lowering import backends as B
from repro.lowering import compile_backend, lower
from repro.lowering.ir import DATAPATH_STATS
from repro.pipelines import hcd, usm

BETA = 4


def _plan(pipe):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return run_plan(pipe, ["interval"],
                        betas={n: BETA for n in pipe.stages})


@pytest.fixture(scope="module")
def hcd_lp():
    pipe = hcd.build()
    plan = _plan(pipe)
    return pipe, plan, plan.types(), lower(pipe, plan)


def _frames(shape, seed):
    return np.random.default_rng(seed).integers(
        0, 256, shape).astype(np.float64)


@pytest.mark.parametrize("backend", ["lowered", "pallas", "sharded"])
@pytest.mark.parametrize("shape", [(37, 50), (64, 128)])
def test_hcd_bit_equal_to_oracle_batched(hcd_lp, backend, shape):
    pipe, _, types, _ = hcd_lp
    imgs = _frames((2,) + shape, 3)
    served = run_fixed(pipe, imgs, types, backend=backend)
    for b in range(imgs.shape[0]):
        oracle = run_fixed(pipe, imgs[b], types)
        for k, v in served.items():
            np.testing.assert_array_equal(v[b], oracle[k],
                                          err_msg=f"{backend}/{k}")


def test_every_stage_on_a_proved_integer_election(hcd_lp):
    pipe, plan, _, lp = hcd_lp
    for n, ls in lp.stages.items():
        if ls.stage.is_input:
            continue
        assert ls.kind in ("intlinear", "intpoly"), n
        assert not ls.uses_f64, n
    assert lp.census(lp.order) == {"f64_stages": 0, "wide_stages": 2}
    assert {n for n, ls in lp.stages.items() if ls.wide} == {"det", "harris"}
    # products take the narrowest carrier their bound allows
    for n in ("Ixx", "Ixy", "Iyy"):
        assert lp.stages[n].carrier == "int32"
    notes = plan.provenance[plan.default_column].notes
    proved = {n.split(":")[0].split(".")[-1] for n in notes
              if n.startswith("datapath[exact] hcd.")}
    assert proved == {"Ix", "Iy", "Ixx", "Ixy", "Iyy", "det", "harris"}
    assert any("rational finish 1/12" in n for n in notes)
    assert any("rational finish -1/400" in n for n in notes)


def test_hcd_forward_has_no_f64(hcd_lp):
    """The jitted, vmapped forward the server runs at batch 4 holds no
    f64 op: the chip computes the same integers as the CPU."""
    import jax
    pipe, _, _, lp = hcd_lp
    run = compile_backend(lp, "jnp", outputs=list(pipe.stages))
    arg = jax.ShapeDtypeStruct((4, 40, 64),
                               np.dtype(B.store_dtype(lp.stages["img"])))
    with jax.enable_x64(True):
        text = jax.jit(jax.vmap(run.forward)).lower(arg).as_text()
    assert "f64" not in text
    assert "i64" in text          # det and harris carry int64


def test_sobel_finish_exhaustive(hcd_lp):
    """rint(fl(acc * cscale)), the oracle's finish of Ix, against the
    integer finish for every accumulator value the proof covers."""
    import jax.numpy as jnp
    ls = hcd_lp[3].stages["Ix"]
    assert ls.rat == (1, 12)
    acc = np.arange(-ls.acc_bound, ls.acc_bound + 1, dtype=np.int32)
    want = np.rint(acc.astype(np.float64) * ls.cscale)
    got = np.asarray(B.rational_round(jnp.asarray(acc), *ls.rat))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # the ties are real: acc = 12n + 6 lands on n + 1/2 and goes to even
    ties = acc[(acc % 12) == 6]
    assert ties.size > 2000
    np.testing.assert_array_equal(np.asarray(got)[(acc % 12) == 6] % 2, 0)


def _harris_oracle(det_q, trace_q):
    """The oracle's harris on scaled integers: det and trace on the 2^-4
    grid, IEEE f64 `det - 0.04 * trace**2`, then rint(* 16)."""
    det = det_q.astype(np.float64) * 2.0 ** -BETA
    tr = trace_q.astype(np.float64) * 2.0 ** -BETA
    return np.rint((det - hcd.HARRIS_K * tr ** 2) * 2.0 ** BETA)


def _harris_integer(ls, det_q, trace_q):
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        x = jnp.asarray(trace_q, jnp.int64) ** 2
        return np.asarray(B.rational_round(
            x, *ls.rat, base=jnp.asarray(det_q, jnp.int64)))


def test_harris_constant_exhaustive_over_trace(hcd_lp):
    """fl(0.04 * T) for every grid value of trace: the integer finish
    equals rint(16 * (det - fl(0.04 * T))) at det = 0 and at det values
    of both parities."""
    _, _, types, lp = hcd_lp
    ls = lp.stages["harris"]
    assert ls.rat == (-1, 400)
    t = types["trace"]
    tq = np.arange(t.int_min, t.int_max + 1, dtype=np.int64)
    for d in (0, 1, -7, 2 ** 35 + 1):
        det_q = np.full_like(tq, d)
        np.testing.assert_array_equal(_harris_integer(ls, det_q, tq),
                                      _harris_oracle(det_q, tq))


def test_harris_joint_rounding_random_and_near_ties(hcd_lp):
    _, _, types, lp = hcd_lp
    ls = lp.stages["harris"]
    td, tt = types["det"], types["trace"]
    rng = np.random.default_rng(20261018)
    det_q = rng.integers(td.int_min, td.int_max + 1, 1 << 20)
    tq = rng.integers(tt.int_min, tt.int_max + 1, 1 << 20)
    np.testing.assert_array_equal(_harris_integer(ls, det_q, tq),
                                  _harris_oracle(det_q, tq))
    # near-ties: trace values whose 0.04 * T * 16 sits closest to a
    # half-integer, against det at the ends of its range (largest ulp of
    # det - 0.04 * T) and of both parities
    all_t = np.arange(tt.int_min, tt.int_max + 1, dtype=np.int64)
    frac = np.abs((all_t ** 2 % 400) - 200)
    near = all_t[np.argsort(frac, kind="stable")[:4096]]
    assert int(frac.min()) == 1          # 1/400 of a unit from the tie
    ends = np.array([td.int_min, td.int_min + 1, -1, 0, 1,
                     td.int_max - 1, td.int_max], dtype=np.int64)
    det_q = np.repeat(ends, near.size)
    tq = np.tile(near, ends.size)
    np.testing.assert_array_equal(_harris_integer(ls, det_q, tq),
                                  _harris_oracle(det_q, tq))


def _two_input_pipe(k):
    p = PipelineBuilder("twoin")
    a = p.image("a", 0, 255)
    b = p.define("b", a + 0.0)
    c = p.define("c", k * (a * b))
    p.output(c)
    types = {"a": FixedPointType(8, 0, signed=False),
             "b": FixedPointType(8, 0, signed=False),
             "c": FixedPointType(14, 4, signed=False)}
    return p.build(), types


def test_unproved_stage_stays_on_f64_with_its_reason():
    """A non-dyadic constant times a product of two inputs cannot be
    enumerated: the stage keeps the f64 replay and says why."""
    pipe, types = _two_input_pipe(0.1)
    lp = lower(pipe, types)
    ls = lp.stages["c"]
    assert ls.kind == "expr" and ls.uses_f64
    assert ls.proof.startswith("f64 kept:")
    assert "one input value" in ls.proof
    img = _frames((16, 16), 4)
    np.testing.assert_array_equal(run_fixed(pipe, img, types,
                                            backend="lowered")["c"],
                                  run_fixed(pipe, img, types)["c"])


def test_dyadic_product_of_two_inputs_is_intpoly():
    pipe, types = _two_input_pipe(0.375)
    lp = lower(pipe, types)
    assert lp.stages["c"].kind == "intpoly"
    assert lp.stages["c"].finish == "shift"
    img = _frames((16, 16), 5)
    for backend in ("lowered", "pallas"):
        np.testing.assert_array_equal(
            run_fixed(pipe, img, types, backend=backend)["c"],
            run_fixed(pipe, img, types)["c"], err_msg=backend)


def test_datapath_counters_and_executor_span_attributes(hcd_lp):
    pipe, _, types, _ = hcd_lp
    DATAPATH_STATS.reset()
    lp = lower(pipe, types)
    snap = dict(DATAPATH_STATS)
    assert snap["lowerings"] == 1
    assert snap["intlinear.int32.rational"] == 2
    assert snap["intlinear.int32.shift"] == 4
    assert snap["intpoly.int32.shift"] == 3
    assert snap["intpoly.int64.shift"] == 1
    assert snap["intpoly.int64.rational"] == 1
    img = _frames((2, 24, 32), 6)
    with obs.tracing() as tr:
        compile_backend(lp, "jnp")(img)
        up = usm.build()
        ut = _plan(up).types()
        compile_backend(lower(up, ut, params=dict(usm.DEFAULT_PARAMS)),
                        "jnp")(img)
    spans = [s for s in tr.spans() if s.name == "exec.lowered"]
    by = {s.attrs["pipeline"]: s.attrs for s in spans}
    assert (by["hcd"]["f64_stages"], by["hcd"]["wide_stages"]) == (0, 2)
    assert by["usm"]["f64_stages"] == 2


def test_cost_model_prices_the_elections(hcd_lp):
    pipe, _, types, lp = hcd_lp
    dps = lowered_datapaths(lp)
    assert dps["Ixx"] == {"kind": "intpoly", "carrier": "int32",
                          "finish": "shift"}
    assert dps["harris"]["finish"] == "rational"
    assert dps["Ix"]["finish"] == "rational"
    assert design_cost(pipe, types, datapaths=dps).power_proxy > 0
