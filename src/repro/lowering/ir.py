"""Typed per-stage program IR for plan-driven lowering.

`lower()` (see `repro.lowering.lower_pipeline`) turns `(Pipeline,
BitwidthPlan)` into a `LoweredPipeline`: one `LoweredStage` per stage
carrying everything a backend needs to synthesize the stage's datapath —
quantized integer taps, beta-alignment shifts, the finishing rule
(dyadic round-half-even shift or one f64 scale multiply), per-axis halos,
sampling rates, saturation bounds, and per-phase datapaths (one set of
bounds per sampling-lattice residue, the paper §IV homogeneity clusters).

Datapath-kind selection is the load-bearing decision.  The bit-exactness
contract with the `run_fixed` per-pixel oracle (numpy f64) rests on two
facts:

  * an ``expr`` stage re-issues the oracle's IEEE-754 double ops in the
    identical order (`dsl.exec.eval_expr` is shared), so it is equal by
    construction;
  * an ``intlinear`` stage replaces the oracle's float tree with integer
    multiply-accumulates, which is equal **iff the oracle's float math was
    exact**: all taps are dyadic multiples of on-grid inputs and every
    partial sum stays below 2^53.  `_plan_intlinear` proves that bound
    from the input types before electing the integer path; anything it
    cannot prove falls back to ``expr``.

The finishing step after an integer accumulation:

  value = s * acc / 2^(w_beta + bmax),   q_out = rint(value * 2^beta_out)

  * dyadic s = sm/2^se  ->  q_out = round_half_even(acc * sm, t) with
    t = se + w_beta + bmax - beta_out (pure integer datapath);
  * otherwise the oracle computes q_out = rint(fl(acc * cscale)) with
    cscale = s * 2^(beta_out - w_beta - bmax) (scaling a double by a power
    of two is lossless).  `_prove_rational` elects an integer *rational
    finish* ``(a, b)``: h = floor((2a*acc + b) / 2b), a remainder of 0 is
    a tie and goes to even.  It is checked against the oracle's own IEEE
    multiply and `rint` for every accumulator value in the proved range,
    so the integers reproduce both roundings, ties included.  Where no
    such proof exists the stage keeps that one f64 multiply, and says
    why in `proof`.

An ``intpoly`` stage is a polynomial of on-grid stages: +, -, *, `Pow`
and dyadic constants.  Its oracle f64 tree is exact while every node's
scaled magnitude stays below 2^53 (the `_plan_intpoly` walk), so the
same tree evaluated on the scaled integers, finished by the
round-half-even shift, is bit-equal.  One non-dyadic constant is allowed
at the root, as ``A ± c * X`` or ``c * X``: the oracle then rounds
``fl(c * X)`` and ``fl(A ± .)`` before its `rint`.  X must be a function
of one input value (enumerable), and the same rational finish is proved
for it: every X value is enumerated, and the inner ``fl(A ± .)`` can
only matter where ``±c*X*2^beta`` lies within half an ulp of |A ± .|'s
largest value from a half-integer, which the proof rules out (or finds
exact ties, which go to even on the integers as in the oracle).

**Narrow datapath re-election** (`lower(..., datapath="narrow")`) is the
real-hardware mode: the exact-mode election above happily hands out
int64 carriers and f64 expression datapaths, which no FPGA/TPU lane
holds natively.  Narrow mode re-elects every datapath int32/f32-first,
and only keeps a 64-bit resource when it can *prove* no narrower one is
bit-exact — recording each election (and each justified retention) in
the plan's provenance:

  * accumulator bounds are re-tightened per tap from the plan's
    per-phase columns (a tap that only ever lands on low-magnitude
    lattice residues is bounded by those residues' types, not the union
    column — edge clamps handled conservatively);
  * an accumulator whose tightened bound still exceeds `INT32_BUDGET`
    is *split* into two int32 partial accumulators (`carrier =
    "int32pair"`, taps partitioned by `acc_split`), combined by one wide
    add before the finishing rule — bit-equal because integer adds are
    associative and the combined value stays below 2^53;
  * an `expr` stage is demoted to f32 evaluation (`expr_dtype = "f32"`)
    when a value-grid walk over its tree proves every intermediate is a
    dyadic rational whose scaled magnitude fits a 24-bit mantissa — then
    every f32 op is exact, hence bit-identical to the oracle's f64 ops.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.fixedpoint import FixedPointType
from repro.core.graph import BinOp, Const, Expr, Pipeline, Pow, Ref, Stage

Residue = Tuple[int, int]


class LoweringError(ValueError):
    """The pipeline (or shape) cannot be lowered by the requested backend."""


# ---------------------------------------------------------------------------
# linear-form matching (generalizes kernels/stencil/ops.py tap extraction)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Tap:
    """One structural stencil tap: `w * input[(i+dy, j+dx)]`."""
    stage: str
    dy: int
    dx: int
    w: float


def match_linear(expr: Expr) -> Optional[Tuple[Tuple[Tap, ...], float]]:
    """Match `[Const(s) *] (sum/difference of [Const(w) *] Ref taps)`.

    This is exactly the shape `core.graph.stencil_expr` emits (plus bare
    linear point-wise stages like ``img2 - img1``), multi-input included.
    Returns (taps, scale) or None when the stage is not a linear stencil.
    """
    scale = 1.0
    body = expr
    if isinstance(body, BinOp) and body.op == "*" \
            and isinstance(body.left, Const) \
            and not isinstance(body.right, (Ref, Const)):
        scale = float(body.left.value)
        body = body.right
    taps: List[Tap] = []

    def go(n: Expr, sign: int) -> bool:
        if isinstance(n, BinOp) and n.op == "+":
            return go(n.left, sign) and go(n.right, sign)
        if isinstance(n, BinOp) and n.op == "-":
            return go(n.left, sign) and go(n.right, -sign)
        if isinstance(n, BinOp) and n.op == "*" \
                and isinstance(n.left, Const) and isinstance(n.right, Ref):
            r = n.right
            taps.append(Tap(r.stage, r.dy, r.dx, sign * float(n.left.value)))
            return True
        if isinstance(n, Ref):
            taps.append(Tap(n.stage, n.dy, n.dx, float(sign)))
            return True
        return False

    if not go(body, 1) or not taps:
        return None
    return tuple(taps), scale


def dyadic_weights(vals: Sequence[float], max_beta: int = 24
                   ) -> Optional[Tuple[List[int], int]]:
    """Smallest w_beta with every `v * 2^w_beta` an exact integer, else None.

    The exact-only core of `kernels.stencil.ops.quantize_weights` (which
    additionally accepts lossy rounding at its beta cap)."""
    for w_beta in range(max_beta + 1):
        sc = 1 << w_beta
        if all(float(v) * sc == int(v * sc) for v in vals):
            return [int(v * sc) for v in vals], w_beta
    return None


def dyadic_scale(s: float, max_num: int = 1 << 20,
                 max_exp: int = 64) -> Optional[Tuple[int, int]]:
    """`s == sm / 2^se` with a small odd-ish integer sm, else None."""
    if s == 0 or not math.isfinite(s):
        return None
    f = Fraction(s)          # exact: every float is p/2^k
    den = f.denominator
    if den & (den - 1) != 0:         # not a power of two (cannot happen for
        return None                  # floats, but keep the guard explicit)
    se = den.bit_length() - 1
    sm = f.numerator
    if abs(sm) > max_num or se > max_exp:
        return None
    return sm, se


# ---------------------------------------------------------------------------
# lowered stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IntTap:
    """Beta-aligned integer tap: `W * q_in[(i+dy, j+dx)]` on scaled ints."""
    stage: str
    dy: int
    dx: int
    W: int


@dataclasses.dataclass
class PhaseSnap:
    """Per-phase datapaths: one output type per sampling-lattice residue.

    `int_ok` marks the common case where every residue shares the union
    column's beta — the residue split then only changes the saturation
    bounds, so the integer datapath re-clips per residue.  Mixed betas
    (possible with hand-built type maps) force the float path: the oracle
    re-snaps each residue's raw value onto a different grid.
    """
    lattice: Tuple[int, int]                     # (My, Mx)
    types: Dict[Residue, FixedPointType]
    int_ok: bool = True


@dataclasses.dataclass
class LoweredStage:
    name: str
    kind: str                        # "input"|"intlinear"|"intpoly"|"expr"
    stage: Stage                     # original IR node (expr/stride/upsample)
    t: Optional[FixedPointType]      # union-column output type (None = float)
    halo: Tuple[int, int]            # per-axis (hy, hx)
    # -- intlinear datapath ---------------------------------------------------
    int_taps: Tuple[IntTap, ...] = ()
    sm: int = 1                      # dyadic finishing numerator
    t_shift: int = 0                 # dyadic finishing right-shift (may be <0)
    dyadic: bool = True
    cscale: float = 1.0              # f64 finishing multiplier (non-dyadic)
    carrier: str = "int64"           # accumulator ("int32"|"int32pair"|"int64")
    acc_bound: int = 0               # proved |accumulator| bound
    # int32pair: int_taps[:acc_split] / int_taps[acc_split:] accumulate in
    # separate int32 registers, combined by one wide add before finishing
    acc_split: int = 0
    # -- expr datapath --------------------------------------------------------
    expr_dtype: str = "f64"          # "f32" only under a narrow-mode proof
    # -- saturation -----------------------------------------------------------
    phase: Optional[PhaseSnap] = None
    # backends keep this stage's tile as f64 values instead of scaled ints
    # (untyped, wider than a double's mantissa, or residue-mixed-beta)
    store_float: bool = False
    # narrow-mode election record ("" in exact mode): the chosen datapath,
    # with the proof obligation that blocked anything narrower
    election: str = ""
    # -- exact integer finish and intpoly datapath -------------------------
    # rational finish (a, b): h = floor((2a*x + b) / 2b), ties to even
    # (intlinear: x = acc; intpoly: x = the value of `rat_term`)
    rat: Optional[Tuple[int, int]] = None
    poly_exact: Optional[Expr] = None    # intpoly: the exact part A (or None)
    rat_term: Optional[Expr] = None      # intpoly: X of A ± c*X (or None)
    # the proof behind an integer finish or intpoly election, or the reason
    # a stage stays on f64; recorded in the plan's provenance
    proof: str = ""

    @property
    def finish(self) -> str:
        """How the stage lands on its output grid: "shift" (round-half-
        even shift), "rational" (exact integer finish of a non-dyadic
        scale), "f64" (one IEEE multiply) or "float" (an expr snap)."""
        if self.kind == "expr":
            return "float"
        if self.rat is not None:
            return "rational"
        if self.kind == "intlinear" and not self.dyadic:
            return "f64"
        return "shift"

    @property
    def datapath_carrier(self) -> str:
        """The register the stage's arithmetic runs in."""
        if self.kind in ("intlinear", "intpoly"):
            return self.carrier
        return self.expr_dtype

    @property
    def uses_f64(self) -> bool:
        """True when the device program touches f64 for this stage."""
        if self.kind == "input":
            return self.store_float
        return (self.store_float or self.finish == "f64"
                or self.datapath_carrier == "f64")

    @property
    def wide(self) -> bool:
        """True for an int64 or int32-pair carrier."""
        return (self.kind in ("intlinear", "intpoly")
                and self.carrier in ("int64", "int32pair"))


@dataclasses.dataclass
class LoweredPipeline:
    """Topologically ordered typed program — what backends compile."""
    pipeline: Pipeline
    stages: Dict[str, LoweredStage]          # in topo order
    order: List[str]
    params: Dict[str, float]
    types: Dict[str, Optional[FixedPointType]]
    column: Optional[str] = None             # plan column, if plan-derived
    datapath: str = "exact"                  # "exact" | "narrow"

    def outputs(self) -> List[str]:
        return list(self.pipeline.outputs)

    def kinds(self) -> Dict[str, str]:
        return {n: s.kind for n, s in self.stages.items()}

    def census(self, names: Sequence[str]) -> Dict[str, int]:
        """``f64_stages`` and ``wide_stages`` among ``names`` (a program's
        stages): the attributes every executor span carries."""
        return _census(self.stages[n] for n in names)


def _census(stages) -> Dict[str, int]:
    sts = list(stages)
    return dict(f64_stages=sum(s.uses_f64 for s in sts),
                wide_stages=sum(s.wide for s in sts))


# ---------------------------------------------------------------------------
# datapath planning
# ---------------------------------------------------------------------------

# stages of each `lower()` by "<kind>.<carrier>.<finish>" (and "lowerings")
DATAPATH_STATS = obs.CounterGroup("lowering.datapath", lowerings=0)

F64_EXACT = 1 << 53      # integer sums below this are exact IEEE doubles
F32_EXACT = 1 << 24      # scaled magnitudes below this are exact IEEE singles
INT32_BUDGET = 1 << 30


def _qabs(t: FixedPointType) -> int:
    return max(abs(t.int_min), t.int_max)


def _touched_residues(s: int, u: int, d: int, m: int) -> Optional[set]:
    """Row (or col) residues mod `m` a tap offset `d` can read, or None.

    The consumer reads input index `floor((y*s + d)/u)`; over one lattice
    period (`y` mod `m*u`) the unclamped indices hit a fixed residue set.
    Edge clamping is handled conservatively: a negative offset can clamp
    onto index 0 (residue 0, added); a positive offset can clamp onto
    `H-1`, whose residue is shape-dependent — unknown at lowering time,
    so the caller falls back to the union bound (None).
    """
    if m <= 1:
        return {0}
    res = {((y * s + d) // u) % m for y in range(m * u)}
    if d < 0:
        res.add(0)
    if d > 0:
        return None
    return res


def _tap_qabs_narrow(st: Stage, tp: Tap, t_in: FixedPointType,
                     phase_in: Optional["PhaseSnap"]) -> int:
    """Tightened |q| bound for one tap from the input's per-phase types.

    Sound because the runtime (every backend and the oracle alike) clips
    the input stage per lattice residue, so a stored value at residue
    (ry, rx) obeys that residue's saturation bounds.
    """
    if phase_in is None or not phase_in.int_ok:
        return _qabs(t_in)
    my, mx = phase_in.lattice
    ry = _touched_residues(st.stride[0], st.upsample[0], tp.dy, my)
    rx = _touched_residues(st.stride[1], st.upsample[1], tp.dx, mx)
    if ry is None or rx is None:
        return _qabs(t_in)
    best = 0
    for a in ry:
        for b in rx:
            t_ph = phase_in.types.get((a, b), t_in)
            best = max(best, _qabs(t_ph))
    return best


def _split_int32(tap_bounds: List[int]
                 ) -> Optional[Tuple[List[int], int]]:
    """2-partition tap indices so each partial sum stays under the int32
    budget.  Returns `(reordered_indices, split_at)` — taps before the
    split accumulate in one int32 register, the rest in the other — or
    None when no split exists.  Integer adds are associative and
    commutative, so any regrouping is bit-exact."""
    if len(tap_bounds) < 2:
        return None
    order = sorted(range(len(tap_bounds)), key=lambda i: -tap_bounds[i])
    a: List[int] = []
    b: List[int] = []
    sa = sb = 0
    for i in order:
        if sa <= sb:
            a.append(i)
            sa += tap_bounds[i]
        else:
            b.append(i)
            sb += tap_bounds[i]
    if sa >= INT32_BUDGET or sb >= INT32_BUDGET or not a or not b:
        return None
    return a + b, len(a)


def _expr_fits_f32(st: Stage, t_out: Optional[FixedPointType],
                   in_types: Dict[str, Optional[FixedPointType]],
                   float_stored: set,
                   phase: Optional["PhaseSnap"]) -> Optional[str]:
    """Proof that f32 evaluation of `st.expr` is bit-identical to f64.

    Walks the tree tracking an exact dyadic value grid `(bound, e)`:
    every node's value is `k * 2^-e` with `|k| <= bound`.  When every
    node keeps `bound < 2^24` (and `e` well inside the exponent range),
    each op's result is exactly representable in BOTH f32 and f64, so
    neither rounds — the two evaluations are equal, and the final snap
    (`rint` after a lossless power-of-two rescale, clip against
    f32-exact bounds) is the same single rounding the oracle performs.

    Returns None when the proof succeeds, else the retention reason.
    """
    if t_out is None:
        return "untyped output"
    if phase is not None:
        return "phase-split residues re-snap per lattice residue"
    if _qabs(t_out) >= F32_EXACT:
        return (f"output grid needs "
                f"{_qabs(t_out).bit_length()} magnitude bits")
    if abs(t_out.beta) > 60:
        return "output beta outside f32 exponent headroom"

    class _No(Exception):
        pass

    def fail(msg: str):
        raise _No(msg)

    def chk(b: int, e: int) -> Tuple[int, int]:
        if b >= F32_EXACT:
            fail(f"a node needs {b.bit_length()} magnitude bits")
        if e > 60:
            fail("a node's beta exceeds f32 exponent headroom")
        return b, e

    def go(n: Expr) -> Tuple[int, int]:
        from repro.core.graph import Call, Cmp, ParamRef, Pow, Select
        if isinstance(n, Const):
            if n.value == 0:
                return 0, 0
            ds = dyadic_scale(float(n.value), max_num=F32_EXACT - 1,
                              max_exp=60)
            if ds is None:
                fail(f"constant {n.value!r} is not f32-exact")
            return chk(abs(ds[0]), ds[1])
        if isinstance(n, Ref):
            t = in_types.get(n.stage)
            if t is None:
                fail(f"input {n.stage!r} is untyped")
            if n.stage in float_stored:
                fail(f"input {n.stage!r} is float-stored")
            return chk(_qabs(t), t.beta)
        if isinstance(n, ParamRef):
            fail(f"runtime parameter {n.name!r} has no proven grid")
        if isinstance(n, BinOp):
            if n.op == "/":
                fail("division rounds")
            (bl, el), (br, er) = go(n.left), go(n.right)
            if n.op == "*":
                return chk(bl * br, el + er)
            e = max(el, er)
            return chk((bl << (e - el)) + (br << (e - er)), e)
        if isinstance(n, Pow):
            b, e = go(n.base)
            if n.n < 0:
                fail("negative power rounds")
            return chk(b ** n.n, e * n.n)
        if isinstance(n, Call):
            if n.fn == "sqrt":
                fail("sqrt rounds")
            gs = [go(a) for a in n.args]
            e = max(ee for _, ee in gs)
            return chk(max(bb << (e - ee) for bb, ee in gs), e)
        if isinstance(n, Cmp):
            go(n.left)
            go(n.right)
            return 1, 0      # exact compare of exact values
        if isinstance(n, Select):
            go(n.cond)
            gs = [go(n.then), go(n.other)]
            e = max(ee for _, ee in gs)
            return chk(max(bb << (e - ee) for bb, ee in gs), e)
        fail(f"unsupported node {type(n).__name__}")

    try:
        go(st.expr)
    except _No as exc:
        return str(exc)
    return None


def _plan_intlinear(st: Stage, taps: Tuple[Tap, ...], scale: float,
                    t_out: FixedPointType,
                    in_types: Dict[str, Optional[FixedPointType]],
                    narrow: bool = False,
                    in_phases: Optional[Dict[str, "PhaseSnap"]] = None):
    """Integer-datapath parameters, or None when exactness is unprovable.

    With `narrow=True` the carrier election is int32-first: accumulator
    bounds are tightened per tap from the inputs' per-phase types, and a
    bound over `INT32_BUDGET` is split across an int32 pair before an
    int64 carrier is conceded (the retention reason lands in `election`).
    """
    if any(in_types.get(tp.stage) is None for tp in taps):
        return None
    w = dyadic_weights([tp.w for tp in taps])
    if w is None:
        return None
    wq, w_beta = w
    bmax = max(in_types[tp.stage].beta for tp in taps)
    int_taps: List[IntTap] = []
    tap_bounds: List[int] = []
    for tp, q in zip(taps, wq):
        t_in = in_types[tp.stage]
        W = q << (bmax - t_in.beta)
        if W == 0:
            continue
        qa = (_tap_qabs_narrow(st, tp, t_in, (in_phases or {}).get(tp.stage))
              if narrow else _qabs(t_in))
        int_taps.append(IntTap(tp.stage, tp.dy, tp.dx, W))
        tap_bounds.append(abs(W) * qa)
    bound = sum(tap_bounds)
    if bound >= F64_EXACT:
        # the oracle's own float sum may round — only `expr` replays that
        return None
    ds = dyadic_scale(scale)
    if ds is not None:
        sm, se = ds
        t_shift = se + w_beta + bmax - t_out.beta
        # the oracle computes fl(s * sum): exact only while |sm * acc|
        # fits a double's mantissa — beyond that the float tree rounds and
        # only the `expr` kind can replay it.  The carrier must hold the
        # *finished* value too: a negative t_shift left-shifts the product
        # (beta_out deeper than the input grid), so bound the post-shift
        # magnitude, not just the accumulator.
        prod = bound * abs(sm)
        if t_shift < 0:
            fin = prod << (-t_shift)
        else:
            fin = prod + (1 << max(t_shift - 1, 0))
        if fin >= F64_EXACT:
            return None
        plan = dict(int_taps=tuple(int_taps), sm=sm, t_shift=t_shift,
                    dyadic=True, cscale=1.0, acc_bound=bound)
        gate = fin       # the finishing multiply/shift runs in-carrier
    else:
        # non-dyadic scale: the oracle's fl(scale * sum), then rint.  An
        # integer rational finish where `_prove_rational` reproduces both
        # roundings over every accumulator value; else one f64 multiply,
        # bit-equal to the oracle's (power-of-two rescale is lossless)
        cscale = scale * 2.0 ** (t_out.beta - w_beta - bmax)
        plan = dict(int_taps=tuple(int_taps), sm=1, t_shift=0, dyadic=False,
                    cscale=cscale, acc_bound=bound)
        gate = bound
        if 2 * bound + 1 > ENUM_CAP:
            plan["proof"] = (f"f64 finish kept: accumulator range "
                             f"2^{(2 * bound + 1).bit_length()} exceeds the "
                             f"enumeration cap 2^{ENUM_CAP.bit_length() - 1}")
        else:
            rat = _prove_rational(scale, 1, t_out.beta, w_beta + bmax,
                                  -bound, bound, None)
            if isinstance(rat, str):
                plan["proof"] = f"f64 finish kept: {rat}"
            else:
                (a, b), ties = rat
                plan.update(rat=(a, b), proof=(
                    f"rational finish {a}/{b}: rint(fl(acc*{scale!r})) "
                    f"reproduced for every acc in [{-bound}, {bound}] "
                    f"({ties} ties to even)"))
                gate = max(bound, 2 * abs(a) * bound + b)
    if gate < INT32_BUDGET:
        plan.update(carrier="int32", acc_split=0,
                    election="int32" if narrow else "")
        return plan
    if not narrow:
        plan.update(carrier="int64", acc_split=0)
        return plan
    # narrow mode: split the accumulation across an int32 pair when every
    # partial sum fits; the widening combine + finish run in int64
    if bound < INT32_BUDGET:
        sp = (list(range(len(int_taps))), len(int_taps))
    else:
        sp = _split_int32(tap_bounds)
    if sp is not None:
        order_ix, k = sp
        plan["int_taps"] = tuple(int_taps[i] for i in order_ix)
        plan.update(
            carrier="int32pair", acc_split=k,
            election=(f"int32pair: acc bound 2^{bound.bit_length()} split "
                      f"{k}+{len(int_taps) - k} taps under INT32_BUDGET"))
        return plan
    why = ("a single tap's bound exceeds INT32_BUDGET"
           if max(tap_bounds) >= INT32_BUDGET
           else "no 2-way tap split fits INT32_BUDGET")
    plan.update(carrier="int64", acc_split=0,
                election=(f"int64 kept: acc bound "
                          f"2^{bound.bit_length()} — {why}"))
    return plan


ENUM_CAP = 1 << 22        # grid values a rational-finish proof enumerates
RAT_MAX_DEN = 1 << 20     # largest denominator of a rational finish
INT64_BUDGET = 1 << 62


def _convergents(r: Fraction, max_den: int):
    """Continued-fraction convergents ``(a, b)`` of ``r``, b <= max_den."""
    sign = -1 if r < 0 else 1
    x = abs(r)
    h0, h1, k0, k1 = 0, 1, 1, 0
    while True:
        q = x.numerator // x.denominator
        h0, h1, k0, k1 = h1, q * h1 + h0, k1, q * k1 + k0
        if k1 > max_den:
            return
        yield sign * h1, k1
        x -= q
        if x == 0:
            return
        x = 1 / x


@functools.lru_cache(maxsize=64)
def _prove_rational(c: float, s: int, beta: int, e: int, lo: int, hi: int,
                    abound: Optional[int], xs_key=None):
    """Exact integer finish of the oracle's ``rint(2^beta * fl(A + s *
    fl(c * X)))`` (``A`` absent when ``abound`` is None), or the reason
    there is none.

    X takes the values ``x * 2^-e`` for the integers x in ``[lo, hi]``
    (or, with ``xs_key = (expr, input beta)``, the scaled values of the
    univariate polynomial `expr` over that range of its one input).  A
    is on the output grid, ``|A * 2^beta| <= abound``.  Every x is
    enumerated, and numpy's IEEE ``c * X`` (the oracle's own op) gives
    sY = s * 2^beta * fl(c * X) exactly.  With N = A*2^beta + floor(sY)
    the oracle returns N + [sY - floor(sY) > 1/2], or the even of N and
    N + 1 on an exact tie, provided the inner fl(A + .) never lands on a
    half-integer that sY does not: its half-ulp at the largest
    |A*2^beta + sY| must be below every non-tie distance from a
    half-integer (checked with a factor-2 margin against the rounding of
    that distance).  The device computes
    h = floor((2a*x + b) / 2b), a tie where the remainder is 0; the
    proof is that h and the tie flags equal the oracle's for every x.
    Returns ``((a, b), ties)`` or a reason string."""
    import numpy as np
    xs = np.arange(lo, hi + 1, dtype=np.int64)
    if xs_key is not None:
        expr, in_beta = xs_key
        xs, ex = int_eval(expr, lambda r: xs, lambda n: in_beta)
        assert ex == e, (ex, e)
    if not 2.0 ** -60 <= abs(c) <= 2.0 ** 60:
        return f"constant {c!r} outside the normal range"
    sy = s * (c * (xs.astype(np.float64) * 2.0 ** -e)) * 2.0 ** beta
    top = float(np.max(np.abs(sy)))
    if top >= 2.0 ** 50:
        return "the scaled term leaves the exact-integer range"
    k = np.floor(sy)
    half_pt = k + 0.5                    # exact: |k| < 2^50
    tie = sy == half_pt
    want = k.astype(np.int64) + (sy >= half_pt)
    ties = int(np.count_nonzero(tie))
    if abound is not None:
        zmax = abound + int(top) + 2
        if zmax >= 1 << 50:
            return f"|A ± c*X| reaches 2^{zmax.bit_length()}"
        half_ulp = 2.0 ** (zmax.bit_length() - 1 - 53)
        near = ~tie & (np.abs(sy - half_pt) <= 2 * half_ulp)
        if near.any():
            return (f"fl(A ± c*X) may round onto a half-integer "
                    f"({int(np.count_nonzero(near))} values of X)")
    xmax = int(np.max(np.abs(xs)))
    r = s * Fraction(c) * Fraction(2) ** (beta - e)
    for a, b in _convergents(r, RAT_MAX_DEN):
        if 2 * abs(a) * xmax + b >= INT64_BUDGET:
            break
        y = xs * (2 * a) + b
        h = y // (2 * b)
        if np.array_equal(h, want) and np.array_equal(y - h * (2 * b) == 0,
                                                      tie):
            return (a, b), ties
    return (f"no rational a/b with b <= 2^{RAT_MAX_DEN.bit_length() - 1} "
            f"reproduces rint(fl({c!r} * X)) over {xs.size} values")


def int_eval(e: Expr, leaf, beta_of):
    """``(k, exponent)``: the value of polynomial `e` is ``k * 2^-exponent``
    with integer ``k``.  ``leaf(ref)`` gives a `Ref`'s scaled integers
    (numpy or jnp arrays), ``beta_of(stage)`` its grid; constants are
    dyadic (`_poly_walk` proved the tree exact and within its carrier).
    Sums align on the finer grid by left shifts, so no bit is lost."""
    def go(n: Expr):
        if isinstance(n, Const):
            if n.value == 0:
                return 0, 0
            return dyadic_scale(float(n.value), max_num=F64_EXACT - 1,
                                max_exp=60)
        if isinstance(n, Ref):
            return leaf(n), beta_of(n.stage)
        if isinstance(n, Pow):
            v, ev = go(n.base)
            out = v
            for _ in range(n.n - 1):
                out = out * v
            return out, ev * n.n
        (lv, el), (rv, er) = go(n.left), go(n.right)
        if n.op == "*":
            return lv * rv, el + er
        ex = max(el, er)
        if ex > el:
            lv = lv << (ex - el)
        if ex > er:
            rv = rv << (ex - er)
        return (lv + rv if n.op == "+" else lv - rv), ex

    return go(e)


class _NotPoly(Exception):
    pass


def _poly_walk(e: Expr, in_types: Dict[str, Optional[FixedPointType]],
               float_stored: set) -> Tuple[int, int, int]:
    """(bound, exponent, max node bound) of an exact polynomial: every
    node's value is k * 2^-exponent with |k| <= bound < 2^53, so the
    oracle's f64 op at that node is exact.  Raises `_NotPoly(reason)`."""
    from repro.core.graph import Call, Cmp, ParamRef, Select
    peak = 0

    def chk(b: int, ex: int) -> Tuple[int, int]:
        nonlocal peak
        if b >= F64_EXACT:
            raise _NotPoly(f"a node needs {b.bit_length()} magnitude bits")
        peak = max(peak, b)
        return b, ex

    def go(n: Expr) -> Tuple[int, int]:
        if isinstance(n, Const):
            if n.value == 0:
                return 0, 0
            ds = dyadic_scale(float(n.value), max_num=F64_EXACT - 1,
                              max_exp=60)
            if ds is None:
                raise _NotPoly(f"constant {n.value!r} is not dyadic")
            return chk(abs(ds[0]), ds[1])
        if isinstance(n, Ref):
            t = in_types.get(n.stage)
            if t is None or n.stage in float_stored:
                raise _NotPoly(f"input {n.stage!r} has no integer grid")
            return chk(_qabs(t), t.beta)
        if isinstance(n, ParamRef):
            raise _NotPoly(f"runtime parameter {n.name!r}")
        if isinstance(n, BinOp):
            if n.op == "/":
                raise _NotPoly("division rounds")
            (bl, el), (br, er) = go(n.left), go(n.right)
            if n.op == "*":
                return chk(bl * br, el + er)
            ex = max(el, er)
            return chk((bl << (ex - el)) + (br << (ex - er)), ex)
        if isinstance(n, Pow):
            if n.n < 1:
                raise _NotPoly("non-positive power")
            b, ex = go(n.base)
            return chk(b ** n.n, ex * n.n)
        if isinstance(n, (Call, Cmp, Select)):
            raise _NotPoly(f"{type(n).__name__} is not a polynomial")
        raise _NotPoly(f"unsupported node {type(n).__name__}")

    b, ex = go(e)
    return b, ex, peak


def _const_term(n: Expr) -> Optional[Tuple[float, Expr]]:
    """``c * X`` or ``X * c`` with a constant c -> (c, X)."""
    if isinstance(n, BinOp) and n.op == "*":
        if isinstance(n.left, Const):
            return float(n.left.value), n.right
        if isinstance(n.right, Const):
            return float(n.right.value), n.left
    return None


def _split_rational(e: Expr):
    """The root pattern with one non-dyadic constant, ``A ± c*X`` or
    ``c*X`` -> (A or None, c, sign of c*X, X)."""
    ct = _const_term(e)
    if ct is not None:
        return None, ct[0], 1, ct[1]
    if isinstance(e, BinOp) and e.op in ("+", "-"):
        ct = _const_term(e.right)
        if ct is not None:
            return e.left, ct[0], 1 if e.op == "+" else -1, ct[1]
    return None


def _plan_intpoly(st: Stage, t_out: Optional[FixedPointType],
                  in_types: Dict[str, Optional[FixedPointType]],
                  float_stored: set, phase: Optional[PhaseSnap]):
    """Integer evaluation of a polynomial stage, or the reason for none."""
    if t_out is None or t_out.width > 52:
        return "output has no integer grid"
    if phase is not None and not phase.int_ok:
        return "phase-split residues re-snap per lattice residue"
    beta = t_out.beta
    try:
        b, ex, peak = _poly_walk(st.expr, in_types, float_stored)
    except _NotPoly as whole:
        parts = _split_rational(st.expr)
        if parts is None:
            return str(whole)
        return _plan_rational_poly(st, t_out, in_types, float_stored, parts)
    t = ex - beta
    fin = (b << -t) if t <= 0 else b + (1 << (t - 1))
    peak = max(peak, fin)
    plan = dict(poly_exact=st.expr, carrier=_poly_carrier(peak),
                acc_bound=b)
    plan["proof"] = (f"intpoly {plan['carrier']}: exact polynomial, "
                     f"nodes below 2^{peak.bit_length()}, "
                     f"round-half-even shift {t}")
    return plan


def _poly_carrier(peak: int) -> str:
    return "int32" if peak < INT32_BUDGET else "int64"


def _plan_rational_poly(st, t_out, in_types, float_stored, parts):
    from repro.core.graph import expr_refs
    a_expr, c, s, x_expr = parts
    beta = t_out.beta
    try:
        xb, xe, peak = _poly_walk(x_expr, in_types, float_stored)
        ab = 0
        if a_expr is not None:
            ab, ae, apeak = _poly_walk(a_expr, in_types, float_stored)
            if ae > beta:
                return "the exact part is finer than the output grid"
            ab <<= beta - ae
            peak = max(peak, apeak, ab)
    except _NotPoly as exc:
        return str(exc)
    refs = set(expr_refs(x_expr))
    if len(refs) != 1:
        return "the non-dyadic term is not a function of one input value"
    ref = refs.pop()
    t_in = in_types[ref.stage]
    if t_in.int_max - t_in.int_min + 1 > ENUM_CAP:
        return (f"input {ref.stage!r} has more than "
                f"2^{ENUM_CAP.bit_length() - 1} grid values")
    rat = _prove_rational(c, s, beta, xe, t_in.int_min, t_in.int_max,
                          None if a_expr is None else ab,
                          (x_expr, t_in.beta))
    if isinstance(rat, str):
        return rat
    (a, b), ties = rat
    y = 2 * abs(a) * xb + b
    peak = max(peak, y, ab + y // (2 * b) + 1)
    carrier = _poly_carrier(peak)
    what = "A ± fl(c*X)" if a_expr is not None else "fl(c*X)"
    return dict(poly_exact=a_expr, rat_term=x_expr,
                rat=(a, b), carrier=carrier, acc_bound=xb, proof=(
                    f"intpoly {carrier}, rational finish {a}/{b}: "
                    f"rint({what}) with c={c!r} reproduced for every "
                    f"{ref.stage} in [{t_in.int_min}, {t_in.int_max}] "
                    f"({ties} ties to even), nodes below "
                    f"2^{peak.bit_length()}"))


def _phase_snap(t_union: FixedPointType, entry) -> PhaseSnap:
    (my, mx), tmap = entry
    return PhaseSnap(lattice=(my, mx), types=dict(tmap),
                     int_ok=all(t.beta == t_union.beta
                                for t in tmap.values()))


def lower(pipeline: Pipeline, types, params: Optional[Dict[str, float]] = None,
          column: Optional[str] = None,
          datapath: str = "exact") -> LoweredPipeline:
    """Lower `(Pipeline, BitwidthPlan-or-TypeMap)` into a typed program.

    Mirrors `dsl.exec.run_fixed`'s duck-typed plan handling: a plan
    supplies its `column` types plus per-phase sub-types; a plain dict is
    a per-stage union type map.

    `datapath="narrow"` turns on int32/f32-first re-election (see the
    module docstring); every election — and every justified 64-bit
    retention — is recorded on the stages and, when `types` is a
    `BitwidthPlan`, appended to the plan column's provenance notes.
    """
    if datapath not in ("exact", "narrow"):
        raise LoweringError(f"unknown datapath mode {datapath!r}; "
                            "expected 'exact' or 'narrow'")
    narrow = datapath == "narrow"
    phase_types = {}
    col = column
    plan_obj = None
    if hasattr(types, "phase_types"):            # BitwidthPlan (duck-typed)
        plan_obj = types
        phase_types = plan_obj.phase_types(column) or {}
        col = column or getattr(plan_obj, "default_column", None)
        types = plan_obj.types(column)
    with obs.span("lowering.lower", pipeline=pipeline.name, column=col,
                  n_stages=len(pipeline.stages), datapath=datapath) as sp:
        tmap: Dict[str, Optional[FixedPointType]] = {
            n: types.get(n) for n in pipeline.stages}
        stages: Dict[str, LoweredStage] = {}
        order = pipeline.topo_order()
        # stages whose values backends must keep as floats (no single
        # scaled-int grid): untyped, wider than a double's mantissa, or
        # residue-mixed-beta.  Their consumers cannot take the integer path.
        float_stored: set = set()
        for name in order:
            st = pipeline.stages[name]
            t_out = tmap.get(name)
            halo = st.halo_yx()
            phase = None
            if name in phase_types and t_out is not None:
                phase = _phase_snap(t_out, phase_types[name])
            sf = (t_out is None or t_out.width > 52
                  or (phase is not None and not phase.int_ok))
            if sf:
                float_stored.add(name)
            if st.is_input:
                stages[name] = LoweredStage(name=name, kind="input", stage=st,
                                            t=t_out, halo=(0, 0),
                                            store_float=sf)
                continue
            lin = match_linear(st.expr) if t_out is not None else None
            plan_int = None
            if lin is not None and not sf \
                    and not any(i in float_stored for i in st.inputs):
                plan_int = _plan_intlinear(
                    st, lin[0], lin[1], t_out,
                    {i: tmap.get(i) for i in st.inputs},
                    narrow=narrow,
                    in_phases={i: stages[i].phase for i in st.inputs})
            if plan_int is not None:
                stages[name] = LoweredStage(name=name, kind="intlinear",
                                            stage=st, t=t_out, halo=halo,
                                            phase=phase, **plan_int)
            else:
                # narrow mode tries the f32 demotion first; otherwise an
                # integer polynomial, else the f64 replay
                f32_no = (_expr_fits_f32(st, t_out, tmap, float_stored, phase)
                          if narrow else "")
                if f32_no is None:
                    stages[name] = LoweredStage(
                        name=name, kind="expr", stage=st, t=t_out, halo=halo,
                        phase=phase, store_float=sf, expr_dtype="f32",
                        election="f32")
                    continue
                poly = ("float-stored output" if sf else
                        _plan_intpoly(st, t_out, tmap, float_stored, phase))
                if isinstance(poly, dict):
                    election = ""
                    if narrow:
                        election = ("int32" if poly["carrier"] == "int32"
                                    else f"int64 kept: {f32_no}; "
                                    f"{poly['proof']}")
                    stages[name] = LoweredStage(name=name, kind="intpoly",
                                                stage=st, t=t_out, halo=halo,
                                                phase=phase, election=election,
                                                **poly)
                    continue
                stages[name] = LoweredStage(
                    name=name, kind="expr", stage=st, t=t_out, halo=halo,
                    phase=phase, store_float=sf, proof=f"f64 kept: {poly}",
                    election=f"f64 kept: {f32_no}" if narrow else "")
        kinds = [s.kind for s in stages.values()]
        sp.set(intlinear=kinds.count("intlinear"),
               intpoly=kinds.count("intpoly"), expr=kinds.count("expr"),
               **_census(stages.values()))
        DATAPATH_STATS.add("lowerings")
        for ls in stages.values():
            if not ls.stage.is_input:
                DATAPATH_STATS.add(f"{ls.kind}.{ls.datapath_carrier}."
                                   f"{ls.finish}")
        if narrow:
            sp.set(narrowed=sum(1 for s in stages.values()
                                if s.election in ("int32", "f32")
                                or s.carrier == "int32pair"))
        if plan_obj is not None and hasattr(plan_obj, "record_election"):
            plan_obj.record_election(col, _election_notes(
                pipeline.name, stages, datapath))
    return LoweredPipeline(pipeline=pipeline, stages=stages, order=order,
                           params=dict(params or {}), types=tmap, column=col,
                           datapath=datapath)


def _election_notes(pipe_name: str, stages: Dict[str, LoweredStage],
                    datapath: str = "narrow") -> List[str]:
    """Provenance lines of a lowering: the proof (or f64 retention
    reason) of every exact integer finish and intpoly election, and in
    narrow mode one census line plus one justification line per retained
    64-bit datapath."""
    labels = []
    details = []
    for name, ls in stages.items():
        if ls.stage.is_input:
            continue
        labels.append(f"{name}={ls.datapath_carrier}")
        if datapath == "narrow" \
                and ls.election.startswith(("int64 kept", "f64 kept")):
            details.append(f"datapath[narrow] {pipe_name}.{name}: "
                           f"{ls.election}")
        elif ls.proof:
            details.append(f"datapath[{datapath}] {pipe_name}.{name}: "
                           f"{ls.proof}")
    census = ([f"datapath[narrow] {pipe_name}: " + ", ".join(labels)]
              if datapath == "narrow" else [])
    return census + details
