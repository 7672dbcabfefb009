"""Execution backends over the lowered IR.

A backend compiles a `LoweredPipeline` into an executor
``fn(image_or_dict) -> {stage: float64 ndarray}``.  Registered backends:

  * ``interp``  — the per-stage `dsl.exec.run_fixed` oracle (numpy f64),
                  kept bit-identical by definition;
  * ``jnp``     — one fused jit program: integer multiply-accumulate
                  datapaths for provably-exact linear stages, f64 replay
                  for the rest, all under an x64 scope.  Bit-identical to
                  the oracle (see `repro.lowering.ir` for the argument);
  * ``pallas``  — the fused line-buffer kernel (`pallas_backend`).

Shared here are the datapath finishing helpers both fused backends use:
round-half-even integer shifts (== `rint` on the exact dyadic value) and
per-residue saturation grids for phase-split stages.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.fixedpoint import FixedPointType
from repro.lowering.ir import (LoweredPipeline, LoweredStage, LoweringError,
                               PhaseSnap, lower)

Executor = Callable[..., Dict[str, np.ndarray]]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# shared datapath pieces (jnp-traceable; work under jit and inside pallas)
# ---------------------------------------------------------------------------

def rhe_shift(p, t: int):
    """Round-half-even of `p / 2^t` on integer arrays (t may be <= 0).

    Bit-identical to `rint` of the exact dyadic rational — the oracle's
    `_snap` on an exact float value — including the tie-to-even cases the
    single-stage kernel's legacy round-half-up misses.
    """
    import jax.numpy as jnp
    if t <= 0:
        return p << (-t)
    base = p >> t                      # arithmetic shift == floor division
    rem = p - (base << t)
    half = 1 << (t - 1)
    inc = (rem > half) | ((rem == half) & ((base & 1) == 1))
    return base + inc.astype(p.dtype)


def residue_bounds(phase: PhaseSnap, t: FixedPointType, rows, cols):
    """(qmin, qmax) saturation grids for a phase-split stage tile.

    `rows` is the ``(n, 1)`` absolute-row index column of the tile (it
    may be traced), `cols` the ``(1, m)`` absolute-column index row.
    Residues absent from the phase map keep the union-column bounds."""
    import jax.numpy as jnp
    my, mx = phase.lattice
    rr = rows % my
    cc = cols % mx
    shape = (rows.shape[0], cols.shape[1])
    qmin = jnp.full(shape, t.int_min, dtype=jnp.int64)
    qmax = jnp.full(shape, t.int_max, dtype=jnp.int64)
    for (ry, rx), t_ph in sorted(phase.types.items()):
        mask = (rr == ry % my) & (cc == rx % mx)
        qmin = jnp.where(mask, t_ph.int_min, qmin)
        qmax = jnp.where(mask, t_ph.int_max, qmax)
    return qmin, qmax


def carrier_dtype(name: str):
    """MAC register dtype: an "int32pair" accumulates in int32 lanes."""
    import jax.numpy as jnp
    return jnp.int32 if name in ("int32", "int32pair") else jnp.int64


def store_dtype(ls: LoweredStage):
    """Tile dtype a fused backend materializes for this stage.

    The smallest *legalized* container (`core.policy.legalize`) that
    holds the stage's (alpha, beta) width: int8/uint8/int16/uint16/
    int32/uint32 — this is where the paper's bit-width savings become
    real HBM/VMEM traffic instead of a cost-model line.  Exact by
    construction: every store site (`finish_intlinear`, `snap_expr`,
    `quantize_input`) clips to ``[t.int_min, t.int_max]`` *before* the
    final ``astype``, and the legalized container holds that full range,
    so narrowing the astype never changes a stored value; loads widen
    back into the MAC carrier (``.astype(carrier)``, zero/sign-extending)
    or dequantize to f64, both lossless.  Widths 33–52 keep an int64
    container (legalize's float32 fallback would round); float-stored
    stages stay f64.
    """
    import jax.numpy as jnp
    if ls.store_float:
        return jnp.float64
    from repro.core.policy import legalize
    lt = legalize(ls.t)
    if lt.fp is not None:              # width <= 32: smallest container
        return lt.dtype
    return jnp.int64                   # 33..52 exact-int bits


def wide_store_dtype(ls: LoweredStage):
    """The pre-legalization container rule (int32/int64/f64) — kept as
    the baseline `measured bytes/pixel` is compared against."""
    import jax.numpy as jnp
    if ls.store_float:
        return jnp.float64
    return jnp.int32 if ls.t.width <= 31 else jnp.int64


def fused_store_dtype(ls: LoweredStage):
    """In-program container for the fused jnp executor's intermediates.

    Inside ONE jit program nothing between stages reaches HBM — XLA
    fuses the elementwise chains — so there the stored container is
    only visible as vector converts, and sub-32-bit lanes pessimize
    CPU XLA by ~10% (hcd) while moving zero real bytes.  Trace-time
    specialization (the AnyHLS idiom, no runtime branching): on CPU
    hosts in-program intermediates are floored at 32 bits; on TPU/GPU
    the true legalized container is kept — there narrow tiles are the
    real VMEM/HBM win.  Value-neutral either way: the clip into
    ``[t.int_min, t.int_max]`` precedes the cast and the wider
    container holds the range.  Every *materialization* point — input
    tiles, pallas band copies, island boundary buffers, sharded
    replicated buffers, serving batches — always uses the true
    `store_dtype`.
    """
    import jax
    import jax.numpy as jnp
    dt = np.dtype(store_dtype(ls))
    if jax.default_backend() in ("tpu", "gpu") or dt.itemsize >= 4:
        return store_dtype(ls)
    return jnp.uint32 if dt.kind == "u" else jnp.int32


def accumulate_intlinear(ls: LoweredStage, tap_of, zeros):
    """Shared MAC loop: `tap_of(tp)` yields the carrier-typed tap tile,
    `zeros()` a fresh carrier-typed accumulator.

    For an "int32pair" carrier the taps before `acc_split` and the rest
    accumulate in separate int32 registers, combined by ONE widening add
    before the finishing rule — bit-equal to a flat sum because integer
    adds are associative/commutative and the combined value was proved
    below 2^53 at lowering time.
    """
    import jax.numpy as jnp
    pair = ls.carrier == "int32pair" and 0 < ls.acc_split < len(ls.int_taps)
    accs = [zeros(), zeros()] if pair else [zeros()]
    for k, tp in enumerate(ls.int_taps):
        g = 1 if pair and k >= ls.acc_split else 0
        accs[g] = accs[g] + tp.W * tap_of(tp)
    if ls.carrier != "int32pair":
        return accs[0]
    acc = accs[0].astype(jnp.int64)
    if pair:
        acc = acc + accs[1].astype(jnp.int64)
    return acc


def snap_float(raw, t: FixedPointType, xp):
    """The oracle's `_snap` (numpy branch) in any xp: rint, clip, rescale."""
    step = 2.0 ** t.beta
    return xp.clip(xp.rint(raw * step), t.int_min, t.int_max) / step


def quantize_input(x, t: Optional[FixedPointType], dtype, xp):
    """Image -> scaled-int tile on `t`'s grid (oracle input snapping)."""
    if t is None:
        return x
    q = xp.clip(xp.rint(x * (2.0 ** t.beta)), t.int_min, t.int_max)
    return q.astype(dtype)


def ingest_input(x, ls: LoweredStage, xp):
    """Image (or pre-quantized container array) -> stored input tile.

    The zero-copy ingestion convention: an array arriving already in the
    stage's legalized container dtype is treated as *pre-quantized* —
    its values are the scaled integers ``rint(v * 2^beta)`` — and used
    as the stored tile directly, skipping the f64 round-trip (for a
    uint8 beta-0 full-range input the raw pixel buffer IS that tile).
    Anything else takes the oracle path: cast to f64, snap to `t`'s
    grid.  Callers must only hand container-dtype arrays that really
    are on-grid (``repro.serve`` quantizes once at submit).
    """
    dt = store_dtype(ls)
    if ls.t is not None and x.dtype == dt:
        return x
    x = x.astype(xp.float64)
    if ls.t is None:
        return x
    return quantize_input(x, ls.t, dt, xp)


def ingest_host(lp: LoweredPipeline, names: Sequence[str], img_of: Dict):
    """Pipeline inputs -> stored input tiles, quantized on the host in
    numpy (the oracle's arithmetic), so the device never sees f64.
    Returns ``({name: container ndarray}, shared shape)``."""
    buffers: Dict[str, np.ndarray] = {}
    shape = None
    for n in names:
        x = np.asarray(img_of[n])
        if x.ndim not in (2, 3):
            raise LoweringError(
                f"images must be (H, W) or (B, H, W); got {tuple(x.shape)}")
        if shape is None:
            shape = tuple(x.shape)
        elif tuple(x.shape) != shape:
            raise LoweringError("all pipeline inputs must share one shape; "
                                f"got {shape} vs {x.shape}")
        buffers[n] = np.asarray(ingest_input(x, lp.stages[n], np))
    return buffers, shape


def finish_intlinear(ls: LoweredStage, acc, rows, cols, container=None):
    """Accumulator -> saturated scaled-int tile (union + per-residue).

    `rows`/`cols` are the tile's absolute row/column indices (see
    `residue_bounds`).  `container` overrides the stored dtype (must
    hold the clipped range; the fused jnp program passes
    `fused_store_dtype`)."""
    import jax.numpy as jnp
    if ls.dyadic:
        q = rhe_shift(acc * ls.sm if ls.sm != 1 else acc, ls.t_shift)
    else:
        q = jnp.rint(acc.astype(jnp.float64) * ls.cscale)
    if ls.phase is not None:
        qmin, qmax = residue_bounds(ls.phase, ls.t, rows, cols)
        q = jnp.clip(q, qmin, qmax)
    else:
        q = jnp.clip(q, ls.t.int_min, ls.t.int_max)
    return q.astype(container if container is not None else store_dtype(ls))


def snap_expr(ls: LoweredStage, raw, rows, cols, container=None):
    """Raw f64 stage tile -> stored tile (int grid or oracle-float).

    `rows`/`cols` as in `finish_intlinear`.  `container` overrides the
    stored dtype on the integer path (must hold the clipped range; the
    fused jnp program passes `fused_store_dtype`); the float paths
    ignore it."""
    import jax.numpy as jnp
    t = ls.t
    if t is None:
        return raw
    if ls.phase is not None and not ls.phase.int_ok:
        # residues carry different betas: store the float composite the
        # oracle stores (union snap, then per-residue re-snap of raw)
        out = snap_float(raw, t, jnp)
        my, mx = ls.phase.lattice
        for (ry, rx), t_ph in sorted(ls.phase.types.items()):
            mask = (rows % my == ry % my) & (cols % mx == rx % mx)
            out = jnp.where(mask, snap_float(raw, t_ph, jnp), out)
        return out
    if ls.store_float:                  # wide type: keep the oracle floats
        return snap_float(raw, t, jnp)
    q = jnp.rint(raw * (2.0 ** t.beta))
    if ls.phase is not None:
        qmin, qmax = residue_bounds(ls.phase, t, rows, cols)
        q = jnp.clip(q, qmin, qmax)
    else:
        q = jnp.clip(q, t.int_min, t.int_max)
    return q.astype(container if container is not None else store_dtype(ls))


def dequant(ls: LoweredStage, tile):
    """Stored tile -> the f64 stage value the oracle's env carries."""
    import jax.numpy as jnp
    if ls.store_float:
        return tile
    return tile.astype(jnp.float64) * (2.0 ** -ls.t.beta)


def dequant_host(ls: LoweredStage, tile) -> np.ndarray:
    """`dequant` in numpy on a fetched container array — the oracle's own
    arithmetic, so device programs need no f64 at their outputs."""
    a = np.asarray(tile)
    if ls.store_float:
        return a
    return a.astype(np.float64) * (2.0 ** -ls.t.beta)


def dequant_f32(ls: LoweredStage, tile):
    """Stored tile -> the *exact* f32 stage value (narrow-mode f32 path).

    Exact because the demotion proof (`ir._expr_fits_f32`) bounds the
    scaled magnitude below 2^24 and a power-of-two rescale is lossless —
    so this f32 value equals the f64 value `dequant` produces, bit for
    bit after the final upconversion."""
    import jax.numpy as jnp
    return tile.astype(jnp.float32) * np.float32(2.0 ** -ls.t.beta)


def needed_stages(lp: LoweredPipeline, outputs: Sequence[str]) -> List[str]:
    """Ancestors of `outputs` in topo order (prune dead stages)."""
    need = set()
    stack = list(outputs)
    while stack:
        n = stack.pop()
        if n in need:
            continue
        need.add(n)
        stack.extend(lp.pipeline.stages[n].inputs)
    return [n for n in lp.order if n in need]


def normalize_images(lp: LoweredPipeline, image):
    """run_fixed's input convention: dict / tuple / single array."""
    input_names = lp.pipeline.input_stages()
    if isinstance(image, dict):
        return [image[n] for n in input_names], input_names
    if isinstance(image, (tuple, list)):
        return list(image), input_names
    return [image], input_names


# ---------------------------------------------------------------------------
# fused jnp backend
# ---------------------------------------------------------------------------

def compile_jnp(lp: LoweredPipeline,
                outputs: Optional[Sequence[str]] = None) -> Executor:
    """One jitted x64 program with the oracle's padded-grid geometry.

    Integer linear stages run as int32/int64 multiply-accumulates; every
    other stage replays the oracle's f64 expression tree
    (`dsl.exec.eval_expr`) on dequantized operands.  Output dict values
    are the same float64 arrays `run_fixed(backend="numpy")` produces.

    Images with a leading batch dimension — ``(B, H, W)`` instead of
    ``(H, W)`` — run as ONE `vmap`-batched program over the same fused
    forward.  Every op in the datapath is per-pixel (MACs, shifts,
    clips, slices; no cross-batch reduction anywhere), so the batched
    program is bit-for-bit the per-image loop (pinned in
    tests/test_serving.py).
    """
    import jax
    import jax.numpy as jnp
    from repro.dsl.exec import _pad_inputs, _stage_out_shape, eval_expr

    outs = list(outputs or lp.pipeline.outputs)
    order = needed_stages(lp, outs)
    params = dict(lp.params)

    def forward(*images):
        tiles: Dict[str, object] = {}      # stored tiles (int grid or f64)
        vals: Dict[str, object] = {}       # f64 env values (lazy-ish)
        shapes: Dict[str, tuple] = {}
        input_names = lp.pipeline.input_stages()
        img_of = dict(zip(input_names, images))
        for name in order:
            ls = lp.stages[name]
            st = ls.stage
            if st.is_input:
                x = img_of[name]
                # trace-time branch: a container-dtype input arrives
                # pre-quantized and is the stored tile zero-copy
                tiles[name] = ingest_input(x, ls, jnp)
                vals[name] = dequant(ls, tiles[name])
                shapes[name] = x.shape
                continue
            in_shape = shapes[st.inputs[0]]
            out_shape = _stage_out_shape(st, in_shape)
            H, W = out_shape
            hy, hx = ls.halo
            if ls.kind == "intlinear":
                cdt = carrier_dtype(ls.carrier)
                padded = _pad_inputs(
                    {i: tiles[i].astype(cdt) for i in st.inputs}, st, jnp)
                sy, sx = st.stride
                # stride folded into the tap slices: decimated pixels are
                # never computed (the interpreter computes-then-drops)
                Hs, Ws = _ceil_div(H, sy), _ceil_div(W, sx)

                def tap_of(tp, padded=padded, hy=hy, hx=hx, H=H, W=W,
                           sy=sy, sx=sx):
                    a = padded[tp.stage]
                    return a[hy + tp.dy: hy + tp.dy + H: sy,
                             hx + tp.dx: hx + tp.dx + W: sx]

                acc = accumulate_intlinear(
                    ls, tap_of, lambda: jnp.zeros((Hs, Ws), cdt))
                q = finish_intlinear(ls, acc,
                                     jnp.arange(acc.shape[0])[:, None],
                                     jnp.arange(acc.shape[1])[None, :],
                                     container=fused_store_dtype(ls))
                tiles[name] = q
            else:
                if ls.expr_dtype == "f32":
                    padded = _pad_inputs(
                        {i: dequant_f32(lp.stages[i], tiles[i])
                         for i in st.inputs}, st, jnp)
                else:
                    padded = _pad_inputs({i: vals[i] for i in st.inputs},
                                         st, jnp)

                def ref(stage, dy, dx, padded=padded, H=H, W=W,
                        hy=hy, hx=hx):
                    a = padded[stage]
                    return a[hy + dy: hy + dy + H, hx + dx: hx + dx + W]

                raw = eval_expr(st.expr, ref, params, jnp, jnp.where)
                sy, sx = st.stride
                if sy > 1 or sx > 1:
                    raw = raw[::sy, ::sx]
                tiles[name] = snap_expr(ls, raw,
                                        jnp.arange(raw.shape[0])[:, None],
                                        jnp.arange(raw.shape[1])[None, :],
                                        container=fused_store_dtype(ls))
            vals[name] = dequant(ls, tiles[name])
            shapes[name] = tuple(vals[name].shape)
        return {k: vals[k] for k in outs}

    jitted = jax.jit(forward)
    vjitted = jax.jit(jax.vmap(forward))

    def run(image, params_override=None):
        if params_override is not None and dict(params_override) != params:
            raise ValueError("params are baked at compile time; re-lower "
                             "with the new params")
        with obs.span("exec.lowered", backend="jnp",
                      pipeline=lp.pipeline.name, outputs=len(outs)) as sp:
            imgs, in_names = normalize_images(lp, image)
            with jax.enable_x64(True):
                # container-dtype frames ship narrow (zero-copy ingest);
                # everything else takes the f64 quantize path in-trace
                def to_dev(im, n):
                    a = np.asarray(im)
                    ls = lp.stages[n]
                    if ls.t is not None \
                            and a.dtype == np.dtype(store_dtype(ls)):
                        return jnp.asarray(a)
                    return jnp.asarray(a, dtype=jnp.float64)

                arrs = tuple(to_dev(im, n)
                             for im, n in zip(imgs, in_names))
                ndims = {a.ndim for a in arrs}
                if ndims == {3}:          # leading batch dim: vmap program
                    if len({a.shape[0] for a in arrs}) != 1:
                        raise LoweringError(
                            "batched inputs must share one batch size; got "
                            f"{[a.shape for a in arrs]}")
                    sp.set(batch=int(arrs[0].shape[0]))
                    out = vjitted(*arrs)
                elif ndims == {2}:
                    out = jitted(*arrs)
                else:
                    raise LoweringError(
                        f"images must all be (H, W) or all (B, H, W); got "
                        f"{[a.shape for a in arrs]}")
                res = {k: np.asarray(v) for k, v in out.items()}
        # read-only post-processing: never feeds back into the computation
        obs.runtime.record_env(res, lp, backend="jnp")
        return res

    run.lowered = lp          # introspection hooks for tests/benchmarks
    run.forward = forward     # the per-image program `run` jits and vmaps
    return run


# ---------------------------------------------------------------------------
# interpreter (oracle) backend + registry
# ---------------------------------------------------------------------------

def compile_interp(lp: LoweredPipeline,
                   outputs: Optional[Sequence[str]] = None) -> Executor:
    """The per-stage numpy f64 oracle, as a backend (the reference).

    Batched ``(B, H, W)`` input runs as a per-image python loop — the
    DEFINITION the batched fused backends are pinned against."""
    outs = list(outputs or lp.pipeline.outputs)
    phase_types = {n: (ls.phase.lattice, dict(ls.phase.types))
                   for n, ls in lp.stages.items() if ls.phase is not None}

    def one(image, params_override):
        from repro.dsl.exec import _run_concrete
        # per-stage spans + runtime range telemetry live inside
        # `_run_concrete` (it sees every intermediate stage value)
        env = _run_concrete(lp.pipeline, image,
                            dict(params_override or lp.params), lp.types,
                            xp=np, phase_types=phase_types or None)
        return {k: np.asarray(env[k]) for k in outs}

    def run(image, params_override=None):
        imgs, names = normalize_images(lp, image)
        # the oracle is definitionally f64: a pre-quantized container
        # frame (zero-copy convention, `ingest_input`) dequantizes to
        # the on-grid value the oracle's own input snap reproduces
        def to_f64(im, n):
            a = np.asarray(im)
            ls = lp.stages[n]
            if ls.t is not None and a.dtype == np.dtype(store_dtype(ls)):
                return a.astype(np.float64) * (2.0 ** -ls.t.beta)
            return a.astype(np.float64)

        arrs = [to_f64(im, n) for im, n in zip(imgs, names)]
        with obs.span("exec.interp", backend="interp",
                      pipeline=lp.pipeline.name, outputs=len(outs)):
            if all(a.ndim == 3 for a in arrs):
                per = [one(dict(zip(names, [a[b] for a in arrs])),
                           params_override)
                       for b in range(arrs[0].shape[0])]
                return {k: np.stack([p[k] for p in per]) for k in outs}
            return one(dict(zip(names, arrs)), params_override)

    run.lowered = lp
    return run


BACKENDS = {
    "interp": compile_interp,
    "jnp": compile_jnp,
}


def register_backend(name: str, factory) -> None:
    BACKENDS[name] = factory


def compile_pipeline(pipeline, types, params=None, backend: str = "jnp",
                     outputs=None, column=None, datapath: str = "exact",
                     **kw) -> Executor:
    """Lower + compile in one call (the `repro.lowering` front door)."""
    lp = lower(pipeline, types, params=params, column=column,
               datapath=datapath)
    return compile_backend(lp, backend, outputs=outputs, **kw)


def compile_backend(lp: LoweredPipeline, backend: str = "jnp",
                    outputs=None, **kw) -> Executor:
    if backend == "pallas":
        from repro.lowering import pallas_backend  # registers itself
    elif backend == "sharded":
        from repro.lowering import sharded         # registers itself
    try:
        factory = BACKENDS[backend]
    except KeyError:
        raise LoweringError(
            f"unknown lowering backend {backend!r}; "
            f"registered: {sorted(BACKENDS)}") from None
    kinds = lp.kinds()
    with obs.span("lowering.compile", backend=backend,
                  pipeline=lp.pipeline.name, n_stages=len(lp.stages),
                  intlinear=sum(1 for k in kinds.values()
                                if k == "intlinear")):
        return factory(lp, outputs=outputs, **kw)
