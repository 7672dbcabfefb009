"""Fused line-buffer Pallas backend over the lowered IR.

Compiles a `LoweredPipeline` + image shape into a chain of fused
`pallas_call`s — one per *rate island* (`repro.lowering.islands`): the
DAG is partitioned into maximal band-schedulable subgraphs, each island
walks a band of every member stage's rows down the image with
intermediates resident in VMEM, and islands hand off through
materialized HBM boundary buffers holding the boundary stages' *stored*
tiles in their smallest legalized container (`backends.store_dtype`:
int8/uint8/int16/uint16/int32 scaled ints, int64 for 33–52 exact bits,
f64 only for float-stored stages).  The historical whole-DAG case is the
single-island fast path; DAGs the old backend rejected with
`LoweringError` (mixed rates, rate-inexact heights, halos deeper than
any aligned tile) now partition instead, so there is NO jnp whole-DAG
fallback left (pass `islands=False` to opt back into the raising
monolithic behavior).

Per-stage datapaths are synthesized from each `LoweredStage`:

  * `intlinear` — integer multiply-accumulate over static tap slices
    (int32, an int32 *pair* with one widening combine, or int64 —
    narrow-mode election, see `repro.lowering.ir`), finished by a
    round-half-even shift (dyadic scale) or one f64 multiply + rint,
    saturated per lattice residue where the plan carries phase types;
  * `expr`      — the oracle's expression tree replayed on dequantized
    taps (`dsl.exec.eval_expr`) in f64, or in f32 under a narrow-mode
    exactness proof, then snapped.

Everything is bit-identical to `run_fixed(backend="numpy")` (see
`repro.lowering.ir` for the exactness arguments; the band geometry is
value-equal to the oracle's padded full-array geometry by the clamp
equivalence spelled out in `kernels.stencil.kernel`, and island
boundaries reproduce the oracle's stage values exactly because the
stored representation IS the oracle's value grid).

`interpret=None` (the default) resolves by platform
(`resolve_interpret`): the kernel runs natively on a TPU — where a plan
that needs 64-bit datapaths raises `LoweringError` naming its stages —
and in interpret mode, with a one-time `RuntimeWarning`, on hosts with
no TPU (the CPU tests).
"""
from __future__ import annotations

from fractions import Fraction
from typing import Dict, Optional, Sequence

from repro import obs
from repro.lowering import backends as B
from repro.lowering.ir import (LoweredPipeline, LoweredStage, LoweringError,
                               _election_notes)
from repro.lowering.islands import Island, partition_islands
from repro.lowering.schedule import Schedule, build_schedule, column_blocks

# ---------------------------------------------------------------------------
# capability detection
# ---------------------------------------------------------------------------

# capability warnings dedupe through the process-wide registry so every
# entry point (pallas, sharded, serve) that resolves capabilities warns
# once per process, not once per compiled executor (tests clear the set)
from repro.obs.warnonce import _WARNED as _warned  # noqa: E402


def _warn_once(msg: str) -> None:
    obs.warn_once(msg, stacklevel=4)


def stages_needing_64bit(lp: LoweredPipeline) -> list:
    """Stages whose in-kernel datapath touches int64/f64, in topo order:
    float-stored or wider-than-31-bit tiles, phase-split residue grids
    (built in int64), int64 and int32-pair carriers (the pair combines in
    int64), f64 finishes and f64 expression replays."""
    out = []
    for n, ls in lp.stages.items():
        if ls.store_float \
                or (ls.t is not None and ls.t.width > 31) \
                or ls.phase is not None \
                or (ls.kind == "intlinear"
                    and (ls.carrier in ("int64", "int32pair")
                         or not ls.dyadic)) \
                or (ls.kind == "expr" and not ls.stage.is_input
                    and ls.expr_dtype == "f64"):
            out.append(n)
    return out


def needs_64bit(lp: LoweredPipeline) -> bool:
    """True when any stage's in-kernel datapath touches int64/f64."""
    return bool(stages_needing_64bit(lp))


def resolve_interpret(lp: Optional[LoweredPipeline] = None) -> bool:
    """`interpret` for the fused kernel: native on a TPU, else interpret.

    The TPU kernel compiler takes 32-bit datapaths only, so a plan that
    needs 64-bit raises `LoweringError` there, naming its stages — it is
    never sent to interpret mode on the chip.  Hosts without a TPU (the
    CPU tests) run the kernel in interpret mode, with a one-time
    `RuntimeWarning`."""
    import jax
    platform = jax.default_backend()
    if platform == "tpu":
        wide = stages_needing_64bit(lp) if lp is not None else []
        if wide:
            notes = "\n".join(_election_notes(lp.pipeline.name, lp.stages))
            raise LoweringError(
                f"pallas: {lp.pipeline.name!r} needs 64-bit datapaths in "
                f"stages {wide}; the TPU kernel takes 32-bit only (narrow "
                f"the plan with lower(..., datapath='narrow')):\n{notes}")
        return False
    _warn_once(
        f"pallas: no TPU (jax default_backend={platform!r}); the fused "
        f"kernel runs in interpret mode")
    return True


# ---------------------------------------------------------------------------
# stage descriptors
# ---------------------------------------------------------------------------

def _input_descriptor(name: str, ls: LoweredStage, ss, slot: int):
    return dict(kind="input", name=name, step=ss.step, lo=ss.lo, L=ss.L,
                H=ss.H, W=ss.W, dtype=B.store_dtype(ls), in_slot=slot)


def _compute_descriptor(lp: LoweredPipeline, name: str, ss):
    import jax.numpy as jnp
    from repro.dsl.exec import eval_expr

    ls = lp.stages[name]
    st = ls.stage
    params = lp.params

    if ls.kind == "intlinear":
        cdt = B.carrier_dtype(ls.carrier)

        def fn(tap, rows, cols, ls=ls, cdt=cdt):
            acc = B.accumulate_intlinear(
                ls,
                lambda tp: tap(tp.stage, tp.dy, tp.dx).astype(cdt),
                lambda: jnp.zeros((rows.shape[0], cols.shape[1]), cdt))
            return B.finish_intlinear(ls, acc, rows, cols)
    else:
        deq = B.dequant_f32 if ls.expr_dtype == "f32" else B.dequant

        def fn(tap, rows, cols, ls=ls, deq=deq):
            def ref(stage, dy, dx):
                return deq(lp.stages[stage], tap(stage, dy, dx))

            raw = eval_expr(st.expr, ref, params, jnp, jnp.where)
            return B.snap_expr(ls, raw, rows, cols)

    return dict(kind="compute", name=name, step=ss.step, lo=ss.lo, L=ss.L,
                H=ss.H, W=ss.W, dtype=B.store_dtype(ls),
                stride=st.stride, upsample=st.upsample,
                inputs=tuple(st.inputs), fn=fn)


def island_program(lp: LoweredPipeline, isl: Island) -> list:
    """Stage descriptors (kernels.stencil.kernel contract) for one island.

    Shared with the `shard_map` band-sharded executor
    (`repro.lowering.sharded`): both execute the same descriptor list
    through `kernels.stencil.kernel.eval_band`, so their datapaths are
    identical closures by construction."""
    program = []
    slot = {n: i for i, n in enumerate(isl.inputs)}
    blocks = column_blocks(lp, isl.schedule.order)
    for n in isl.schedule.order:
        ss = isl.schedule.stages[n]
        if n in slot:
            d = _input_descriptor(n, lp.stages[n], ss, slot[n])
        else:
            d = _compute_descriptor(lp, n, ss)
        d["M"] = blocks[n]
        d["Wb"] = -(-ss.W // blocks[n])
        program.append(d)
    tapped = {i for d in program if d["kind"] == "compute"
              for i in d["inputs"]}
    for d in program:
        d["tapped"] = d["name"] in tapped
    for out_slot, n in enumerate(isl.outputs):
        for d in program:
            if d["name"] == n:
                d["out_slot"] = out_slot
    return program


# ---------------------------------------------------------------------------
# compile
# ---------------------------------------------------------------------------

def compile_pallas(lp: LoweredPipeline,
                   outputs: Optional[Sequence[str]] = None,
                   interpret: Optional[bool] = None,
                   tile_rows: Optional[int] = None,
                   islands: bool = True) -> B.Executor:
    """Shape-specialized executor: the island plan + kernels are built
    (and cached) per input shape on first call.

    `islands=False` opts out of partitioning: the whole DAG must band-
    schedule as one program or `LoweringError` is raised (the historical
    contract, for callers that want to catch-and-fallback themselves).
    """
    from repro.kernels.stencil.kernel import fused_pipeline

    outs = list(outputs or lp.pipeline.outputs)
    order = B.needed_stages(lp, outs)
    input_names = [n for n in order if lp.stages[n].stage.is_input]
    interp = resolve_interpret(lp) if interpret is None else interpret
    # 64-bit datapaths exist only off-TPU (interpret mode); a 32-bit plan
    # traces without x64 so no 64-bit constant reaches the kernel
    x64 = needs_64bit(lp)
    cache: Dict[tuple, list] = {}

    def compile_island(isl: Island, batch: Optional[int]):
        return fused_pipeline(island_program(lp, isl),
                              grid=isl.schedule.grid,
                              interpret=interp, batch=batch)

    def build(shape):
        # a leading batch dim becomes the kernels' outer grid axis; the
        # band plan itself is a function of the per-image (H, W) only
        batch = shape[0] if len(shape) == 3 else None
        in_shape = tuple(shape[-2:])
        if islands:
            plan = partition_islands(lp, in_shape, outputs=outs,
                                     tile_rows=tile_rows)
            isls = plan.islands
        else:
            sched: Schedule = build_schedule(lp, in_shape, order=order,
                                             outputs=outs,
                                             tile_rows=tile_rows)
            isls = [Island(0, [n for n in sched.order
                               if not lp.stages[n].stage.is_input],
                           input_names, outs, Fraction(1), sched,
                           single_tile=False)]
        return [(isl, compile_island(isl, batch)) for isl in isls]

    def run(image, params_override=None):
        import jax
        import jax.numpy as jnp
        if params_override is not None and \
                dict(params_override) != lp.params:
            raise ValueError("params are baked at compile time; re-lower "
                             "with the new params")
        imgs, _ = B.normalize_images(lp, image)
        img_of = dict(zip(lp.pipeline.input_stages(), imgs))
        with obs.span("exec.pallas", backend="pallas",
                      pipeline=lp.pipeline.name, outputs=len(outs)) as sp:
            buffers, shape = B.ingest_host(lp, input_names, img_of)
            if len(shape) == 3:
                sp.set(batch=int(shape[0]))
            with jax.enable_x64(x64):
                buffers = {n: jnp.asarray(a) for n, a in buffers.items()}
                if shape not in cache:
                    sp.set(kernel_cache="miss")
                    cache[shape] = build(shape)
                else:
                    sp.set(kernel_cache="hit")
                compiled = cache[shape]
                sp.set(islands=len(compiled))
                for isl, call in compiled:
                    out_b, saved_b = isl.boundary_bytes(lp)
                    with obs.span("exec.pallas.island",
                                  island=isl.idx, rate=str(isl.rate),
                                  stages=len(isl.stages),
                                  grid=isl.schedule.grid,
                                  single_tile=isl.single_tile,
                                  carriers=isl.carrier_mix(lp),
                                  containers=isl.stored_mix(lp),
                                  out_mb=round(out_b / 1e6, 4),
                                  saved_mb=round(saved_b / 1e6, 4)):
                        for n, arr in zip(isl.outputs,
                                          call(*[buffers[n]
                                                 for n in isl.inputs])):
                            buffers[n] = arr
            res = {n: B.dequant_host(lp.stages[n], buffers[n]) for n in outs}
        # fused kernels: intermediates never leave their island's bands,
        # so telemetry covers the materialized boundaries + outputs only
        obs.runtime.record_env(res, lp, backend="pallas")
        return res

    run.lowered = lp
    run.interpret = interp    # False on a TPU: the kernel runs natively
    return run


B.register_backend("pallas", compile_pallas)
