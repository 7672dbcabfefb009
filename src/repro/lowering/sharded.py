"""`shard_map` band-sharded execution of the lowered island plan.

The pallas backend walks each rate island's row-band schedule serially
down the image; this backend distributes the *same* band walk across
devices: a 1-D mesh (`launch.mesh.make_band_mesh`, axis ``"band"``)
splits each island's grid into contiguous runs of ``k = cdiv(grid,
n_shards)`` bands (the grid pads up to ``k * n_shards``; rows past the
image are computed and dropped), every device executes its run with
the island's intermediates device-local, and the per-shard output rows
concatenate back into the
full stage arrays along the lattice-aligned band axis — bands are the
partition unit exactly as they are the VMEM-residency unit in the fused
kernel, and island boundaries stay materialized (replicated) buffers
just like the HBM stitching.

Bit-exactness is by construction, not by re-derivation: the shard body
executes the SAME stage descriptors (`pallas_backend.island_program`)
through the SAME band geometry (`kernels.stencil.kernel.eval_band`) as
the fused Pallas kernel, with `load_band` a `dynamic_slice` on the
replicated row-padded input instead of the kernel's band DMA.  Device
``d`` computes band steps ``[d*k, (d+1)*k)`` via `lax.axis_index`;
since every band's
value depends only on the (replicated) island inputs, the concatenated
result is bit-identical to the serial walk — pinned against the numpy
oracle in tests/test_serving.py, batched and phase-split plans included.

Single-tile islands (grid == 1 cannot split) run the identical band
walk unsharded on the local device, with a one-time `RuntimeWarning`
via `repro.obs.warn_once` — never a different datapath, so exactness is
unaffected.

Images with a leading batch dimension ``(B, H, W)`` vmap each band's
evaluation over the batch axis inside `shard_map` (bands stay the
partition unit; the batch axis is replicated).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from repro import obs
from repro.lowering import backends as B
from repro.lowering.ir import LoweredPipeline
from repro.lowering.islands import Island, partition_islands
from repro.lowering.pallas_backend import (island_program,
                                           island_span_attrs, needs_64bit)


def _band_walk(program: Sequence[dict], k: int, nbands: int, base_of,
               batched: bool):
    """f(*inputs) -> tuple of output stage arrays for `k` band steps.

    Inputs are the island inputs in row-padded column blocks
    (`kernels.stencil.kernel.to_blocks` for an `nbands` walk), with a
    leading batch dim when `batched`; outputs are ``([B,] M, k*step,
    Wb)`` column blocks.  `base_of()` yields the first band index of
    this walk — 0 for the serial walk, ``axis_index("band") * k`` inside
    a shard.  The k steps run as one `lax.map`, each step slicing its
    bands (outside any vmap: a batched dynamic slice would become a
    gather) and re-running `eval_band` — the one shared definition of
    the tap/clamp geometry — per image.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.stencil.kernel import (band_output, eval_band,
                                              input_pads)

    ins = sorted((d for d in program if d["kind"] == "input"),
                 key=lambda d: d["in_slot"])
    outs = sorted((d for d in program if d.get("out_slot") is not None),
                  key=lambda d: d["out_slot"])

    def fn(*inputs):
        base = base_of()

        def step(j):
            i = base + j
            bands = [jax.lax.dynamic_slice_in_dim(
                         x, i * d["step"] + d["lo"] + input_pads(d, nbands)[0],
                         d["L"], axis=x.ndim - 2)[..., :d["Wb"]]
                     for x, d in zip(inputs, ins)]

            def one(*bands):
                tiles = eval_band(program, i,
                                  lambda d, start: bands[d["in_slot"]],
                                  nbands)
                return tuple(jnp.stack([band_output(d, t)
                                        for t in tiles[d["name"]]])
                             for d in outs)

            return jax.vmap(one)(*bands) if batched else one(*bands)

        out = jax.lax.map(step, jnp.arange(k, dtype=jnp.int32))
        # (k, [B,] M, step, Wb) -> ([B,] M, k*step, Wb)
        res = []
        for b, d in zip(out, outs):
            b = jnp.moveaxis(b, 0, -3)
            res.append(b.reshape(b.shape[:-3] + (k * d["step"], d["Wb"])))
        return tuple(res)

    return fn


def compile_island(lp: LoweredPipeline, isl: Island, mesh,
                   batch: Optional[int]):
    """Jitted ``f(*island inputs) -> island outputs`` over `mesh`, and
    whether it is band-sharded (single-tile islands are not)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels.stencil.kernel import from_blocks, to_blocks

    S = mesh.shape["band"]
    grid = isl.schedule.grid
    sharded = not isl.single_tile
    if not sharded:
        obs.warn_once(
            f"sharded: island {isl.idx} of {lp.pipeline.name!r} falls "
            f"back to the serial band walk (single-tile island)")
    # the band grid pads up to a multiple of the mesh: the extra
    # bands compute rows past the image, which are dropped
    k = -(-grid // S) if sharded else grid
    nbands = k * S if sharded else grid
    program = island_program(lp, isl)
    ins = sorted((d for d in program if d["kind"] == "input"),
                 key=lambda d: d["in_slot"])
    outs_d = sorted((d for d in program
                     if d.get("out_slot") is not None),
                    key=lambda d: d["out_slot"])
    body = _band_walk(program, k, nbands,
                      (lambda: jax.lax.axis_index("band") * k) if sharded
                      else (lambda: 0), batched=batch is not None)
    if sharded:
        # every input is replicated; outputs shard their band-built
        # row axis over the mesh
        row = P(None, "band", None) if not batch \
            else P(None, None, "band", None)
        body = jax.shard_map(body, mesh=mesh,
                             in_specs=(P(),) * len(ins),
                             out_specs=(row,) * len(outs_d),
                             check_vma=False)

    def run(*arrays):
        out = body(*[to_blocks(a, d, nbands)
                     for a, d in zip(arrays, ins)])
        return tuple(from_blocks(y, d) for y, d in zip(out, outs_d))

    return jax.jit(run), sharded


def compile_sharded(lp: LoweredPipeline,
                    outputs: Optional[Sequence[str]] = None,
                    mesh=None,
                    tile_rows: Optional[int] = None) -> B.Executor:
    """Band-sharded executor over `mesh` (default: all local devices).

    Shape-specialized like the pallas backend: the island plan and the
    jitted shard programs are built (and cached) per input shape on
    first call.
    """
    from repro.launch.mesh import make_band_mesh

    outs = list(outputs or lp.pipeline.outputs)
    order = B.needed_stages(lp, outs)
    input_names = [n for n in order if lp.stages[n].stage.is_input]
    cache: Dict[tuple, tuple] = {}
    host_buffers = B.HostBuffers()
    x64 = needs_64bit(lp)
    census = lp.census(order)

    def build(shape, mesh):
        batch = shape[0] if len(shape) == 3 else None
        in_shape = tuple(shape[-2:])
        plan = partition_islands(lp, in_shape, outputs=outs,
                                 tile_rows=tile_rows)
        compiled = []
        for isl in plan.islands:
            call, is_sharded = compile_island(lp, isl, mesh, batch)
            compiled.append((isl, call, dict(island_span_attrs(lp, isl),
                                             sharded=is_sharded)))
        return compiled, sum(a["sharded"] for _, _, a in compiled)

    def run(image, params_override=None):
        import jax
        import jax.numpy as jnp
        if params_override is not None and \
                dict(params_override) != lp.params:
            raise ValueError("params are baked at compile time; re-lower "
                             "with the new params")
        m = make_band_mesh() if mesh is None else mesh
        imgs, _ = B.normalize_images(lp, image)
        img_of = dict(zip(lp.pipeline.input_stages(), imgs))
        with obs.span("exec.sharded", backend="sharded",
                      pipeline=lp.pipeline.name, outputs=len(outs),
                      shards=m.shape["band"], **census) as sp:

            def to_device():
                # narrow replicated inputs: container-dtype frames ship
                # as-is across the mesh (zero-copy ingest)
                buffers, _ = B.ingest_host(lp, input_names, img_of)
                return {n: jnp.asarray(a) for n, a in buffers.items()}

            def dispatch(buffers):
                shape = tuple(buffers[input_names[0]].shape)
                if len(shape) == 3:
                    sp.set(batch=shape[0])
                key = shape + (m.shape["band"],)
                if key not in cache:
                    sp.set(kernel_cache="miss")
                    cache[key] = build(shape, m)
                else:
                    sp.set(kernel_cache="hit")
                compiled, n_sharded = cache[key]
                sp.set(islands=len(compiled), sharded_islands=n_sharded)
                for isl, call, attrs in compiled:
                    with obs.span("exec.sharded.island", **attrs):
                        for n, arr in zip(isl.outputs,
                                          call(*[buffers[i]
                                                 for i in isl.inputs])):
                            buffers[n] = arr
                return buffers

            with jax.enable_x64(x64):
                res = B.run_on_device(lp, outs, to_device, dispatch,
                                      host_buffers)
        # like pallas: intermediates never materialize, telemetry covers
        # island boundaries + outputs only
        obs.runtime.record_env(res, lp, backend="sharded")
        return res

    run.lowered = lp
    return run


B.register_backend("sharded", compile_sharded)
