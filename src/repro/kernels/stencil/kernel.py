"""Fixed-point stencil Pallas kernels — the paper's core datapath on TPU.

FPGA adaptation (DESIGN.md §2): the paper's designs stream pixels through
*line buffers* so each output pixel sees its stencil window without HBM
re-reads.  The TPU analogue keeps a band of rows (the tile + halo) resident
in VMEM: the input stays in HBM (`pl.ANY`), each grid step copies one
(TH + 2*hy)-row band, and the taps become static shifted slices combined
with integer multiply-accumulate in VREGs.

Two entry points live here:

  * `fixedpoint_stencil` — the single-stage kernel (one linear stencil,
    unit stride), with per-axis halos: a horizontal-only stencil copies a
    band of TH rows (no row halo at all — the line-buffer-free case).
    Arithmetic is the paper's saturating fixed point, exactly:

        out_q = clip((sum_k w_q[k] * in_q[y+dy_k, x+dx_k] + bias) >> shift,
                     qmin, qmax)

    with `shift = beta_in + w_beta - beta_out` (round-half-up; all integer
    math exact in int32 — ops.py checks the width budget).

  * `fused_pipeline` — the multi-stage generalization the plan-driven
    lowering (`repro.lowering.pallas_backend`) compiles into: one grid
    walks a band schedule over the whole stage DAG, every intermediate
    stage's rows stay in VMEM, and each input band arrives by a
    double-buffered DMA.  The kernel body here owns the *geometry* (band
    loads, tap offsets, rate changes, edge replication); the caller
    supplies each stage's datapath as a closure
    ``fn(tap, rows, cols) -> tile`` so this module stays IR-agnostic.

Stage descriptors for `fused_pipeline` are plain dicts:

    kind      "input" | "compute"
    name      stage key
    step      output rows per grid tile
    lo, L     row-span start (relative to i*step) and length
    H, W      full stage height/width
    M, Wb     column blocks and lanes per block (see `eval_band`)
    dtype     tile/output dtype
    in_slot   (inputs) operand index of the pallas_call
    stride, upsample, fn   (compute) vertical/horizontal rates + datapath
    tapped    whether a compute stage of the program reads this stage
    out_slot  optional output index

Taps implement the executor's exact sampling semantics: output row `y`
of a stage reads its input at row `clip(floor((y*sy + dy) / uy))`
(upsample-expand, shift, decimate, edge-replicate) — identical to
edge-padding the expanded array like `dsl.exec._pad_inputs` does.  Every
tap is a static slice, reshape or lane shift: band rows are *virtual*
rows (the lattice-aligned schedule makes each tap's band offset
independent of the band index), and border bands replace the rows past
the image edge with the edge row (`_edge_fix`).
"""
from __future__ import annotations

import functools
from math import gcd
from typing import Callable, Dict, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Tap = Tuple[int, int, int]   # (dy, dx, w_q)
Halo = Union[int, Tuple[int, int]]


def _halo_yx(halo: Halo) -> Tuple[int, int]:
    if isinstance(halo, tuple):
        return halo
    return (int(halo), int(halo))


def _stencil_kernel(x_ref, o_ref, *, taps: Sequence[Tap], halo: Tuple[int, int],
                    shift: int, qmin: int, qmax: int, tile_h: int, width: int):
    i = pl.program_id(0)
    hy, hx = halo
    # one VMEM-resident band of rows: the line-buffer analogue (hy rows of
    # halo only — a horizontal stencil's band is just its own tile rows)
    band = x_ref[pl.ds(i * tile_h, tile_h + 2 * hy), :]
    acc = jnp.zeros((tile_h, width), jnp.int32)
    for dy, dx, wq in taps:
        if wq == 0:
            continue
        sl = band[hy + dy: hy + dy + tile_h,
                  hx + dx: hx + dx + width]
        acc = acc + wq * sl
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift     # round-half-up
    o_ref[...] = jnp.clip(acc, qmin, qmax)            # saturation mode


def fixedpoint_stencil(x_q: jax.Array, taps: Sequence[Tap], halo: Halo,
                       shift: int, qmin: int, qmax: int,
                       tile_h: int = 8, interpret: bool = True) -> jax.Array:
    """Apply the quantized stencil to a pre-padded scaled-int image.

    x_q: int32 (H + 2*hy, W + 2*hx), edge-padded per axis
    returns int32 (H, W) at the output type's scale.
    """
    hy, hx = _halo_yx(halo)
    Hp, Wp = x_q.shape
    H, W = Hp - 2 * hy, Wp - 2 * hx
    if H % tile_h != 0:
        raise ValueError(f"H={H} not divisible by tile_h={tile_h}")
    kern = functools.partial(_stencil_kernel, taps=tuple(taps),
                             halo=(hy, hx), shift=shift, qmin=qmin,
                             qmax=qmax, tile_h=tile_h, width=W)
    return pl.pallas_call(
        kern,
        grid=(H // tile_h,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # stays in HBM; band-loaded
        out_specs=pl.BlockSpec((tile_h, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.int32),
        interpret=interpret,
    )(x_q)


# ---------------------------------------------------------------------------
# fused multi-stage band kernel
# ---------------------------------------------------------------------------

# Scoped VMEM the fused kernel may claim (the compiler's default scoped
# limit is far below both a v5e core's VMEM and what a 4K band program of
# a deep pipeline keeps live).  `tests/test_tpu_compile.py` compiles the 4K
# dus_ext band program under it.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# rows of the TPU's (8, 128) vector tile: output blocks and band DMA
# windows start and span multiples of it (`repro.lowering.schedule`)
ROW_ALIGN = 8
LANES = 128


def _wide(x):
    """The 32-bit view taps shuffle: Mosaic decimates, repeats and
    interleaves rows of 32-bit vectors only (packed 8/16-bit rows share a
    sublane), so narrow containers widen once per band before their taps
    read them — value-preserving, every container fits int32."""
    dt = jnp.dtype(x.dtype)
    if dt.itemsize >= 4:
        return x
    return x.astype(jnp.float32 if jnp.issubdtype(dt, jnp.floating)
                    else jnp.int32)


def _lowest(dt):
    dt = jnp.dtype(dt)
    if jnp.issubdtype(dt, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dt).min


def _bcast_rows(x, row: int, n: int):
    return jnp.broadcast_to(x[row:row + 1], (n,) + x.shape[1:])


def _affine_rows(x, n: int, s: int, u: int, c: int):
    """Rows ``floor((r*s + c) / u)`` of `x` for r in [0, n) — static.

    Decimation is a reshape, upsampling an interleave of shifted slices;
    rows past the end of `x` (only ever read by rows the caller drops or
    by interleave padding) replicate the last row."""
    g = gcd(s, u)
    s, u, c = s // g, u // g, c // g          # floor(c/g): exact, see below
    Lx = x.shape[0]
    if u == 1:
        if c >= Lx:
            return _bcast_rows(x, Lx - 1, n)
        y = x[c:c + n * s]
        if y.shape[0] < n * s:
            y = jnp.concatenate(
                [y, _bcast_rows(x, Lx - 1, n * s - y.shape[0])], axis=0)
        if s == 1:
            return y
        return y.reshape((n, s) + x.shape[1:])[:, 0]
    # coprime s, u > 1: output row r = m*u + t reads m*s + floor((t*s+c)/u)
    m = -(-n // u)
    phases = [_affine_rows(x, m, s, 1, (t * s + c) // u) for t in range(u)]
    return jnp.stack(phases, axis=1).reshape((m * u,) + x.shape[1:])[:n]


def take_rows(x, n: int, s: int, u: int, c: int):
    """Rows ``clip(floor((r*s + c) / u), 0, len(x) - 1)`` for r in [0, n).

    The one row geometry of a tap: `s`/`u` are the consumer's vertical
    stride/upsample and `c` its band-relative offset, all static, so the
    result is slices, a reshape or an interleave — never a gather.  The
    clip replicates the first/last row of `x`: a no-op under a banded
    schedule (the span pass keeps every tap inside the parent band) and
    the oracle's edge-replicate clamp under a single-tile schedule.
    (``floor((r*s + c)/u) == floor((r*s/g + floor(c/g)) / (u/g))`` for
    ``g = gcd(s, u)``, which `_affine_rows` relies on.)"""
    Lx = x.shape[0]
    raw = (np.arange(n) * s + c) // u         # non-decreasing
    n_lo = int((raw < 0).sum())
    n_hi = int((raw >= Lx).sum())
    parts = []
    if n_lo:
        parts.append(_bcast_rows(x, 0, n_lo))
    if n - n_lo - n_hi:
        parts.append(_affine_rows(x, n - n_lo - n_hi, s, u, n_lo * s + c))
    if n_hi:
        parts.append(_bcast_rows(x, Lx - 1, n_hi))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _gather_lanes(block_of, blk: np.ndarray, lane: np.ndarray):
    """Static lane gather over column blocks: output lane k is lane
    ``lane[k]`` of block ``blk[k]``.  Emitted as maximal runs of
    contiguous lanes (slices) and of one repeated lane (broadcasts of an
    edge column)."""
    parts = []
    q, n = 0, len(blk)
    while q < n:
        b, l = int(blk[q]), int(lane[q])
        x = block_of(b)
        k = q + 1
        if k < n and blk[k] == b and lane[k] == l + 1:
            while k < n and blk[k] == b and lane[k] == lane[k - 1] + 1:
                k += 1
            parts.append(x[:, l:l + k - q])
        else:
            while k < n and blk[k] == b and lane[k] == l:
                k += 1
            parts.append(jnp.broadcast_to(x[:, l:l + 1], (x.shape[0], k - q)))
        q = k
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _border_possible(d: Dict, nbands: int) -> bool:
    return d["lo"] < 0 or (nbands - 1) * d["step"] + d["lo"] + d["L"] > d["H"]


def _edge_fix(blocks, start, H: int, L: int):
    """Edge-replicate a band tile at the image border.

    A band tile holds rows ``[start, start + L)`` of its stage computed
    on *virtual* rows; rows above 0 / below ``H - 1`` must hold the
    stage's row 0 / row ``H - 1`` (the oracle pads every stage's input
    that way).  Only border bands take the fix (`lax.cond`); the schedule
    guarantees every band overlaps its stage, so both edge rows it needs
    are inside the tile."""
    rid = start + jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    def fix(bs):
        out = []
        for b in bs:
            low = _lowest(b.dtype)
            top = jnp.max(jnp.where(rid == 0, b, low), axis=0, keepdims=True)
            bot = jnp.max(jnp.where(rid == H - 1, b, low), axis=0,
                          keepdims=True)
            out.append(jnp.where(rid < 0, top, jnp.where(rid >= H, bot, b)))
        return out

    return jax.lax.cond((start < 0) | (start + L > H), fix,
                        lambda bs: list(bs), list(blocks))


def eval_band(program: Sequence[Dict], i, load_band,
              nbands: int) -> Dict[str, list]:
    """Evaluate one band step `i` of a fused stage program.

    This is the ONE definition of the band geometry — tap offsets, rate
    changes and edge-replicate clamps — shared by the pallas kernel
    (`_fused_kernel`, where `load_band` reads the band its DMA fetched)
    and the `shard_map` band-sharded executor (`repro.lowering.sharded`,
    where `load_band` is a `dynamic_slice` on a device-local array).
    Sharing it is what makes the sharded program bit-identical to the
    fused kernel by construction.

    Every stage is held in ``M`` column blocks (``d["M"]``): block ``p``
    lane ``q`` is column ``q*M + p``.  Column decimation and upsampling
    then only ever pick whole blocks and shift lanes, and row rate
    changes are static reshapes (`take_rows`), so every tap is a static
    slice — the form Mosaic compiles.

    `load_band(d, start)` returns input descriptor `d`'s band of virtual
    rows ``[start, start + d["L"])`` as ``(M, L, Wb)``; rows outside the
    image may hold anything.  `i` may be traced (pallas `program_id`, a
    shard's band index); `nbands` is the number of bands walked.
    Returns ``{stage: [block (L, Wb), ...]}`` in stored dtypes.
    """
    by_name = {d["name"]: d for d in program}
    tiles: Dict[str, list] = {}
    wide: Dict[str, list] = {}
    for d in program:
        start = i * d["step"] + d["lo"]
        L, M = d["L"], d["M"]
        if d["kind"] == "input":
            band = load_band(d, start)
            blocks = [band[p] for p in range(M)]
        else:
            sy, sx = d["stride"]
            uy, ux = d["upsample"]
            rows = start + jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
            lanes = np.arange(d["Wb"])
            row_views: Dict[tuple, jax.Array] = {}

            def tap(pname, dy, dx, *, p, d=d, sy=sy, sx=sx, uy=uy, ux=ux,
                    lanes=lanes, row_views=row_views):
                pd = by_name[pname]
                c = d["lo"] * sy + dy - pd["lo"] * uy

                def block_of(b):
                    key = (pname, b, c)
                    if key not in row_views:
                        row_views[key] = take_rows(wide[pname][b], d["L"],
                                                   sy, uy, c)
                    return row_views[key]

                col = np.clip(((lanes * d["M"] + p) * sx + dx) // ux,
                              0, pd["W"] - 1)
                return _gather_lanes(block_of, col % pd["M"], col // pd["M"])

            blocks = []
            for p in range(M):
                cols = (jax.lax.broadcasted_iota(jnp.int32, (1, d["Wb"]), 1)
                        * M + p)
                blocks.append(d["fn"](functools.partial(tap, p=p), rows,
                                      cols))
        tiles[d["name"]] = blocks
        if d.get("tapped"):
            w = [_wide(b) for b in blocks]
            if _border_possible(d, nbands):
                w = _edge_fix(w, start, d["H"], L)
            wide[d["name"]] = w
    return tiles


def band_output(d: Dict, tile: jax.Array) -> jax.Array:
    """The `step` output rows of a stage's band tile (drops the halo)."""
    return tile[-d["lo"]: -d["lo"] + d["step"]]


def dma_window(d: Dict) -> Tuple[int, int, int]:
    """``(first, offset, length)`` of an input's band DMA window.

    The TPU copies whole (8, 128) tiles of an HBM array, so the window a
    band's copy fetches starts on a multiple of `ROW_ALIGN` rows of the
    row-padded input: band ``i`` copies rows ``[first + i*step, first +
    i*step + length)`` and its virtual rows ``[i*step + lo, ... + L)``
    sit at the static `offset` inside (the schedule keeps an input's
    `step` a multiple of `ROW_ALIGN` whenever there is more than one
    band)."""
    base = d["lo"] + max(0, -d["lo"])        # first band row, padded
    offset = base % ROW_ALIGN
    length = -(-(offset + d["L"]) // ROW_ALIGN) * ROW_ALIGN
    return base - offset, offset, length


def input_pads(d: Dict, nbands: int) -> Tuple[int, int]:
    """Rows an input is padded with above/below so that every band
    window of a `nbands` walk loads in bounds (the padding's values are
    never used: `eval_band` edge-replicates virtual rows)."""
    top = max(0, -d["lo"])
    first, _, length = dma_window(d)
    bot = max(0, (nbands - 1) * d["step"] + first + length - top - d["H"])
    return top, bot


def _input_lanes(d: Dict) -> int:
    """Lanes of an input's block arrays: `Wb` rounded up to whole
    128-lane tiles, so band DMAs copy whole tiles (extra lanes are never
    read)."""
    return -(-d["Wb"] // LANES) * LANES


def to_blocks(x, d: Dict, nbands: int):
    """``(..., H, W)`` image -> ``(..., M, top + H + bot, lanes)`` column
    blocks (block ``p`` lane ``q`` = column ``q*M + p``), row-padded per
    `input_pads` and lane-padded per `_input_lanes`."""
    M, Wb = d["M"], _input_lanes(d)
    top, bot = input_pads(d, nbands)
    cfg = [(0, 0)] * (x.ndim - 2) + [(top, bot), (0, M * Wb - x.shape[-1])]
    x = jnp.pad(x, cfg)
    x = x.reshape(x.shape[:-1] + (Wb, M))
    return jnp.moveaxis(x, -1, -3)


def from_blocks(y, d: Dict):
    """Inverse of `to_blocks` for an output stage: ``(..., M, H', Wb)``
    -> ``(..., H, W)`` (drops ragged-band rows and block padding)."""
    y = jnp.moveaxis(y, -3, -1)
    y = y.reshape(y.shape[:-2] + (y.shape[-2] * y.shape[-1],))
    return y[..., :d["H"], :d["W"]]


def _write_outputs(program: Sequence[Dict], tiles: Dict, out_refs,
                   batched: bool) -> None:
    for d in program:
        slot = d.get("out_slot")
        if slot is None:
            continue
        for p, tile in enumerate(tiles[d["name"]]):
            rows = band_output(d, tile)
            if batched:
                out_refs[slot][0, p] = rows   # block carries a unit batch dim
            else:
                out_refs[slot][p] = rows


def _fused_kernel(*refs, program: Sequence[Dict], n_in: int, n_out: int,
                  batched: bool, nbands: int):
    """One band step: double-buffered band DMA, then `eval_band`.

    Each HBM input gets a two-slot VMEM scratch: band `i` computes out
    of slot ``i % 2`` while the async copy of band ``i + 1`` fills the
    other slot, overlapping the HBM->VMEM line-buffer fill with compute
    (grid steps run sequentially per core, so scratch persists across
    them).  Inputs arrive row-padded (`input_pads`), so every band's
    copy fetches an aligned window in bounds (`dma_window`).  Prefetch never
    crosses the image boundary of the outer batch axis: each image's
    first band is fetched under the ``i == 0`` warm-up (one bubble per
    image).
    """
    in_refs = refs[:n_in]
    out_refs = refs[n_in:n_in + n_out]
    scratch = refs[n_in + n_out:]          # (vmem, sem) pair per input
    if batched:
        bi, i = pl.program_id(0), pl.program_id(1)
    else:
        bi, i = None, pl.program_id(0)
    inputs = [d for d in program if d["kind"] == "input"]
    cur, nxt = i % 2, (i + 1) % 2

    def dma(d, slot, j):
        vmem = scratch[2 * d["in_slot"]]
        sem = scratch[2 * d["in_slot"] + 1]
        first, _, length = dma_window(d)
        b = pl.multiple_of(j * d["step"] + first, ROW_ALIGN) \
            if nbands > 1 else first
        src = in_refs[d["in_slot"]]
        src = src.at[bi, :, pl.ds(b, length), :] if batched \
            else src.at[:, pl.ds(b, length), :]
        return pltpu.make_async_copy(src, vmem.at[slot], sem.at[slot])

    for d in inputs:
        @pl.when(i == 0)                   # warm-up: fetch this image's
        def _(d=d):                        # first band
            dma(d, cur, i).start()

        @pl.when(i + 1 < nbands)
        def _(d=d):
            dma(d, nxt, i + 1).start()
    for d in inputs:
        dma(d, cur, i).wait()

    def load_band(d, start):
        # `start` is the virtual row the DMA'd window holds at `offset`
        _, offset, _ = dma_window(d)
        return scratch[2 * d["in_slot"]][cur][:, offset:offset + d["L"],
                                              :d["Wb"]]

    tiles = eval_band(program, i, load_band, nbands)
    _write_outputs(program, tiles, out_refs, batched)


def fused_pipeline(program: Sequence[Dict], grid: int, name: str,
                   interpret: bool = True,
                   batch: int | None = None) -> Callable:
    """Compile a band-scheduled stage program into one pallas_call.

    Returns a jitted ``f(*input_arrays) -> tuple(output_arrays)`` over
    plain ``(H, W)`` (or, with `batch`, ``(batch, H, W)``) arrays; see the
    module docstring for the descriptor contract.  The wrapper converts
    inputs to row-padded column blocks (`to_blocks`) and outputs back
    (`from_blocks`) around the kernel.  With `batch` the grid gains an
    outer batch axis — ``grid=(batch, bands)`` — so every (image, band)
    pair is one grid step of the same VMEM-resident band program.  The
    last band may be ragged: output blocks past a stage's height are
    dropped on write.  `name` names the kernel in compiled programs and
    device traces.
    """
    ins = sorted((d for d in program if d["kind"] == "input"),
                 key=lambda d: d["in_slot"])
    outs = sorted((d for d in program if d.get("out_slot") is not None),
                  key=lambda d: d["out_slot"])
    scratch_shapes = []
    for d in ins:
        scratch_shapes += [pltpu.VMEM((2, d["M"], dma_window(d)[2],
                                       _input_lanes(d)), d["dtype"]),
                           pltpu.SemaphoreType.DMA((2,))]
    kern = functools.partial(_fused_kernel, program=tuple(program),
                             n_in=len(ins), n_out=len(outs),
                             batched=batch is not None, nbands=grid)
    if batch is None:
        out_specs = [pl.BlockSpec((d["M"], d["step"], d["Wb"]),
                                  lambda i: (0, i, 0)) for d in outs]
        out_shape = [jax.ShapeDtypeStruct((d["M"], d["H"], d["Wb"]),
                                          d["dtype"]) for d in outs]
        grid_dims: Tuple[int, ...] = (grid,)
    else:
        out_specs = [pl.BlockSpec((1, d["M"], d["step"], d["Wb"]),
                                  lambda b, i: (b, 0, i, 0)) for d in outs]
        out_shape = [jax.ShapeDtypeStruct((batch, d["M"], d["H"], d["Wb"]),
                                          d["dtype"]) for d in outs]
        grid_dims = (batch, grid)
    call = pl.pallas_call(
        kern,
        grid=grid_dims,
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * len(ins),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )

    def run(*arrays):
        out = call(*[to_blocks(a, d, grid) for a, d in zip(arrays, ins)])
        out = out if isinstance(out, (tuple, list)) else (out,)
        return tuple(from_blocks(y, d) for y, d in zip(out, outs))

    return jax.jit(run)
