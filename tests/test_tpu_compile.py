"""Compile the main path's device programs for a TPU v5e that is
described, not attached: the fused band kernel (dus_ext at 1080p and
4K), the lowered XLA forward of usm and hcd at 1080p, and the 4-device
band-sharded program at 4K.  What the TPU compilers refuse fails here,
at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and pytest-xdist
workers each import every test module.
"""
import os
import warnings

import numpy as np
import pytest

from repro.lowering import backends as B
from repro.lowering import compile_pipeline, lower
from repro.lowering.islands import partition_islands
from repro.pipelines import dus, hcd, usm
from repro.pipelines import workflows as W

BATCH = 4


def _types(pipe):
    """The benchmark's widths: static-analysis alphas, beta = 4."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        alphas, signed = W.static_alphas(pipe)
        return W.types_from_alpha(pipe, alphas, signed,
                                  {n: 4 for n in pipe.stages})


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _frame_args(lp, names, shape, sharding):
    import jax
    return [jax.ShapeDtypeStruct(shape, np.dtype(B.store_dtype(lp.stages[n])),
                                 sharding=sharding) for n in names]


@pytest.mark.parametrize("shape", [(1080, 1920), (2160, 3840)],
                         ids=["1080p", "4k"])
def test_fused_kernel_compiles_for_v5e(one_chip, shape):
    from repro.kernels.stencil.kernel import fused_pipeline
    from repro.lowering.pallas_backend import island_program
    lp = lower(dus.build_extended(), _types(dus.build_extended()))
    plan = partition_islands(lp, shape)
    assert plan.fully_fused
    for isl in plan.islands:
        call = fused_pipeline(island_program(lp, isl),
                              grid=isl.schedule.grid, interpret=False,
                              batch=BATCH)
        compiled = call.lower(*_frame_args(
            lp, isl.inputs, (BATCH,) + shape, one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["usm", "hcd"])
def test_lowered_forward_compiles_for_v5e(one_chip, name):
    """XLA:TPU takes the exact datapaths' f64/int64 ops (compiling; hcd's
    f64 `harris` replay is not bit-exact on the chip — see ROADMAP S3)."""
    import jax
    pipe, params = {"usm": (usm.build(), dict(usm.DEFAULT_PARAMS)),
                    "hcd": (hcd.build(), {})}[name]
    run = compile_pipeline(pipe, _types(pipe), params=params, backend="jnp",
                           outputs=list(pipe.stages))
    with jax.enable_x64(True):
        fwd = jax.jit(jax.vmap(run.forward))
        fwd.lower(*_frame_args(run.lowered, pipe.input_stages(),
                               (BATCH, 1080, 1920), one_chip)).compile()


def test_sharded_band_walk_compiles_for_4_devices(topo):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.lowering.sharded import compile_island
    mesh = Mesh(np.array(topo.devices[:4]), ("band",))
    lp = lower(dus.build_extended(), _types(dus.build_extended()))
    plan = partition_islands(lp, (2160, 3840))
    for isl in plan.islands:
        fn, sharded = compile_island(lp, isl, mesh, BATCH)
        assert sharded
        fn.lower(*_frame_args(lp, isl.inputs, (BATCH, 2160, 3840),
                              NamedSharding(mesh, P()))).compile()
