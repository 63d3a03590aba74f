"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jax compiles
for a v5e that is described (``topologies.get_topology_desc``), not
attached, so what Mosaic or XLA:TPU would refuse — an unsupported in-kernel
op, a block shape off the (8, 128) tiling, a program that does not fit the
chip's 16 GiB of HBM — fails here, at the widths of the scale-16 Kronecker
graph ``chip_smoke.py`` decomposes (n_r = 917,504 r-cliques, n_s =
15,728,640 s-cliques, C = 3 members each, after padding).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry compiled for an absent chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import ScatterSpec, _dense_engine
from repro.core.schedule import PeelSchedule
from repro.kernels import ops
from repro.kernels.segment_sum import (DEFAULT_BLOCK_N, DEFAULT_CHUNK_E,
                                       segment_sum_sorted)

N_R = 917_504
N_S = 15_728_640
C = 3
E = N_S * C
MAX_CHUNKS = 64
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_fits(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, (m.argument_size_in_bytes, m.temp_size_in_bytes)
    return m


def test_segment_sum_sorted_lane_major_compiles(one_chip):
    """The per-edge operand is (1, E): no 128-fold lane padding."""
    f = jax.jit(lambda d, i: segment_sum_sorted(
        d, i, N_R, max_chunks=MAX_CHUNKS, interpret=False))
    compiled = f.lower(_spec(one_chip, (1, E), jnp.int32),
                       _spec(one_chip, (E,), jnp.int32)).compile()
    m = _assert_fits(compiled)
    # the two (E,)-sized operands, unpadded, plus the small block windows
    assert m.argument_size_in_bytes < 3 * 4 * E


def test_dense_engine_tpu_default_plan_compiles(one_chip):
    """The whole fused-hierarchy peel with the TPU-default scatter plan."""
    spec = ScatterSpec(block_n=DEFAULT_BLOCK_N, chunk_e=DEFAULT_CHUNK_E,
                       max_chunks=MAX_CHUNKS, n_seg_pad=N_R, interpret=False)
    compiled = _dense_engine.lower(
        _spec(one_chip, (N_S, C), jnp.int32),
        _spec(one_chip, (N_R,), jnp.int32),
        _spec(one_chip, (E,), jnp.int32),
        _spec(one_chip, (E,), jnp.int32),
        _spec(one_chip, (N_R,), jnp.bool_),
        schedule=PeelSchedule(kind="exact", s_choose_r=C),
        max_rounds=N_R + 2, spec=spec, hierarchy=True,
        fused=False).compile()
    _assert_fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_tricount_oriented_compiles(one_chip):
    f = jax.jit(lambda a: ops.tricount_oriented(a, interpret=False))
    compiled = f.lower(_spec(one_chip, (4096, 4096), jnp.float32)).compile()
    _assert_fits(compiled)
