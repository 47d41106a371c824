"""Compile the main-path Pallas kernels and the hybrid engine for a TPU v5e.

No chip is needed: the TPU compiler is installed with JAX, and it compiles
for a ``v5e:2x2`` topology that is described, not attached. The kernels run
interpreted in every other test of the suite, where Mosaic's tiling, layout
and VMEM rules are never checked; these tests are where a block shape or an
op Mosaic refuses fails, at the sizes the smoke deployment serves
(V = 2**15 vertices, Q = 16 queries).

The topology is described inside a module-scoped fixture — never while a
module is imported — and the tests skip from that fixture where it cannot
be described. The persistent compilation cache is switched off around
them: an entry written for a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V = 32768
W = V // 32
Q = 16
L = 256            # landmark budget of the label-join probe (8 words)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(name, sds):
    """(kernel, operand shapes, tiles) exactly as the named ops.py wrapper
    calls it at V, Q: the single-query packed step runs the fused kernel on
    an 8-row slab; queries are padded to the 8-row sublane multiple."""
    from repro.kernels.bfs_multi_step.kernel import (
        multi_bfs_step_packed_pallas,
    )
    from repro.kernels.bfs_pull_step.kernel import bfs_pull_step_pallas
    from repro.kernels.label_join.kernel import label_join_packed_pallas
    from repro.kernels.mosaic import pick_row_tile, pick_word_tile

    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    push = dict(tr=pick_row_tile(V), tw=pick_word_tile(W))
    return {
        "bfs_step_packed": (multi_bfs_step_packed_pallas, (
            sds((8, V), f32), sds((V, W), u32), sds((V,), i32),
            sds((8, V), i32)), push),
        "multi_bfs_step_packed": (multi_bfs_step_packed_pallas, (
            sds((Q, V), f32), sds((V, W), u32), sds((V,), i32),
            sds((Q, V), i32)), push),
        "multi_bfs_pull_step": (bfs_pull_step_pallas, (
            sds((Q, W), u32), sds((V, W), u32), sds((V,), i32),
            sds((Q, V), i32)), dict(tr=pick_row_tile(V))),
        "label_join_packed": (label_join_packed_pallas, (
            sds((Q, L // 32), u32), sds((Q, L // 32), u32)),
            dict(tq=pick_row_tile(Q), tw=pick_word_tile(L // 32))),
    }[name]


@pytest.mark.parametrize("name", ["bfs_step_packed", "multi_bfs_step_packed",
                                  "multi_bfs_pull_step", "label_join_packed"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, tiles = _kernel_case(name, sds)
    compiled = fn.lower(*args, **tiles, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hybrid_multi_bfs_fits_one_chip(one_chip):
    import importlib

    from repro.core.graph import GraphState

    bfs = importlib.import_module("repro.core.bfs")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = GraphState(sds((V,), jnp.int32), sds((V,), jnp.bool_),
                       sds((V,), jnp.int32), sds((V,), jnp.int32),
                       sds((V, W), jnp.uint32), sds((V, W), jnp.uint32))
    slots = sds((Q,), jnp.int32)
    compiled = bfs._multi_bfs_jit.lower(
        state, slots, slots, backend="hybrid", parents=True,
        alpha=bfs.DEFAULT_ALPHA, beta=bfs.DEFAULT_BETA).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
