"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which ships with jaxlib, compiles each
program for one chip of a described v5e:2x2 topology, so what the chip's
compiler would refuse (a kernel tiling, a layout, VMEM use) fails here at
no chip time. The topology is described inside a fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file. The persistent compile cache stays off around these
compiles — an entry compiled for a described chip cannot be read back on
the CPU.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from coreth_tpu.ops.keccak_pallas import segment_keccak_pallas
from coreth_tpu.ops.keccak_planned import MAX_SEGMENTS, WORDS_PER_BLOCK, _make_step
from coreth_tpu.ops.keccak_staged import _segment_keccak


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lanes,blocks", [(8192, 1)])
def test_pallas_segment_kernel_compiles(one_chip, lanes, blocks):
    words = _spec((lanes, blocks, WORDS_PER_BLOCK), jnp.uint32, one_chip)
    compiled = jax.jit(segment_keccak_pallas).lower(words).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes,blocks", [(8192, 2)])
def test_xla_segment_keccak_compiles(one_chip, lanes, blocks):
    words = _spec((lanes, blocks, WORDS_PER_BLOCK), jnp.uint32, one_chip)
    compiled = jax.jit(_segment_keccak).lower(words).compile()
    assert compiled.memory_analysis() is not None


def test_planned_commit_step_compiles(one_chip):
    lanes, blocks, npatch = 8192, 1, 4096
    words = 2 * lanes * blocks * WORDS_PER_BLOCK
    step = _make_step(_segment_keccak, donate=False)
    args = (
        _spec((words,), jnp.uint32, one_chip),           # flat_words
        _spec((1 + 2 * lanes, 8), jnp.uint32, one_chip),  # dig
        _spec((npatch,), jnp.int32, one_chip),           # dst words
        _spec((npatch,), jnp.int32, one_chip),           # child lanes
        _spec((npatch,), jnp.int32, one_chip),           # byte shifts
        _spec((MAX_SEGMENTS, 3), jnp.int32, one_chip),   # segment meta
        _spec((), jnp.int32, one_chip),                  # segment index
    )
    compiled = step.lower(*args, lanes=lanes, blocks=blocks,
                          npatch=npatch).compile()
    assert compiled.memory_analysis() is not None


def test_resident_commit_program_compiles(one_chip):
    """The resident executor's whole-commit program (tile loop, switch
    over arena classes) at a block-sized key: no arena-sized relayout
    inside the loop, which row-padded arenas keep row-major."""
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    ex = ResidentExecutor(fused=True)
    classes = (1, 2, 3, 4)
    key = (classes, 8192, (6144,) * 4, 4096, 4096, 32, (1024, 16, 16, 16), 0)
    (rows,), (aux,) = ex._upload_shapes(key)
    args = ([_spec((8192, 8), jnp.uint32, one_chip)]
            + [_spec((6144, ex._arena_width(c)), jnp.uint32, one_chip)
               for c in classes]
            + [_spec((rows,), jnp.uint32, one_chip),
               _spec((aux,), jnp.int32, one_chip)])
    compiled = ex._fused_jit(key).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    arena_copies = [line for line in compiled.as_text().splitlines()
                    if " copy(" in line and "u32[6144," in line]
    assert not arena_copies, arena_copies[:2]
