"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed here compiles each program for
one chip of a ``v5e:2x2`` topology from abstract shapes, and refuses what
the chip would refuse (a lowering Mosaic or XLA cannot take, a program
that does not fit).  The pairing programs compile with the ``int8`` lowering
the chip traces (the CPU would resolve ``int32``).  The persistent compile
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each xdist worker imports every
test file.
"""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.ops import compile_cache, fq


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def int8_lowering():
    """Trace with the chip's lowering; fresh traces on both sides."""
    prev = fq.set_fq_backend("int8")
    jax.clear_caches()
    yield
    fq.set_fq_backend(prev)
    jax.clear_caches()


def _on(sharding, structs):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        structs)


def test_verify_128x32_int8(one_chip, int8_lowering):
    from lighthouse_tpu.ops.verify import _device_verify

    lowered = _device_verify.lower(
        *_on(one_chip, compile_cache._bucket_shape_structs(128, 32)))
    assert "i8" in lowered.as_text(), "int8 lowering not in force"
    assert lowered.compile().memory_analysis().temp_size_in_bytes > 0


def test_verify_electra_block_rows_int8(one_chip, int8_lowering):
    """An Electra block's bucket: 11 sets in 16, the 26,560-key aggregate
    over 13 key rows of 2,048 (16 a set), 37 rows in the 64-row bucket."""
    from lighthouse_tpu.ops.verify import _device_verify

    lowered = _device_verify.lower(
        *_on(one_chip, compile_cache._bucket_shape_structs(16, 2048, 64, 16)))
    assert "s1_set_sum" in lowered.as_text(debug_info=True)
    assert lowered.compile().memory_analysis().temp_size_in_bytes > 0


def test_epoch_boundary_2_20(one_chip):
    from lighthouse_tpu.ops.shuffle_device import _boundary_kernel

    with jax.enable_x64(True):
        args = _on(one_chip, compile_cache.boundary_shape_structs(2**20))
        compiled = _boundary_kernel.lower(*args, in_leak=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_kzg_batch_8_int8(one_chip, int8_lowering):
    from lighthouse_tpu.ops.kzg_device import _device_kzg_batch

    lowered = _device_kzg_batch.lower(
        *_on(one_chip, compile_cache.kzg_shape_structs(8)))
    assert "i8" in lowered.as_text(), "int8 lowering not in force"
    assert lowered.compile().memory_analysis().temp_size_in_bytes > 0
