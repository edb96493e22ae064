"""Compile the write path's kernels and the diagnostics' histogram program
for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler refuses here what it would refuse on the
chip (tiling, VMEM, HBM). The topology is described inside a fixture, so
only the test worker that runs this file loads the TPU library.
`pic_run_chunk` at paper_config takes about a minute to compile; the
chip smoke run covers it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.bit1 import paper_config
from repro.kernels.bitshuffle.kernel import byte_shuffle_block
from repro.kernels.bitshuffle.ops import shuffled_items
from repro.pic.histogram import species_histograms


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n_bytes,itemsize", [
    (1 << 20, 4),        # one 1 MiB codec block of float32
    (1 << 20, 8),
    (4000, 4),           # a tail block
    (8, 4),              # the PRNG key leaf of a checkpoint
])
def test_shuffle_block_compiles_for_v5e(one_chip, n_bytes, itemsize):
    block = jax.ShapeDtypeStruct((n_bytes,), jnp.uint8, sharding=one_chip)
    compiled = byte_shuffle_block.lower(block, itemsize=itemsize,
                                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(1 << 25,), (1 << 25, 3)])
def test_block_shuffle_never_relayouts_a_particle_record(one_chip, shape):
    """Shuffling one 1 MiB block of a paper_config particle record ([C]
    positions, [C, 3] velocities) takes a few MiB of temp memory, not a
    copy of the whole record in 128-lane tiles."""
    assert shape[0] == paper_config().capacity
    arr = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = shuffled_items.lower(arr, first, n_items=1 << 18,
                                    interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("capacity", [1 << 23, 1 << 25])
def test_species_histograms_stay_in_blocks(one_chip, capacity):
    """One species' speed and energy histograms at a chip's share of the
    paper problem (2^23) and at paper_config (2^25) take under 64 MiB of
    temp memory: no [C, 65] intermediate, and at 2^25 nothing of the
    particle axis' length (128 MiB in float32)."""
    v = jax.ShapeDtypeStruct((capacity, 3), jnp.float32, sharding=one_chip)
    f = jax.ShapeDtypeStruct((capacity,), jnp.float32, sharding=one_chip)
    compiled = species_histograms.lower(
        v, f, f, mass=1836.0, bins=64, v_range=(0.0, 5.0),
        e_range=(0.0, 10.0)).compile()
    assert "jit_species_histograms" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
