"""AOT compiles of the main path's device programs for a described v5e.

Nothing runs here: the TPU compiler that ships with jax compiles for a
chip that is described, not attached (`jax.experimental.topologies`),
and refuses what the chip's compiler would refuse — misaligned Pallas
tiles, over-budget VMEM, shard_map programs the installed jax rejects.
The interpret-mode and CPU-mesh tests cannot see any of that.

The topology is described inside a module fixture, never at import:
only one process may load libtpu at a time, and xdist workers all
import this file. Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache off around
    # these compiles so nothing warns or writes.
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any describe failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _words(B, C, sharding):
    return (jax.ShapeDtypeStruct((B, C, 256), jnp.uint32, sharding=sharding),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sharding))


def test_pallas_words_compiles_at_identify_step(topo):
    """The identifier's device step: AUTO_DEVICE_BATCH large-file rows
    on the 57-chunk CAS grid, through the Pallas chunk-stage kernel."""
    from spacedrive_tpu.ops.blake3_jax import LARGE_CHUNKS
    from spacedrive_tpu.ops.blake3_pallas import blake3_words_pallas
    from spacedrive_tpu.ops.staging import AUTO_DEVICE_BATCH

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = blake3_words_pallas.__wrapped__.lower(
        *_words(AUTO_DEVICE_BATCH, LARGE_CHUNKS, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_cas_compiles_on_four_chips(topo, monkeypatch):
    """sharded_hasher's program (Pallas body under shard_map) on the
    4-chip mesh — the installed shard_map refuses it unless the Pallas
    out_shape's varying axes are not checked."""
    from spacedrive_tpu.ops import blake3_pallas
    from spacedrive_tpu.ops.blake3_jax import LARGE_CHUNKS, make_sharded_blake3
    from spacedrive_tpu.parallel.mesh import batch_mesh

    mesh = batch_mesh(topo.devices)
    sharded = NamedSharding(mesh, P("data"))
    # The per-shard body picks its kernel from the default backend (the
    # CPU here); steer it to the chip's kernel inside this test only.
    monkeypatch.setattr(blake3_pallas, "supported", lambda: True)
    compiled = make_sharded_blake3(mesh).__wrapped__.lower(
        *_words(4 * 2048, LARGE_CHUNKS, sharded)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_hamming_compiles_on_tile_mesh(topo):
    """make_sharded_hamming over the 2x2 (rows, cols) tile mesh."""
    from spacedrive_tpu.ops.hamming import make_sharded_hamming

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("rows", "cols"))
    rows = jax.ShapeDtypeStruct((4096, 2), jnp.uint32,
                                sharding=NamedSharding(mesh, P("rows", None)))
    cols = jax.ShapeDtypeStruct((4096, 2), jnp.uint32,
                                sharding=NamedSharding(mesh, P("cols", None)))
    compiled = make_sharded_hamming(mesh).__wrapped__.lower(
        rows, cols).compile()
    assert compiled.memory_analysis() is not None
