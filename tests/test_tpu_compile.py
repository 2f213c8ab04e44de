"""The main-path Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, which catches what interpret mode cannot (block shapes the
compiler refuses, casts it lacks, VMEM overflow).  Shapes are the
``DetectionConfig`` defaults — raw 288^2 cropped to 256^2, tile 64,
RS(15,12) over GF(16), an extractor of 64 channels with 60 code bits and
its correlation bank — except the extractor's depth: 2 instead of 7.
The last test compiles ``run_batch``'s sharded program for all four
described chips.
Blocks 1..D-1 share one loop body in the decode kernel, so depth only
adds a loop trip, and depth 2 still compiles the loop.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the tests run
under several workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.extractor import init_extractor, pack_params
from repro.kernels.fused_extractor import fused_extractor
from repro.kernels.fused_preprocess import fused_preprocess
from repro.kernels.fused_tile_preprocess import fused_tile_preprocess
from repro.kernels.rs_decode import rs_decode_batch

B, RAW, CROP, TILE, CHANNELS, DEPTH, N_BITS = 8, 288, 256, 64, 64, 2, 60


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tile_first_ingest_compiles(one_chip):
    txt = _compiled_text(
        lambda r, o: fused_tile_preprocess(r, o, resize=RAW, crop=CROP,
                                           tile=TILE, interpret=False),
        _spec(one_chip, (B, RAW, RAW, 3), jnp.uint8),
        _spec(one_chip, (B, 2), jnp.int32))
    assert "tpu_custom_call" in txt


def test_staged_ingest_compiles(one_chip):
    txt = _compiled_text(
        lambda r: fused_preprocess(r, resize=RAW, crop=CROP,
                                   interpret=False),
        _spec(one_chip, (B, RAW, RAW, 3), jnp.uint8))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_flat_decode_with_correlation_bank_compiles(one_chip, dtype):
    params = init_extractor(jax.random.key(0), n_bits=N_BITS,
                            channels=CHANNELS, depth=DEPTH, tile=TILE)
    packed = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          pack_params(params, dtype))
    txt = _compiled_text(
        lambda t, p: fused_extractor(t, p, interpret=False),
        _spec(one_chip, (B, TILE, TILE, 3), jnp.float32), packed)
    assert "tpu_custom_call" in txt


def test_rs_kernel_compiles(one_chip):
    txt = _compiled_text(
        lambda b: rs_decode_batch(b, interpret=False),
        _spec(one_chip, (B, N_BITS), jnp.int32))
    assert "tpu_custom_call" in txt


def test_sharded_round_compiles_without_collectives(topo, monkeypatch):
    """``run_batch``'s program over a 2x2 mesh: Mosaic kernels cannot be
    partitioned automatically, so each device runs the stages on its
    shard inside ``shard_map``; nothing crosses devices."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.detect import DetectionConfig
    from repro.core.stages import StageRegistry
    from repro.kernels import ops

    # the default backend here is the CPU: steer the kernels to compile
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    data = NamedSharding(mesh, PartitionSpec("data"))
    params = init_extractor(jax.random.key(0), n_bits=N_BITS,
                            channels=CHANNELS, depth=DEPTH, tile=TILE)
    reg = StageRegistry(DetectionConfig(), params)
    txt = reg.sharded_round(mesh).lower(
        _spec(data, (B, RAW, RAW, 3), jnp.uint8),
        _spec(data, (B,), jax.random.key(0).dtype)).compile().as_text()
    assert txt.count("tpu_custom_call") == 3   # ingest, decode, RS
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in txt
