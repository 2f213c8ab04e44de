"""Per-kernel shape/dtype sweeps: pallas_call (interpret mode) vs the
pure-jnp oracle in kernels/ref.py.  Tile-first and staged ingest are two
programs, compared under the cross-program contract (docs/api.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiling
from repro.core.detect import CROSS_PROGRAM_LOGIT_ATOL
from repro.kernels.fused_preprocess import fused_preprocess
from repro.kernels.fused_tile_preprocess import fused_tile_preprocess
from repro.kernels import ref as kref


@pytest.mark.parametrize("H,W,resize,crop", [
    (256, 256, 256, 256),
    (512, 512, 288, 256),
    (300, 400, 256, 224),
    (64, 64, 48, 32),
    (128, 96, 80, 64),
])
def test_fused_preprocess_shapes(H, W, resize, crop):
    rng = np.random.default_rng(0)
    raw = jnp.asarray(rng.integers(0, 256, (2, H, W, 3), dtype=np.uint8))
    out = fused_preprocess(raw, resize=resize, crop=crop, interpret=True)
    ref = kref.fused_preprocess_ref(raw, resize=resize, crop=crop)
    assert out.shape == (2, crop, crop, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_fused_preprocess_dtypes(dtype):
    rng = np.random.default_rng(1)
    if dtype == np.uint8:
        raw = rng.integers(0, 256, (3, 96, 96, 3), dtype=np.uint8)
    else:
        raw = rng.uniform(0, 255, (3, 96, 96, 3)).astype(np.float32)
    out = fused_preprocess(jnp.asarray(raw), resize=64, crop=48,
                           interpret=True)
    ref = kref.fused_preprocess_ref(jnp.asarray(raw), resize=64, crop=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=1e-4)


def test_fused_preprocess_custom_stats():
    rng = np.random.default_rng(2)
    raw = jnp.asarray(rng.integers(0, 256, (1, 80, 80, 3), dtype=np.uint8))
    mean = np.array([0.5, 0.5, 0.5], np.float32)
    std = np.array([0.5, 0.5, 0.5], np.float32)
    out = fused_preprocess(raw, resize=80, crop=80, mean=mean, std=std,
                           interpret=True)
    ref = kref.fused_preprocess_ref(raw, resize=80, crop=80, mean=mean,
                                    std=std)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)


# ---------------------------------------------------------------------------
# tile-first fused ingest kernel
# ---------------------------------------------------------------------------


def _tile_geometry(tile):
    """(crop, resize, raw) for a tile size — crop = 2x2 grid of tiles."""
    crop = 2 * tile
    return crop, crop + max(tile // 4, 8), crop + 32


# tile-first vs staged ingest: the same interpolation sums in two
# programs, so the pixels may differ by float reassociation only
# (normalised pixels are O(1); a few ulp)
INGEST_ATOL = 1e-5


@pytest.mark.parametrize("strategy", tiling.STRATEGIES)
@pytest.mark.parametrize("tile", [32, 64, 128])
def test_fused_tile_preprocess_bit_exact_vs_staged(strategy, tile):
    """The tentpole contract: slicing the interpolation matrices before
    the matmuls == slicing the full preprocessed image after them, for
    every strategy and tile size, up to float reassociation between the
    two programs."""
    crop, resize, raw_hw = _tile_geometry(tile)
    rng = np.random.default_rng(tile)
    raw = jnp.asarray(rng.integers(0, 256, (2, raw_hw, raw_hw, 3),
                                   dtype=np.uint8))
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(7), i))(
        jnp.arange(2))
    offs = tiling.tile_first_offsets(strategy, keys, img_size=crop,
                                     tile=tile)
    out = fused_tile_preprocess(raw, offs, resize=resize, crop=crop,
                                tile=tile, interpret=True)
    full = fused_preprocess(raw, resize=resize, crop=crop, interpret=True)
    staged = tiling.extract_tiles(full, offs, tile)
    assert out.shape == (2, tile, tile, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(staged),
                               rtol=0, atol=INGEST_ATOL)


@pytest.mark.parametrize("b", [1, 3])
def test_fused_tile_preprocess_ragged_batches(b):
    rng = np.random.default_rng(b)
    raw = jnp.asarray(rng.integers(0, 256, (b, 96, 96, 3),
                                   dtype=np.uint8))
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(b), i))(
        jnp.arange(b))
    offs = tiling.tile_first_offsets("random_grid", keys, img_size=64,
                                     tile=32)
    out = fused_tile_preprocess(raw, offs, resize=72, crop=64, tile=32,
                                interpret=True)
    full = fused_preprocess(raw, resize=72, crop=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(tiling.extract_tiles(full, offs, 32)),
        rtol=0, atol=INGEST_ATOL)


def test_fused_tile_preprocess_matches_oracle():
    """allclose against the jnp oracle (jax.image.resize + slice)."""
    rng = np.random.default_rng(11)
    raw = jnp.asarray(rng.integers(0, 256, (3, 128, 96, 3),
                                   dtype=np.uint8))
    offs = jnp.asarray([[0, 0], [16, 48], [48, 16]], jnp.int32)
    out = fused_tile_preprocess(raw, offs, resize=80, crop=64, tile=16,
                                interpret=True)
    ref = kref.fused_tile_preprocess_ref(raw, offs, resize=80, crop=64,
                                         tile=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=1e-4)


def test_fused_tile_preprocess_logits_bit_exact():
    """End of the ingest contract: the extractor's logits on tile-first
    tiles match those on staged preprocess -> select_tiles_per_image
    (same hard bits, logits within the cross-program tolerance)."""
    from repro.core.extractor import extractor_forward, init_extractor
    rng = np.random.default_rng(5)
    raw = jnp.asarray(rng.integers(0, 256, (3, 96, 96, 3),
                                   dtype=np.uint8))
    params = init_extractor(jax.random.key(1), n_bits=12, channels=4,
                            depth=1)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(2), i))(
        jnp.arange(3))
    offs = tiling.tile_first_offsets("random_grid", keys, img_size=64,
                                     tile=32)
    tiles_tf = fused_tile_preprocess(raw, offs, resize=72, crop=64,
                                     tile=32, interpret=True)
    full = fused_preprocess(raw, resize=72, crop=64, interpret=True)
    tiles_staged, offs2 = tiling.select_tiles_per_image(
        "random_grid", keys, full, 32)
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(offs2))
    a = np.asarray(extractor_forward(params, tiles_tf))
    b = np.asarray(extractor_forward(params, tiles_staged))
    np.testing.assert_array_equal(a > 0, b > 0)
    np.testing.assert_allclose(a, b, rtol=0, atol=CROSS_PROGRAM_LOGIT_ATOL)


def test_resize_matrix_matches_jax_image():
    """The interpolation-matrix trick must equal jax.image bilinear."""
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 1, (40, 7)).astype(np.float32)
    M = kref.resize_matrix(40, 28)
    ref = jax.image.resize(jnp.asarray(x), (28, 7), method="bilinear",
                           antialias=False)
    np.testing.assert_allclose(M @ x, np.asarray(ref), atol=1e-5)
