"""Pallas TPU kernel: tile-first fused Resize -> Crop -> Normalize ->
Tile-extract.

The staged ingest (``fused_preprocess.py``) resizes/normalises the FULL
image even though the qrmark decode stage reads exactly one l x l tile of
it — at the default 256^2 image / 64^2 tile that is ~16x more output (and
>4x more MXU FLOPs) than the pipeline ever consumes.  This kernel makes
the *selected tile* the unit of ingest work: because the staged transform
is two interpolation matmuls per channel,

    full[c] = scale_c * (Ry @ img[:, :, c] @ Rx) + bias_c,

the (y, x) tile of the output only needs rows [y, y+l) of ``Ry`` and
columns [x, x+l) of ``Rx`` — output row i depends on nothing but row i of
``Ry``, so slicing the interpolation matrices *before* the matmuls yields the
same values as slicing the full preprocessed image after them (up to
float reassociation by the compiler),
while shrinking the per-image FLOPs from

    3 * (crop*H*W + crop*W*crop)   to   3 * (l*H*W + l*W*l).

Per-image tile offsets (already derived from per-image fold_in keys by
``tiling.per_image_offsets``, so they are available *before* ingest) are
applied as a vmapped ``dynamic_slice`` over the shared (crop, H)/(W, crop)
matrices on the way into the kernel; the kernel itself is two small MXU
matmuls per channel per grid step on a planar (3, H, W) image block
(layout notes in ``fused_preprocess.py``) and yields the (b, l, l, 3)
decode input directly — the full preprocessed image is never materialised.

Multi-tile escalation form: offsets may also be (b, k, 2) — k tiles per
image (``tiling.escalation_offsets`` plans).  The grid becomes b*k steps
whose image block index is ``step // k``, so each raw image is read k
times from its single HBM copy (never replicated host-side) and the
kernel emits (b*k, l, l, 3) tile-major per image — escalated tiles ride
exactly the same MXU path as the single-tile hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_preprocess import (affine_constants, from_planar,
                                            interp_affine, interp_matrices,
                                            load_planar, to_planar)


def _kernel(img_ref, ry_ref, rx_ref, out_ref, *, scale, bias):
    # ry (tile, H) / rx (W, tile) are this image's pre-sliced matrices;
    # the math is the staged kernel's interp_affine, shared verbatim
    outs = interp_affine(load_planar(img_ref), ry_ref[...], rx_ref[...],
                         scale, bias)
    for c in range(3):
        out_ref[c] = outs[c]


def slice_interp_matrices(offsets, *, H: int, W: int, resize: int,
                          crop: int, tile: int):
    """Per-image (tile, H) row / (W, tile) column slices of the shared
    interpolation matrices at the given (b, 2) int32 tile offsets
    (offsets live in the cropped image's coordinate space)."""
    ry, rx = interp_matrices(H, W, resize=resize, crop=crop)

    def one(o):
        return (jax.lax.dynamic_slice(ry, (o[0], 0), (tile, H)),
                jax.lax.dynamic_slice(rx, (0, o[1]), (W, tile)))

    return jax.vmap(one)(offsets.astype(jnp.int32))


def fused_tile_preprocess(raw, offsets, *, resize: int = 256,
                          crop: int = 256, tile: int = 64,
                          mean=None, std=None, interpret: bool = True):
    """uint8 (b, H, W, 3) + tile offsets -> f32 tiles.

    ``offsets`` is (b, 2) — one tile per image, output
    (b, tile, tile, 3) — or (b, k, 2) — a k-tile escalation plan per
    image, output (b*k, tile, tile, 3) flattened image-major (rows
    [i*k, (i+1)*k) are image i's tiles).  Each output tile equals
    ``extract_tiles(fused_preprocess(raw), <its offset>, tile)`` up to
    float reassociation, without materialising the (b, crop, crop, 3)
    intermediate; the multi-tile grid reads each raw image block k
    times rather than replicating it.  The kernel reads planar
    (3, H, W) image blocks and writes planar (3, tile, tile) tiles.
    interpret=True executes on CPU; interpret=False compiles for the
    TPU.  Not jitted here: callers jit around it (the interpolation
    matrices are host constants).
    """
    b, H, W, C = raw.shape
    assert C == 3
    assert tile <= crop, f"tile {tile} exceeds crop {crop}"
    offsets = jnp.asarray(offsets, jnp.int32)
    k = offsets.shape[1] if offsets.ndim == 3 else 1
    n = b * k
    ry_t, rx_t = slice_interp_matrices(
        offsets.reshape(n, 2), H=H, W=W, resize=resize, crop=crop,
        tile=tile)
    scale, bias = affine_constants(mean, std)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bias=bias),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, 3, H, W), lambda i: (i // k, 0, 0, 0)),
            pl.BlockSpec((None, tile, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, W, tile), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 3, tile, tile),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 3, tile, tile), jnp.float32),
        interpret=interpret,
        name="fused_tile_preprocess",
    )(to_planar(raw), ry_t, rx_t)
    return from_planar(out)
