"""Pallas TPU kernel: fused Resize -> CenterCrop -> Normalize.

QRMark Appendix B.1 fuses the fragmented preprocess ops into one Triton
kernel to kill launch overhead and intermediate HBM round-trips.  The TPU
adaptation changes the *algorithm*, not just the API: bilinear resampling
is a gather on GPU, but gathers are slow on the TPU vector unit — instead
the (static) resize+crop composition is expressed as two small
interpolation MATRICES so the whole transform runs on the MXU:

    out[c] = scale_c * (Ry @ img[:, :, c] @ Rx) + bias_c

Ry (crop, H) and Rx (W, crop) each carry <= 2 nonzeros/row (bilinear
weights with half-pixel centers and edge clamp); normalisation folds into
a per-channel affine (scale = 1/(255*std), bias = -mean/std) whose six
floats are baked into the kernel body.

Layout: the kernel reads a planar (3, H, W) block per grid step and
writes planar (3, crop, crop) output; ``to_planar``/``from_planar`` run
the transposes in XLA on either side.  A channels-last (H, W, 3) block
would put the three channels on the 128-lane axis, padding every vector
register 42x and forcing lane slices per channel; the TPU compiler also
has no uint8 -> f32 cast, so ``load_planar`` widens through int32.  At
the default geometry one step holds a uint8 (3, 288, 288) block
(~330 KB with lane padding), its f32 copy and a (3, 256, 256) f32 output
(~790 KB) — a few MB with double buffering, far inside the 128 MiB of
VMEM the v5e compiler reports.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.transforms import IMAGENET_MEAN, IMAGENET_STD
from repro.kernels.ref import resize_matrix


def interp_affine(img, ry, rx, scale, bias):
    """The shared kernel math: per-channel Ry @ img @ Rx + affine
    normalise.  Both the staged and the tile-first kernels
    (``fused_tile_preprocess.py``) call this — one body, so the two
    ingest paths cannot drift apart.

    img (3, H, W) f32 planar; ry (rows, H); rx (W, cols); scale/bias
    are three host floats each -> list of three (rows, cols) planes.
    The dots run at full fp32 precision: the interpolation weights are
    not bf16-exact in general.
    """
    hi = jax.lax.Precision.HIGHEST
    outs = []
    for c in range(3):  # channels unrolled: 2 MXU matmuls per channel
        t = jnp.dot(ry, img[c], precision=hi,
                    preferred_element_type=jnp.float32)
        t = jnp.dot(t, rx, precision=hi,
                    preferred_element_type=jnp.float32)
        outs.append(t * scale[c] + bias[c])
    return outs


def load_planar(img_ref):
    """The (3, H, W) raw block as f32.  uint8 goes through int32: the
    TPU compiler has no direct uint8 -> f32 cast."""
    img = img_ref[...]
    if img.dtype == jnp.uint8:
        img = img.astype(jnp.int32)
    return img.astype(jnp.float32)


def affine_constants(mean, std):
    """Per-channel (scale, bias) host floats of the normalisation
    ``(x / 255 - mean) / std`` — baked into the kernel body, so no
    1-D operand reaches the kernel."""
    mean = np.asarray(IMAGENET_MEAN if mean is None else mean, np.float32)
    std = np.asarray(IMAGENET_STD if std is None else std, np.float32)
    scale = (np.float32(1.0) / (np.float32(255.0) * std)).astype(np.float32)
    bias = (-mean / std).astype(np.float32)
    return tuple(float(v) for v in scale), tuple(float(v) for v in bias)


def to_planar(raw):
    """(b, H, W, 3) -> (b, 3, H, W): the kernels' input layout, with
    channels off the lane axis."""
    return jnp.transpose(raw, (0, 3, 1, 2))


def from_planar(x):
    """(n, 3, h, w) kernel output -> the (n, h, w, 3) channels-last
    layout the decode stage reads."""
    return jnp.transpose(x, (0, 2, 3, 1))


def interp_matrices(H: int, W: int, *, resize: int, crop: int):
    """The (crop, H) row / (W, crop) column interpolation matrices of
    the resize+centercrop composition (host constants)."""
    off = (resize - crop) // 2
    ry = jnp.asarray(resize_matrix(H, resize, off, crop))          # (crop,H)
    rx = jnp.asarray(resize_matrix(W, resize, off, crop).T)        # (W,crop)
    return ry, rx


def _kernel(img_ref, ry_ref, rx_ref, out_ref, *, scale, bias):
    outs = interp_affine(load_planar(img_ref), ry_ref[...], rx_ref[...],
                         scale, bias)
    for c in range(3):
        out_ref[c] = outs[c]


def fused_preprocess(raw, *, resize: int = 256, crop: int = 256,
                     mean=None, std=None, interpret: bool = True):
    """uint8 (b, H, W, 3) -> normalized f32 (b, crop, crop, 3).

    The kernel reads and writes planar (b, 3, ., .) blocks; the
    transposes on either side run in XLA.  interpret=True executes the
    kernel body on CPU; interpret=False compiles it for the TPU.  Not
    jitted here: mean/std and the interpolation matrices are host
    constants; callers jit around it.
    """
    b, H, W, C = raw.shape
    assert C == 3
    ry, rx = interp_matrices(H, W, resize=resize, crop=crop)
    scale, bias = affine_constants(mean, std)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bias=bias),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, 3, H, W), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((crop, H), lambda i: (0, 0)),
            pl.BlockSpec((W, crop), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 3, crop, crop),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 3, crop, crop), jnp.float32),
        interpret=interpret,
        name="fused_preprocess",
    )(to_planar(raw), ry, rx)
    return from_planar(out)
