"""Seeded weights and images, made on the device in one jitted call each.

Weights follow a configuration's ``extractor`` block: He-normal 3x3
conv weights, zero biases, a dense head scaled by ``head_scale`` (an
untrained conv path then adds noise to each logit without flipping
bits), and a bank of unit-norm, zero-mean white patterns, one per
codeword bit, which the correlation path reads.

Images are a pool of procedural RGB pictures (sinusoid gradients, six
soft rectangles, pixel noise), the same kind of content as the
project's synthetic images.  A key of ``message_bits`` random bits is
RS-encoded and embedded into every tile of the centre crop's grid as
the spread-spectrum sum of the bank's patterns, at an RMS of
``embed_rms`` on the [-1, 1] pixel scale; a tile-first decoder then
reads the key back from any tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


SEED_USES = ("tiles", "weights", "pool", "traffic", "sample", "warm")


def sub_seeds(seed: int) -> dict:
    """One uint32 word per use, from the run's seed (any size of
    integer, so large seeds are taken whole)."""
    words = np.random.SeedSequence(int(seed)).generate_state(len(SEED_USES))
    return {u: int(w) for u, w in zip(SEED_USES, words)}


def _he(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(
        2.0 / (shape[0] * shape[1] * shape[2]))


@functools.partial(jax.jit, static_argnames=(
    "channels", "depth", "n_bits", "tile", "head_scale"))
def make_params(key, *, channels: int, depth: int, n_bits: int, tile: int,
                head_scale: float):
    ks = jax.random.split(key, depth + 3)
    blocks, cin = [], 3
    for i in range(depth):
        blocks.append({"w": _he(ks[i], (3, 3, cin, channels)),
                       "b": jnp.zeros((channels,), jnp.float32)})
        cin = channels
    bank = jax.random.normal(ks[depth + 2], (n_bits, tile, tile, 3),
                             jnp.float32)
    bank = bank - bank.mean(axis=(1, 2, 3), keepdims=True)
    bank = bank / jnp.sqrt(jnp.sum(bank * bank, axis=(1, 2, 3),
                                   keepdims=True))
    return {
        "blocks": blocks,
        "to_bits": {"w": _he(ks[depth], (3, 3, channels, n_bits)),
                    "b": jnp.zeros((n_bits,), jnp.float32)},
        "head": {"w": 0.2 * head_scale * jax.random.normal(
            ks[depth + 1], (n_bits, n_bits), jnp.float32),
            "b": jnp.zeros((n_bits,), jnp.float32)},
        "corr": bank,
        "corr_scale": jnp.ones((n_bits,), jnp.float32),
    }


def _picture(key, size: int):
    """One procedural picture, float32 in [0, 1], (size, size, 3)."""
    k_wave, k_rect, k_noise = jax.random.split(key, 3)
    t = jnp.linspace(0.0, 1.0, size)
    yy, xx = jnp.meshgrid(t, t, indexing="ij")
    abp = jax.random.uniform(k_wave, (3, 3), minval=1.0, maxval=6.0)
    img = 0.5 + 0.25 * jnp.sin(
        2 * jnp.pi * (abp[:, 0, None, None] * yy + abp[:, 1, None, None]
                      * xx) + abp[:, 2, None, None])
    img = img.transpose(1, 2, 0)
    ix = jnp.arange(size)
    for kr in jax.random.split(k_rect, 6):
        k0, k1, k2, k3 = jax.random.split(kr, 4)
        y0, x0 = jax.random.randint(k0, (2,), 0, size - 8)
        h, w = jax.random.randint(k1, (2,), 8, size // 2)
        col = jax.random.uniform(k2, (3,))
        alpha = jax.random.uniform(k3, (), minval=0.2, maxval=0.7)
        m = (((ix >= y0) & (ix < y0 + h))[:, None]
             & ((ix >= x0) & (ix < x0 + w))[None, :])[..., None]
        img = jnp.where(m, (1 - alpha) * img + alpha * col, img)
    img = img + 0.02 * jax.random.normal(k_noise, img.shape)
    return jnp.clip(img, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=(
    "n", "size", "crop", "tile", "embed_rms"))
def make_pool(key, bank, codeword, *, n: int, size: int, crop: int,
              tile: int, embed_rms: float):
    """(n, size, size, 3) uint8 pictures carrying ``codeword`` (0/1 of
    the bank's length) on every tile of the centre crop."""
    pics = jax.vmap(lambda k: _picture(k, size))(jax.random.split(key, n))
    x = jnp.round(pics * 255.0) / 127.5 - 1.0          # [-1, 1]
    sign = 2.0 * codeword.astype(jnp.float32) - 1.0
    delta = jnp.einsum("n,nhwc->hwc", sign, bank)
    delta = delta * embed_rms / jnp.sqrt(jnp.mean(delta * delta))
    g = crop // tile
    off = (size - crop) // 2
    mark = jnp.zeros((size, size, 3), jnp.float32)
    mark = mark.at[off: off + g * tile, off: off + g * tile].set(
        jnp.tile(delta, (g, g, 1)))
    x = jnp.clip(x + mark[None], -1.0, 1.0)
    return jnp.clip(jnp.round((x + 1.0) * 127.5), 0, 255).astype(jnp.uint8)
