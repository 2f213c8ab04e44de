"""The plain float32 reference that decides ``correct``.

It restates, in straightforward ``jax.numpy`` and numpy, what a
configuration says the detection system computes, and imports nothing
of the system under test:

* keys: image ``j`` of key index ``s`` uses
  ``fold_in(fold_in(key(seed), s), j)``;
* tile choice (``random_grid``): one grid cell drawn uniformly from the
  image's key;
* ingest: uint8 -> [0, 1] -> bilinear resize (half-pixel centres, no
  antialias) -> centre crop -> ImageNet mean/std;
* decode: 3x3 SAME conv blocks (conv + bias, norm over channels, ReLU),
  the 3x3 ``to_bits`` conv, global average pool, dense head; plus, where
  the tile matches the correlation bank, the bank's correlation with the
  3x3 high-pass of the tile;
* Reed-Solomon: the systematic evaluation code over GF(2^m) on the
  points alpha^0..alpha^(n-1), unique decoding up to t symbol errors
  (syndromes of the dual code); a word that does not decode keeps its
  received message symbols and ``ok`` false.

Run it under ``jax.default_matmul_precision("highest")``: a float32 dot on
a TPU runs in bfloat16 passes otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)

# primitive polynomials of GF(2^m), with the x^m term
_PRIM_POLY = {4: 0b10011, 8: 0b100011101}


# -- keys and tiles ----------------------------------------------------------


def image_keys(seed: int, index: np.ndarray, pos: np.ndarray):
    """Per-image keys fold_in(fold_in(key(seed), index), pos)."""
    base = jax.random.key(np.uint32(seed))
    return jax.vmap(lambda s, j: jax.random.fold_in(
        jax.random.fold_in(base, s), j))(
            jnp.asarray(index, jnp.uint32), jnp.asarray(pos, jnp.uint32))


def grid_offsets(keys, *, img: int, tile: int):
    """random_grid: (b, 2) int32 (y, x) of one uniformly drawn cell."""
    g = img // tile

    def one(k):
        c = jax.random.randint(k, (), 0, g * g)
        return jnp.stack([c // g, c % g]) * tile

    return jax.vmap(one)(keys).astype(jnp.int32)


# -- ingest and decode -------------------------------------------------------


def preprocess(raw, *, resize: int, crop: int):
    """uint8 (b, H, W, 3) -> normalised float32 (b, crop, crop, 3)."""
    b = raw.shape[0]
    x = raw.astype(jnp.float32) / 255.0
    x = jax.image.resize(x, (b, resize, resize, 3), method="bilinear",
                         antialias=False)
    y0 = (resize - crop) // 2
    x = x[:, y0: y0 + crop, y0: y0 + crop, :]
    return (x - IMAGENET_MEAN) / IMAGENET_STD


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def decode(params, x):
    """(b, h, w, 3) decode inputs -> (b, n_bits) logits: the conv path,
    plus the bank's correlation where ``x`` is tile-sized."""
    h = x
    for blk in params["blocks"]:
        y = _conv(h, blk["w"]) + blk["b"]
        mu = y.mean(axis=-1, keepdims=True)
        var = y.var(axis=-1, keepdims=True)
        h = jax.nn.relu((y - mu) * jax.lax.rsqrt(var + 1e-5))
    y = _conv(h, params["to_bits"]["w"]) + params["to_bits"]["b"]
    logits = y.mean(axis=(1, 2)) @ params["head"]["w"] + params["head"]["b"]
    bank = params.get("corr")
    if bank is not None and bank.shape[1:3] == x.shape[1:3]:
        box = jnp.ones((3, 3, 1, 3), jnp.float32) / 9.0
        blur = jax.lax.conv_general_dilated(
            x, box, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=3)
        logits = logits + jnp.einsum("bhwc,nhwc->bn", x - blur, bank) \
            * params["corr_scale"]
    return logits


@functools.partial(jax.jit, static_argnames=("resize", "crop", "tile"))
def tile_logits(params, raw, keys, *, resize: int, crop: int, tile: int):
    """One tile per image, chosen by its key (tile-first decoding)."""
    x = preprocess(raw, resize=resize, crop=crop)
    offs = grid_offsets(keys, img=crop, tile=tile)
    tiles = jax.vmap(lambda im, o: jax.lax.dynamic_slice(
        im, (o[0], o[1], 0), (tile, tile, 3)))(x, offs)
    return decode(params, tiles)


@functools.partial(jax.jit, static_argnames=("resize", "crop"))
def image_logits(params, raw, *, resize: int, crop: int):
    """The whole cropped image (the sequential baseline)."""
    return decode(params, preprocess(raw, resize=resize, crop=crop))


# -- Reed-Solomon over GF(2^m) -----------------------------------------------


class RS:
    """Systematic evaluation-based RS(n, k) over GF(2^m)."""

    def __init__(self, m: int, n: int, k: int):
        self.m, self.n, self.k, self.t = m, n, k, (n - k) // 2
        if self.t > 1:
            raise NotImplementedError("the reference decodes t <= 1 only")
        q = 1 << m
        exp = np.zeros(2 * (q - 1), np.int64)
        log = np.zeros(q, np.int64)
        v = 1
        for i in range(q - 1):
            exp[i], log[v] = v, i
            v <<= 1
            if v & q:
                v ^= _PRIM_POLY[m]
        exp[q - 1:] = exp[: q - 1]
        a = np.arange(q)
        mul = exp[(log[:, None] + log[None, :])]
        mul[(a[:, None] == 0) | (a[None, :] == 0)] = 0
        self.mul = mul                       # (q, q) table
        self.inv = np.zeros(q, np.int64)
        self.inv[1:] = exp[(q - 1 - log[1:]) % (q - 1)]
        xs = exp[:n]
        # generator: row j is the codeword of the j-th unit message,
        # i.e. the Lagrange basis of the first k points, evaluated
        g = np.zeros((k, n), np.int64)
        for j in range(k):
            for i in range(n):
                num = den = 1
                for l_ in range(k):
                    if l_ != j:
                        num = mul[num, xs[i] ^ xs[l_]]
                        den = mul[den, xs[j] ^ xs[l_]]
                g[j, i] = mul[num, self.inv[den]]
        self.g = g
        # parity check: the dual of an evaluation code on all n points
        # is generalised RS with multipliers 1 / prod_{l != i}(x_i - x_l)
        h = np.zeros((n - k, n), np.int64)
        for i in range(n):
            d = 1
            for l_ in range(n):
                if l_ != i:
                    d = mul[d, xs[i] ^ xs[l_]]
            vi, p = self.inv[d], 1
            for r in range(n - k):
                h[r, i] = mul[vi, p]
                p = mul[p, xs[i]]
        self.h = h
        if self._dot(g, h.T).any():
            raise AssertionError("RS parity check is not dual to G")

    def _dot(self, a, b):
        """GF matrix product (r, c) x (c, s)."""
        out = np.zeros((a.shape[0], b.shape[1]), np.int64)
        for c in range(a.shape[1]):
            out ^= self.mul[a[:, c][:, None], b[c][None, :]]
        return out

    def to_symbols(self, bits):
        bits = np.asarray(bits, np.int64).reshape(len(bits), -1, self.m)
        return bits @ (1 << np.arange(self.m - 1, -1, -1))

    def to_bits(self, sym):
        sh = np.arange(self.m - 1, -1, -1)
        return ((np.asarray(sym)[..., None] >> sh) & 1).reshape(
            len(sym), -1).astype(np.int32)

    def encode(self, message_bits) -> np.ndarray:
        """(k*m,) bits -> (n*m,) codeword bits."""
        msg = self.to_symbols(np.asarray(message_bits)[None])
        return self.to_bits(self._dot(msg, self.g))[0]

    def decode(self, bits):
        """(N, n*m) received bits -> (message bits (N, k*m), ok (N,))."""
        r = self.to_symbols(bits).copy()
        s = self._dot(r, self.h.T)                  # (N, n-k) syndromes
        ok = ~s.any(axis=1)
        # one symbol error of value v at e gives the syndromes v * h[:, e]
        for e in range(self.n if self.t else 0):
            v = self.mul[s[:, 0], self.inv[self.h[0, e]]]
            hit = (~ok) & (v != 0) & np.all(
                s == self.mul[v[:, None], self.h[:, e][None, :]], axis=1)
            r[hit, e] ^= v[hit]
            ok = ok | hit
        return self.to_bits(r[:, : self.k]), ok
