"""Pallas TPU kernels: the fused extractor decode stage.

Decode — the extractor's conv stack, GAP + head — runs as one
``pallas_call`` per tile batch, so conv activations never round-trip
HBM (QRMark §5.2 names this stage the accelerator-bound one).  The
spread-spectrum correlation bank is one batched MXU dot after the
kernel (``extractor.correlate_packed``), and the two are added the way
the unfused graph adds them.  Two kernels compute the same math:

``fused_extractor`` (the *flat* schedule, the only one that compiles
for TPU) — grid=(b,), one image per step.  The activation is a
flattened (l*l, C) array with channels on lanes, held in VMEM between
zero halo rows; a 3x3 tap is a window of it (details in the function's
docstring).  The conv runs in row chunks under ``fori_loop`` and blocks
1..D-1 run as one loop over stacked weights, so the compiled kernel
holds one layer body.

``fused_extractor_blocked`` (the *blocked* schedule) — grid=(b //
batch_block,) over (bb, l, l, 3) blocks:

* a padded-activation VMEM scratch (bb, l+2, l+2, C) holds every
  inter-layer activation with its halo in place, so layers read their
  nine tap-shifted views as scratch slices;
* a (bb*l*l, C) accumulator scratch collects the conv output one
  channel tile at a time (N-restricted tap dots); channel_norm couples
  all C channels of a layer, so the tile is an in-body loop, not a grid
  dimension;
* the bias + channel-norm + ReLU epilogue runs on the (M, C) GEMM
  layout, then GAP + head end the step.

It keeps channels-last 4-D blocks and does not compile for TPU;
``StageRegistry`` refuses it there.

The precision ladder is carried by the packed params, not the kernel:
fp32 packs run full-precision dots (``Precision.HIGHEST``), bf16 packs
bf16-input MXU dots with fp32 accumulation, int8 packs
(``pack_params(..., "int8")``) per-channel-scaled int8 weight x per-row
quantized activation dots with int32 accumulation and fp32 dequantize —
all through the per-tap ``tap_dot`` primitive.  Results of the two
kernels and of the unfused graph agree under the cross-program contract
(``docs/api.md``).  interpret=True executes on the CPU; interpret=False
compiles for the TPU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.extractor import (channel_norm, conv_head_packed,
                                  correlate_packed, mxu_precision, tap_dot)


# Pixels per row chunk of the flat kernel's conv loop: each loop body
# holds (CHUNK_PIXELS, C) tap views, so the kernel's code and its live
# values stay small whatever the tile size.
CHUNK_PIXELS = 512

def _split_corr(packed):
    """The packed params without the correlation bank: the kernels run
    the conv path only; the bank's one dot runs outside them."""
    return {k: v for k, v in packed.items()
            if k not in ("corr", "corr_scale")}


def _add_corr(out, packed, tiles, with_embed):
    """Add the correlation term (``correlate_packed``, one batched dot
    in XLA) to the kernel's head logits."""
    logits, g = (out if with_embed else (out, None))
    corr = correlate_packed(packed, tiles)
    if corr is not None:
        logits = logits + corr
    return (logits, g) if with_embed else logits


def _full_spec(shape):
    """BlockSpec broadcasting one whole (weight) array to every step."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _stack_mid_blocks(packed):
    """Kernel params: block 0 (cin=3) apart, blocks 1..D-1 (all C -> C)
    stacked on a leading axis so the kernel loops over them, plus
    to_bits and head.  The correlation bank stays out."""
    blocks = packed["blocks"]
    kp = {"first": blocks[0], "to_bits": packed["to_bits"],
          "head": packed["head"]}
    if len(blocks) > 1:
        kp["mid"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks[1:])
    return kp


def fused_extractor(tiles, packed, *, interpret: bool = True,
                    with_embed: bool = False):
    """tiles (b, l, l, 3) f32 + packed extractor params -> (b, n_bits)
    f32 logits, flat schedule (grid=(b,), one image per step).

    ``packed`` is ``extractor.pack_params(params, dtype)`` — built once
    per pipeline, reused across every batch; its leaf dtypes select the
    fp32 / bf16 / int8 compute path.  Not jitted here: callers jit
    around it.

    The kernel computes the conv path of ``conv_head_packed`` (same
    per-tap dots, same tap order, same epilogue) on a flattened
    (l*l, C) activation: a SAME 3x3 tap is a window of a VMEM scratch
    that holds the activation between zero halo rows, offset by
    ``(dy-1)*l + (dx-1)`` rows, with the pixels that wrapped across a
    row edge masked to zero.  Every value stays 2-D with channels on
    lanes, and blocks 1..D-1 run as one loop over stacked weights, so
    the kernel compiles in one layer body's time instead of D.  The
    correlation bank's dot runs after the kernel in XLA
    (``correlate_packed``).

    Each step writes a (1, n_bits) block of a (b, 1, n_bits) output:
    the block's last two dims equal the array's, which the TPU compiler
    requires of a block narrower than (8, 128).

    ``with_embed=True`` returns ``(logits, embed)`` where ``embed`` is
    the (b, n_bits) f32 GAP vector the head consumes — an intermediate
    the kernel already computes, written to a second output block.
    """
    b, l = tiles.shape[0], tiles.shape[1]
    L = l * l
    pad = -(-(l + 8) // 8) * 8        # zero halo rows, sublane-aligned
    rows = math.gcd(l, max(1, CHUNK_PIXELS // l))
    CH = rows * l                     # pixels per chunk: whole rows
    S = L + 2 * pad                   # rows of one padded activation
    n_chunks = L // CH
    n_bits = packed["head"]["b"].shape[0]
    C = packed["blocks"][0]["w"].shape[-1]
    kp = _stack_mid_blocks(packed)
    n_mid = len(packed["blocks"]) - 1
    leaves, treedef = jax.tree.flatten(kp)
    n_par = len(leaves)
    n_out = 2 if with_embed else 1

    def kernel(img_ref, *refs):
        pk = jax.tree.unflatten(treedef, refs[:n_par])
        out_refs = refs[n_par: n_par + n_out]
        x0_ref, buf_ref = refs[-2:]
        # chunks are whole image rows, so every chunk has the same
        # column pattern
        col = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (CH, 1), 0), l)
        edge_ok = {0: col != 0, 2: col != l - 1}

        def conv_chunk(src, off, r0, w, scale, cin):
            """Rows [r0, r0+CH) of the SAME 3x3 conv of the activation
            held in rows [off, off + S) of ``src`` between zero halo
            rows: per kernel row dy one aligned window load, its three
            dx taps static sublane shifts of it, pixels wrapped across
            a row edge zeroed."""
            acc = None
            for dy in range(3):
                base = pl.multiple_of(
                    off + pad + r0 + (dy - 1) * l - 8, 8)
                win = src[pl.ds(base, CH + 16), :]
                for dx in range(3):
                    xs = win[7 + dx: 7 + dx + CH]
                    if dx != 1:
                        xs = jnp.where(edge_ok[dx], xs, 0.0)
                    y = tap_dot(xs, w, 3 * dy + dx, cin, scale)
                    acc = y if acc is None else acc + y
            return acc

        def layer(src, src_off, dst_off, entry, cin):
            def chunk(j, carry):
                r0 = pl.multiple_of(j * CH, 8)
                y = conv_chunk(src, src_off, r0, entry["w"],
                               entry.get("scale"), cin)
                dst = pl.multiple_of(dst_off + pad + r0, 8)
                buf_ref[pl.ds(dst, CH), :] = jax.nn.relu(
                    channel_norm(y + entry["b"]))
                return carry
            jax.lax.fori_loop(0, n_chunks, chunk, 0)

        x0_ref[...] = jnp.zeros_like(x0_ref)
        buf_ref[...] = jnp.zeros_like(buf_ref)
        x0_ref[pad: pad + L, :] = img_ref[...].reshape(L, 3)
        # buf_ref holds two activations (rows [0, S) and [S, 2S));
        # layer i reads one and writes the other
        layer(x0_ref, 0, 0, {k: r[...] for k, r in pk["first"].items()},
              3)
        if n_mid:
            def mid(i, carry):
                layer(buf_ref, (i % 2) * S, ((i + 1) % 2) * S,
                      {k: r[i] for k, r in pk["mid"].items()}, C)
                return carry
            jax.lax.fori_loop(0, n_mid, mid, 0)
        tb = {k: r[...] for k, r in pk["to_bits"].items()}

        def gap_chunk(j, acc):
            y = conv_chunk(buf_ref, (n_mid % 2) * S,
                           pl.multiple_of(j * CH, 8), tb["w"],
                           tb.get("scale"), C) + tb["b"]
            return acc + jnp.sum(y, axis=0, keepdims=True)
        g = jax.lax.fori_loop(0, n_chunks, gap_chunk,
                              jnp.zeros((1, n_bits), jnp.float32)) / L
        cdt = pk["head"]["w"].dtype
        logits = jnp.dot(g.astype(cdt), pk["head"]["w"][...],
                         precision=mxu_precision(cdt),
                         preferred_element_type=jnp.float32)
        out_refs[0][...] = logits + pk["head"]["b"][...]
        if with_embed:
            out_refs[1][...] = g

    out_spec = pl.BlockSpec((None, 1, n_bits), lambda i: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, 1, n_bits), jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, l, l, 3), lambda i: (i, 0, 0, 0))] +
                 [_full_spec(x.shape) for x in leaves],
        out_specs=[out_spec] * n_out,
        out_shape=[out_shape] * n_out,
        scratch_shapes=[pltpu.VMEM((L + 2 * pad, 3), jnp.float32),
                        pltpu.VMEM((2 * S, C), jnp.float32)],
        interpret=interpret,
        name="fused_extractor",
    )(tiles, *leaves)
    out = tuple(o.reshape(b, n_bits) for o in out)
    return _add_corr(out if with_embed else out[0], packed, tiles,
                     with_embed)


def _taps_fold(read_tap, entry, cin, j0, nj):
    """Nine tap-shifted dots, N-restricted to weight columns
    [j0, j0+nj), accumulated in the static left-fold order of
    ``conv3x3_mm`` — bit-identical to the full-width conv's columns."""
    w2d = entry["w"][:, j0: j0 + nj]
    scale = entry.get("scale")
    if scale is not None:
        scale = scale[j0: j0 + nj]
    acc = None
    for tap in range(9):
        y = tap_dot(read_tap(tap), w2d, tap, cin, scale)
        acc = y if acc is None else acc + y
    return acc


def _scratch_shapes(bb, l, C):
    """Padded-activation + channel-tile accumulator scratch in VMEM."""
    return [pltpu.VMEM((bb, l + 2, l + 2, C), jnp.float32),
            pltpu.VMEM((bb * l * l, C), jnp.float32)]


def fused_extractor_blocked(tiles, packed, *, batch_block: int = 1,
                            channel_tile: int = 0,
                            double_buffer: bool = True,
                            interpret: bool = True,
                            with_embed: bool = False):
    """Blocked-schedule decode: tiles (b, l, l, 3) f32 -> (b, n_bits)
    f32 logits, bitwise equal to ``fused_extractor`` for fp32 packs.

    ``batch_block`` images per grid step (ragged batches are zero-padded
    up to a multiple and the pad rows sliced off — every body op is
    batch-stable, so pad rows cannot perturb real rows).
    ``channel_tile`` bounds the output-column slice each inner dot
    produces (0 = full width).  ``double_buffer`` marks the batch grid
    dimension parallel on TPU so block fetches pipeline; it is a no-op
    under interpret.  ``with_embed=True`` adds a second (b, n_bits)
    output carrying the GAP vector (see ``fused_extractor``); the
    logits ops are unchanged.
    """
    b, l = tiles.shape[0], tiles.shape[1]
    n_bits = packed["head"]["b"].shape[0]
    C = packed["blocks"][0]["w"].shape[-1]
    bb = max(1, min(batch_block, b))
    ct = min(channel_tile, C) if channel_tile else C

    if b % bb:
        pad = bb - b % bb
        padded = jnp.concatenate(
            [tiles, jnp.zeros((pad,) + tiles.shape[1:], tiles.dtype)])
        out = fused_extractor_blocked(
            padded, packed, batch_block=bb, channel_tile=channel_tile,
            double_buffer=double_buffer, interpret=interpret,
            with_embed=with_embed)
        if with_embed:
            return out[0][:b], out[1][:b]
        return out[:b]

    leaves, treedef = jax.tree.flatten(_split_corr(packed))
    M = bb * l * l
    n_out = 2 if with_embed else 1

    def kernel(img_ref, *refs):
        param_refs = refs[:-(n_out + 2)]
        out_refs = refs[-(n_out + 2):-2]
        xp_ref, y_ref = refs[-2], refs[-1]
        out_ref = out_refs[0]
        pk = jax.tree.unflatten(treedef, [r[...] for r in param_refs])
        tiles_blk = img_ref[...]  # (bb, l, l, 3)
        # zero the scratch borders once per step (the interior is
        # overwritten every layer)
        xp_ref[...] = jnp.zeros_like(xp_ref)

        # layer 0 reads the image block directly (cin=3 taps)
        x4 = jnp.pad(tiles_blk, ((0, 0), (1, 1), (1, 1), (0, 0)))

        def read0(tap):
            dy, dx = divmod(tap, 3)
            return jax.lax.slice(
                x4, (0, dy, dx, 0), (bb, dy + l, dx + l, 3)).reshape(M, 3)

        def read_sc(tap):
            dy, dx = divmod(tap, 3)
            return xp_ref[:, dy: dy + l, dx: dx + l, :].reshape(M, C)

        for li, blk in enumerate(pk["blocks"]):
            read_tap, cin = (read0, 3) if li == 0 else (read_sc, C)
            for j0 in range(0, C, ct):
                nj = min(ct, C - j0)
                y_ref[:, j0: j0 + nj] = _taps_fold(
                    read_tap, blk, cin, j0, nj)
            # flat-norm epilogue on the (M, C) GEMM layout
            y = jax.nn.relu(channel_norm(y_ref[...] + blk["b"]))
            xp_ref[:, 1: l + 1, 1: l + 1, :] = y.reshape(bb, l, l, C)

        # to_bits (N=n_bits is small: always full width) + GAP + head
        tb = pk["to_bits"]
        yt = _taps_fold(read_sc, tb, C, 0, n_bits)
        yt = yt.reshape(bb, l, l, n_bits) + tb["b"]
        g = yt.mean(axis=(1, 2))
        if with_embed:
            out_refs[1][...] = g
        cdt = pk["head"]["w"].dtype
        logits = jnp.dot(g.astype(cdt), pk["head"]["w"],
                         precision=mxu_precision(cdt),
                         preferred_element_type=jnp.float32)
        out_ref[...] = logits + pk["head"]["b"]

    kwargs = {}
    if double_buffer and not interpret:
        # pipeline consecutive image blocks on TPU
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    out_spec = pl.BlockSpec((bb, n_bits), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((b, n_bits), jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=(b // bb,),
        in_specs=[pl.BlockSpec((bb, l, l, 3), lambda i: (i, 0, 0, 0))] +
                 [_full_spec(x.shape) for x in leaves],
        out_specs=[out_spec] * n_out if with_embed else out_spec,
        out_shape=[out_shape] * n_out if with_embed else out_shape,
        scratch_shapes=_scratch_shapes(bb, l, C),
        interpret=interpret,
        name="fused_extractor_blocked",
        **kwargs,
    )(tiles, *leaves)
    return _add_corr(tuple(out) if with_embed else out, packed, tiles,
                     with_embed)
