"""HiDDeN-style watermark encoder H_E and tile extractor H_D (QRMark §4.1).

Pure-JAX conv nets (NHWC).  The encoder embeds an N-bit message into an
l x l tile as a residual (x_w = x_0 + alpha * delta, ReDMark-style); the
extractor recovers soft bit logits from a (possibly transformed) tile.
Both are small enough to train on CPU at reduced scale and are the
"decode" stage of the detection pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def conv_init(key, kh, kw, cin, cout, scale=None):
    scale = scale or (2.0 / (kh * kw * cin)) ** 0.5
    return scale * jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)


def conv2d(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def channel_norm(x, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _block(params, x):
    x = conv2d(x, params["w"]) + params["b"]
    return jax.nn.relu(channel_norm(x))


# ---------------------------------------------------------------------------
# extractor H_D
# ---------------------------------------------------------------------------


def init_extractor(key, *, n_bits: int, channels: int = 64,
                   depth: int = 7, tile: int = 0,
                   patterns: "jnp.ndarray" = None) -> dict:
    """HiDDeN-style conv extractor + a spread-spectrum correlation path.

    The correlation bank (init tied to the encoder's pattern bank when
    given) makes the 60-bit code linearly decodable from step 0; the conv
    stack learns the nonlinear robustness corrections under attacks.
    This warm-start is the CPU-scale adaptation recorded in DESIGN.md —
    at paper scale the conv path alone trains to the same point."""
    ks = jax.random.split(key, depth + 4)
    blocks = []
    cin = 3
    for i in range(depth):
        blocks.append({"w": conv_init(ks[i], 3, 3, cin, channels),
                       "b": jnp.zeros((channels,))})
        cin = channels
    p = {
        "blocks": blocks,
        "to_bits": {"w": conv_init(ks[depth], 3, 3, channels, n_bits),
                    "b": jnp.zeros((n_bits,))},
        "head": {"w": dense_init(ks[depth + 1], (n_bits, n_bits),
                                 scale=0.2),
                 "b": jnp.zeros((n_bits,))},
    }
    if tile:
        if patterns is None:
            patterns = pattern_bank(ks[depth + 2], n_bits, tile)
        p["corr"] = patterns
        p["corr_scale"] = jnp.ones((n_bits,))
    return p


def pattern_bank(key, n_bits: int, tile: int):
    """Unit-norm white patterns, one per bit."""
    P = jax.random.normal(key, (n_bits, tile, tile, 3), jnp.float32)
    P = P - P.mean(axis=(1, 2, 3), keepdims=True)
    return P / jnp.sqrt(jnp.sum(jnp.square(P), axis=(1, 2, 3),
                                keepdims=True))


def highpass(x):
    """Remove local mean (3x3): image content is low-frequency, the
    spread-spectrum watermark is white — classic correlation denoising."""
    c = x.shape[-1]
    k = jnp.ones((3, 3, 1, 1), jnp.float32) / 9.0
    k = jnp.tile(k, (1, 1, 1, c))
    blur = jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c)
    return x - blur


# -- matmul-form forward: the one body shared by the unfused XLA path
# -- and the fused Pallas decode kernel (kernels/fused_extractor.py)

DECODE_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                 "int8": jnp.int8}

INT8_QMAX = 127.0


def quantize_weight_int8(w2d):
    """(K, N) fp32 weight -> (int8 weight, fp32 per-output-channel
    scale (N,)): symmetric per-channel quantization, the static half of
    the int8 decode rung (computed once at ``pack_params`` time)."""
    scale = jnp.maximum(jnp.abs(w2d).max(axis=0),
                        jnp.float32(1e-8)) / INT8_QMAX
    q = jnp.clip(jnp.round(w2d / scale), -INT8_QMAX,
                 INT8_QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_rows_int8(x2d):
    """(M, K) fp32 activations -> (int8, fp32 per-row scale (M, 1)):
    the dynamic half of the int8 rung.  Per-ROW scales keep the op
    batch-stable (row i of a size-b batch quantizes exactly as it would
    alone), which the ragged-serving/bit-identity contract needs."""
    s = jnp.maximum(jnp.abs(x2d).max(axis=1, keepdims=True),
                    jnp.float32(1e-8)) / INT8_QMAX
    q = jnp.clip(jnp.round(x2d / s), -INT8_QMAX,
                 INT8_QMAX).astype(jnp.int8)
    return q, s


def mxu_precision(dtype):
    """Dot precision for a compute dtype: fp32 operands run at full
    fp32 precision (``HIGHEST``) everywhere, so the fp32 rung means
    fp32 on the TPU's MXU too; bf16 operands keep the default single
    pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _shifts3x3(x):
    """The nine 3x3-tap shifted views of x (b, h, w, c), zero padding,
    [ky, kx] order — the implicit im2col a SAME 3x3 conv reads."""
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return [xp[:, dy: dy + h, dx: dx + w, :]
            for dy in range(3) for dx in range(3)]


def tap_dot(xs2d, w2d, tap, cin, scale=None):
    """One tap's dot: (M, cin) shifted view x rows [tap*cin, (tap+1)*cin)
    of a packed weight -> (M, cout), fp32 result.

    THE per-tap primitive every decode path shares (the unfused graph,
    the flat Pallas kernel, and the blocked kernel all accumulate these
    in the same static tap order).  fp32/bf16 weights: cast input, MXU dot, fp32
    accumulation.  int8 weights (``scale`` = the per-output-channel
    dequant scale, column-sliced the same way as ``w2d`` when the
    caller channel-tiles): dynamic per-row activation quantization,
    int8 x int8 -> int32 dot, fp32 dequantize — so the int8 partial
    sums join the same fp32 left-fold as the other rungs."""
    wt = w2d[tap * cin: (tap + 1) * cin]
    if w2d.dtype == jnp.int8:
        xq, s = quantize_rows_int8(xs2d)
        y = jax.lax.dot_general(xq, wt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * s * scale[None, :]
    return jnp.dot(xs2d.astype(w2d.dtype), wt,
                   precision=mxu_precision(w2d.dtype),
                   preferred_element_type=jnp.float32)


def conv3x3_mm(x, w2d, scale=None):
    """SAME 3x3 conv as nine accumulated MXU matmuls: x (b, h, w, c) x
    packed weight (9c, cout) -> (b*h*w, cout), fp32 accumulation.

    Tap-accumulated rather than one materialised (b*h*w, 9c) im2col
    matmul, so the live working set stays activation-sized (the
    full-image sequential path and training also run this body).  Tap
    order is static, every tap dot keeps M = b*h*w, and the nine
    partial sums add elementwise.  ``scale`` carries
    the int8 rung's per-channel dequant scales (see :func:`tap_dot`)."""
    b, h, w, c = x.shape
    acc = None
    for tap, xs in enumerate(_shifts3x3(x)):
        y = tap_dot(xs.reshape(b * h * w, c), w2d, tap, c, scale)
        acc = y if acc is None else acc + y
    return acc


def _box3x3(x):
    """3x3 box blur, zero padding — the mean ``highpass`` subtracts,
    as the same nine-tap sum the conv path uses (shared, so the
    kernel's and the unfused graph's blur cannot drift)."""
    acc = None
    for xs in _shifts3x3(x):
        acc = xs if acc is None else acc + xs
    return acc * (1.0 / 9.0)


def pack_params(params, dtype="fp32"):
    """Extractor params -> the matmul-friendly layout the decode path
    consumes (built once per pipeline; :func:`extractor_forward_packed`
    and the Pallas kernel both read this form).

    Matmul operands (block/to_bits/head weights, correlation bank) are
    stored in the compute ``dtype`` ("fp32" or "bf16" — the MXU input
    precision); every epilogue term (biases, corr_scale) stays fp32
    because accumulation and the norm/ReLU epilogue always run in
    fp32.

    "int8" is the lowest rung of the precision ladder: conv/to_bits
    weights quantize symmetrically per output channel at pack time
    (``quantize_weight_int8``, the scale rides along as a fp32
    ``"scale"`` leaf), while head + correlation — a negligible FLOP
    slice but the decision-critical epilogue — stay fp32."""
    cdt = DECODE_DTYPES[dtype] if isinstance(dtype, str) else dtype

    def conv_entry(w4d, bias):
        w2d = w4d.reshape(-1, w4d.shape[-1])
        if cdt == jnp.int8:
            q, scale = quantize_weight_int8(w2d.astype(jnp.float32))
            return {"w": q, "scale": scale,
                    "b": bias.astype(jnp.float32)}
        return {"w": w2d.astype(cdt), "b": bias.astype(jnp.float32)}

    # the head (and corr bank below) stay fp32 in int8 packs
    hdt = jnp.float32 if cdt == jnp.int8 else cdt
    pk = {
        "blocks": [conv_entry(b["w"], b["b"]) for b in params["blocks"]],
        "to_bits": conv_entry(params["to_bits"]["w"],
                              params["to_bits"]["b"]),
        "head": {"w": params["head"]["w"].astype(hdt),
                 "b": params["head"]["b"].astype(jnp.float32)},
    }
    if "corr" in params:
        n, t = params["corr"].shape[0], params["corr"].shape[1]
        # (n, t, t, 3) -> (t*t*3, n), row p*3 + c: the correlation is
        # then one (b, t*t*3) x (t*t*3, n) dot over the flattened
        # channels-last highpass tiles
        pk["corr"] = params["corr"].transpose(1, 2, 3, 0).reshape(
            t * t * 3, n).astype(hdt)
        pk["corr_scale"] = params["corr_scale"].astype(jnp.float32)
    return pk


def _dequant_w(entry):
    w = entry["w"].astype(jnp.float32)
    if entry["w"].dtype == jnp.int8:
        w = w * entry["scale"][None, :]
    return w


def unpack_params(packed):
    """Exact inverse of :func:`pack_params` for fp32 packs (bf16 packs
    round-trip to the bf16-rounded weights, int8 packs to the
    dequantized q * scale weights)."""
    cin = 3
    blocks = []
    for blk in packed["blocks"]:
        cout = blk["w"].shape[-1]
        blocks.append({"w": _dequant_w(blk).reshape(3, 3, cin, cout),
                       "b": blk["b"]})
        cin = cout
    nb = packed["to_bits"]["w"].shape[-1]
    p = {
        "blocks": blocks,
        "to_bits": {"w": _dequant_w(packed["to_bits"]).reshape(
            3, 3, cin, nb),
            "b": packed["to_bits"]["b"]},
        "head": {"w": packed["head"]["w"].astype(jnp.float32),
                 "b": packed["head"]["b"]},
    }
    if "corr" in packed:
        t3, n = packed["corr"].shape
        t = int(round((t3 // 3) ** 0.5))
        p["corr"] = packed["corr"].astype(jnp.float32).reshape(
            t, t, 3, n).transpose(3, 0, 1, 2)
        p["corr_scale"] = packed["corr_scale"]
    return p


def conv_head_packed(packed, tiles):
    """The conv path of the packed forward: conv blocks, to_bits, GAP
    and head -> (head logits, GAP vector ``g``), both (b, n_bits) f32.

    The Pallas decode kernel runs this body per grid step; the
    unfused graph runs it over the whole batch.  Matmul inputs are
    cast to the packed compute dtype; accumulation
    (``preferred_element_type``) and the epilogue stay fp32.  int8
    packs route their conv matmuls through the quantized ``tap_dot``
    path (the head reads the pack's fp32 head dtype)."""
    b, l = tiles.shape[0], tiles.shape[1]
    cdt = packed["head"]["w"].dtype
    x = tiles
    for blk in packed["blocks"]:
        y = conv3x3_mm(x, blk["w"], blk.get("scale"))
        x = jax.nn.relu(channel_norm(
            y.reshape(b, l, l, -1) + blk["b"]))
    y = conv3x3_mm(x, packed["to_bits"]["w"],
                   packed["to_bits"].get("scale"))
    y = y.reshape(b, l, l, -1) + packed["to_bits"]["b"]
    g = y.mean(axis=(1, 2))  # GAP
    logits = jnp.dot(g.astype(cdt), packed["head"]["w"],
                     precision=mxu_precision(cdt),
                     preferred_element_type=jnp.float32)
    return logits + packed["head"]["b"], g


def correlate_packed(packed, tiles):
    """The spread-spectrum correlation term (b, n_bits) f32, or None
    when the pack has no bank or the bank's tile size differs from the
    tiles' (the conv path alone then decodes, e.g. full-image baseline
    mode).  highpass = tiles minus their 3x3 box blur, flattened
    channels-last and correlated with the whole bank in one dot."""
    b, l = tiles.shape[0], tiles.shape[1]
    if "corr" not in packed or packed["corr"].shape[0] != l * l * 3:
        return None
    cdt = packed["corr"].dtype
    hp = (tiles - _box3x3(tiles)).reshape(b, l * l * 3)
    corr = jnp.dot(hp.astype(cdt), packed["corr"],
                   precision=mxu_precision(cdt),
                   preferred_element_type=jnp.float32)
    return corr * packed["corr_scale"]


def extractor_forward_packed_embed(packed, tiles):
    """:func:`extractor_forward_packed` that additionally returns the
    GAP vector ``g`` — the to_bits global-average-pooled features the
    head consumes.  ``g`` is the serving tier's near-duplicate
    embedding (``serving.cache.EmbeddingCache``): it already exists on
    the logits path, so exposing it costs no extra arithmetic.

    The conv path (:func:`conv_head_packed`) is the body the Pallas
    decode kernel runs per grid step; the correlation term
    (:func:`correlate_packed`) is one batched dot outside the kernel.
    The kernel path and this unfused graph add the two the same way.
    """
    logits, g = conv_head_packed(packed, tiles)
    corr = correlate_packed(packed, tiles)
    if corr is not None:
        logits = logits + corr
    return logits, g


def extractor_forward_packed(packed, tiles):
    """tiles (b, l, l, 3) on packed params -> (b, n_bits) f32 logits —
    the embed-free view of :func:`extractor_forward_packed_embed` (same
    ops, same order; the GAP vector is simply not returned)."""
    return extractor_forward_packed_embed(packed, tiles)[0]


def extractor_forward(params, tiles):
    """tiles (b, l, l, 3) in [-1, 1] -> bit logits (b, n_bits).

    Same math as the original conv formulation (semantic oracle:
    ``kernels.ref.fused_extractor_ref``), expressed through the packed
    matmul body the fused kernel also computes.  Packing inside jit is
    free (reshapes and casts constant-fold)."""
    return extractor_forward_packed(pack_params(params), tiles)


def extractor_forward_embed(params, tiles):
    """Unfused forward returning (logits, gap_embedding) — the
    embed-emitting decode for pipelines running without the fused
    kernel (``fused_decode=False``).  Logits are those of
    :func:`extractor_forward` (same body, same op order)."""
    return extractor_forward_packed_embed(pack_params(params), tiles)


# ---------------------------------------------------------------------------
# encoder H_E
# ---------------------------------------------------------------------------


def init_encoder(key, *, n_bits: int, channels: int = 32,
                 depth: int = 4, tile: int = 0) -> dict:
    ks = jax.random.split(key, depth + 3)
    blocks = []
    cin = 3
    for i in range(depth):
        blocks.append({"w": conv_init(ks[i], 3, 3, cin, channels),
                       "b": jnp.zeros((channels,))})
        cin = channels
    p = {
        "blocks": blocks,
        # input: features + broadcast message + original image
        "fuse": {"w": conv_init(ks[depth], 3, 3, channels + n_bits + 3,
                                channels),
                 "b": jnp.zeros((channels,))},
        "out": {"w": conv_init(ks[depth + 1], 1, 1, channels, 3,
                               scale=0.02),
                "b": jnp.zeros((3,))},
    }
    if tile:
        p["patterns"] = pattern_bank(ks[depth + 2], n_bits, tile)
    return p


def encoder_forward(params, tiles, messages, *, alpha: float = 1.0,
                    embed_rms: float = 0.06):
    """tiles (b, l, l, 3), messages (b, n) in {0,1} -> watermarked tiles.

    The residual is power-normalised to ``embed_rms`` per sample before
    the alpha scale, which (a) pins the embedding strength / PSNR by
    construction (rms 0.06 on a [-1,1] range ~= 30.5 dB) and (b) makes
    training insensitive to the initial scale of the output conv — the
    optimisation then shapes the *code*, not the amplitude."""
    b, l, _, _ = tiles.shape
    x = tiles
    for blk in params["blocks"]:
        x = _block(blk, x)
    m = (2.0 * messages.astype(jnp.float32) - 1.0)
    mb = jnp.broadcast_to(m[:, None, None, :], (b, l, l, m.shape[-1]))
    x = jnp.concatenate([x, mb, tiles], axis=-1)
    x = _block(params["fuse"], x)
    delta = conv2d(x, params["out"]["w"]) + params["out"]["b"]
    if "patterns" in params:
        # spread-spectrum pathway: delta += sum_i mtilde_i * P_i
        delta = delta + jnp.einsum("bn,nhwc->bhwc", m, params["patterns"])
    rms = jnp.sqrt(jnp.mean(jnp.square(delta), axis=(1, 2, 3),
                            keepdims=True) + 1e-8)
    delta = delta * (embed_rms / rms)
    return jnp.clip(tiles + alpha * delta, -1.0, 1.0), delta
