"""Operations and bytes each stage must do per image, from the shapes.

Counts are of multiply-adds (2 FLOP each) in the matrix products the
algorithm needs; elementwise work (norms, ReLU, the box blur) is left
out, so a share of the peak built on them is a lower bound of the
chip's use.  Bytes are what the algorithm must move through HBM at
least: its inputs and outputs, and weights once per call.
"""
from __future__ import annotations


def conv_flops_per_pixel(channels: int, depth: int, n_bits: int) -> float:
    """3x3 SAME conv stack: ``depth`` blocks (the first reads 3
    channels) and the ``to_bits`` conv."""
    return 2.0 * 9 * (3 * channels + (depth - 1) * channels * channels
                      + channels * n_bits)


def preprocess_flops(raw: int, out: int) -> float:
    """Separable resize as two interpolation matmuls per channel, with
    the crop (and tile) folded into the matrices: (out, raw) @ (raw,
    raw), then (out, raw) @ (raw, out)."""
    return 3 * (2.0 * out * raw * raw + 2.0 * out * out * raw)


def decode_flops(ext: dict, side: int, corr: bool) -> float:
    """Decode of one ``side``-square input: conv stack, GAP head and,
    where the bank applies, the correlation."""
    c, d, n = ext["channels"], ext["depth"], ext["n_bits"]
    f = side * side * conv_flops_per_pixel(c, d, n) + 2.0 * n * n
    if corr:
        f += 2.0 * side * side * 3 * n
    return f


def weight_bytes(ext: dict, side: int, corr: bool) -> float:
    c, d, n = ext["channels"], ext["depth"], ext["n_bits"]
    w = 9 * (3 * c + (d - 1) * c * c + c * n) + d * c + n + n * n + n
    if corr:
        w += side * side * 3 * n + n
    return 4.0 * w


def stages(cfg: dict) -> dict:
    """{stage: {"flops": per image, "bytes": per image,
    "bytes_per_call": weights}} for a configuration file's dict."""
    det, ext, raw = cfg["detection"], cfg["extractor"], cfg["raw_size"]
    crop, tile = det["img_size"], det["tile"]
    tiled = det["mode"] != "sequential"
    side = tile if tiled else crop
    corr = tiled        # the bank is tile-sized
    n_bits = ext["n_bits"]
    raw_bytes = 3.0 * raw * raw                    # uint8 in
    side_bytes = 4.0 * 3 * side * side             # float32 decode input
    out = {
        "ingest": {"flops": preprocess_flops(raw, side),
                   "bytes": raw_bytes + side_bytes,
                   "bytes_per_call": 4.0 * 2 * side * raw},
        "decode": {"flops": decode_flops(ext, side, corr),
                   "bytes": side_bytes + 4.0 * n_bits,
                   "bytes_per_call": weight_bytes(ext, side, corr)},
    }
    return out


def step_flops(cfg: dict) -> float:
    """Matmul FLOP per image of the whole detection step."""
    return sum(s["flops"] for s in stages(cfg).values())
