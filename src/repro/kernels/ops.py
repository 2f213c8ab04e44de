"""Public wrappers for the Pallas kernels.

Import surface used by the rest of the framework; each op runs its
Pallas kernel compiled on a TPU backend and in interpret mode on the
CPU (tests), and has a pure-jnp oracle in ref.py.  Any other backend is
an error: no kernel falls back to interpret mode on an accelerator.
"""
from __future__ import annotations

import jax

from repro.kernels.fused_preprocess import fused_preprocess as \
    _fused_preprocess
from repro.kernels.fused_tile_preprocess import fused_tile_preprocess as \
    _fused_tile_preprocess


def interpret_mode() -> bool:
    """Whether the kernels run interpreted: True on the CPU backend,
    False on TPU, an error anywhere else."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for 'tpu' and are interpreted on "
        f"'cpu'; backend {backend!r} is neither")


def fused_preprocess(raw, *, resize: int = 256, crop: int = 256,
                     mean=None, std=None):
    """Fused Resize->CenterCrop->Normalize (QRMark App. B.1, TPU form)."""
    return _fused_preprocess(raw, resize=resize, crop=crop, mean=mean,
                             std=std, interpret=interpret_mode())


def fused_tile_preprocess(raw, offsets, *, resize: int = 256,
                          crop: int = 256, tile: int = 64,
                          mean=None, std=None):
    """Tile-first fused ingest: Resize->Crop->Normalize->Tile-extract in
    one kernel — the (b, tile, tile, 3) decode input directly, equal
    (up to float reassociation) to ``fused_preprocess`` +
    ``tiling.extract_tiles`` at ``offsets``.
    Offsets may also be a (b, k, 2) escalation plan, emitting
    (b*k, tile, tile, 3) image-major so escalated tiles ride the same
    MXU path (see ``tiling.escalation_offsets``)."""
    return _fused_tile_preprocess(raw, offsets, resize=resize, crop=crop,
                                  tile=tile, mean=mean, std=std,
                                  interpret=interpret_mode())


def fused_extractor(tiles, packed, schedule=None, with_embed=False):
    """Fused decode: the extractor's conv blocks + GAP/head in one
    kernel launch per tile batch, then the correlation bank's dot.
    ``packed`` = ``extractor.pack_params(params, dtype)``; its dtype
    selects the fp32 (full precision), bf16 (MXU compute, fp32
    accumulation) or int8 (per-channel-scaled weights, int32
    accumulation) path.

    ``schedule`` picks the kernel blocking: ``None`` runs the flat
    grid=(b,) kernel; a ``kernels.autotune.Schedule`` (or anything with
    ``batch_block`` / ``channel_tile`` / ``double_buffer`` attributes)
    runs the blocked kernel, which compiles only in interpret mode.

    ``with_embed=True`` returns ``(logits, embed)``: the GAP vector is
    emitted as a second kernel output (no extra arithmetic) — the
    serving tier's near-duplicate cache key."""
    interpret = interpret_mode()
    if schedule is None:
        from repro.kernels.fused_extractor import fused_extractor as _fx
        return _fx(tiles, packed, interpret=interpret,
                   with_embed=with_embed)
    from repro.kernels.fused_extractor import fused_extractor_blocked
    return fused_extractor_blocked(
        tiles, packed, batch_block=schedule.batch_block,
        channel_tile=schedule.channel_tile,
        double_buffer=schedule.double_buffer, interpret=interpret,
        with_embed=with_embed)


def rs_decode(bits, *, code=None):
    """Batched Berlekamp-Welch decode (Pallas kernel for the default
    (15,12) GF(16) code; jax_rs fallback otherwise)."""
    from repro.core.rs.codec import DEFAULT_CODE
    from repro.kernels.rs_decode import rs_decode_batch
    return rs_decode_batch(bits, code=code or DEFAULT_CODE,
                           interpret=interpret_mode())
