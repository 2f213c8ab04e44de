"""The comparison that decides ``correct``.

A seeded sample of the images the window answered is decoded again by
the plain float32 reference (``bench/reference.py``, at "highest" matmul
precision), with the same images and the same keys, in blocks.  Three
numbers are compared, each with its limit:

* ``max_logit_diff``: the largest |logit| gap between the system and the
  reference over the sample.  It covers ingest, tile choice and decode.
  The limit comes from the configuration file (``check``), set between
  the gap of sound runs and the gap the reference itself shows when its
  dots run one precision lower (the control).
* ``rs_mismatch``: images whose ``ok`` differs from the reference RS
  decoder run on the system's own thresholded logits, or whose message
  differs where the decode succeeded.  Exact: limit 0.  (A word that
  does not decode carries no message, so its bits are not compared.)
* ``missing``: answers that never came or came back as an error
  (refusals at admission are not answers and are counted as ``failed``
  instead).  Limit 0.
* ``empty_sample``: 1 where no answer could be checked.  Limit 0.
"""
from __future__ import annotations

import sys
from typing import Dict

import jax
import numpy as np

from bench import reference

BLOCK = 32          # reference images per block


def reference_logits(cfg: dict, params, raw: np.ndarray, keys,
                     precision: str = "highest") -> np.ndarray:
    det = cfg["detection"]
    geo = {"resize": det["resize_src"], "crop": det["img_size"]}
    out = []
    with jax.default_matmul_precision(precision):
        for i in range(0, len(raw), BLOCK):
            if det["mode"] == "sequential":
                lg = reference.image_logits(params, raw[i: i + BLOCK], **geo)
            else:
                lg = reference.tile_logits(params, raw[i: i + BLOCK],
                                           keys[i: i + BLOCK],
                                           tile=det["tile"], **geo)
            out.append(np.asarray(lg))
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


def numbers(rows: Dict[str, np.ndarray], ref_logits: np.ndarray,
            code: reference.RS) -> Dict[str, float]:
    """max_logit_diff and rs_mismatch of ``rows`` against the reference."""
    if not len(ref_logits):
        return {"max_logit_diff": 0.0, "rs_mismatch": 0}
    gap = float(np.max(np.abs(rows["logits"] - ref_logits)))
    msg, ok = code.decode((rows["logits"] > 0).astype(np.int32))
    bad = (ok != rows["ok"]) | (ok & np.any(msg != rows["message_bits"],
                                            axis=1))
    return {"max_logit_diff": gap, "rs_mismatch": int(bad.sum())}


def compare(win, cfg: dict, params, pool: np.ndarray, code: reference.RS,
            key_bits: np.ndarray, seed: int) -> Dict[str, dict]:
    limits = cfg["check"]
    raw = pool[win.pool_index]
    keys = reference.image_keys(seed, win.key_index, win.key_pos)
    ref = reference_logits(cfg, params, raw, keys)
    got = numbers(win.rows, ref, code)
    if len(ref):
        match = np.mean(win.rows["ok"] & np.all(
            win.rows["message_bits"] == key_bits[None], axis=1))
        print(f"checked {len(ref)} images; share decoding to the "
              f"embedded key {float(match)!r}", file=sys.stderr, flush=True)
    return {
        "max_logit_diff": {"value": got["max_logit_diff"],
                           "limit": limits["max_logit_diff"]},
        "rs_mismatch": {"value": got["rs_mismatch"], "limit": 0},
        "missing": {"value": int(win.wrong_outcome), "limit": 0},
        "empty_sample": {"value": int(len(ref) == 0), "limit": 0},
    }
