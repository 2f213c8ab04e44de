"""Subprocess helper for tests/test_lanes.py: forces a 4-device CPU
topology (XLA_FLAGS must be set before jax initialises, hence the
separate process) and checks that the data-parallel sharded
``DetectionPipeline.run_batch`` matches the single-device path (equal
decisions, logits within the cross-program tolerance), including for a
ragged batch that needs padding, and that the tile-first fused ingest
matches the staged full-image path on the sharded mesh.

Not named test_*.py on purpose — pytest must not collect it.
"""
import dataclasses
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SRC = str(Path(__file__).resolve().parents[1] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.detect import (CROSS_PROGRAM_LOGIT_ATOL,  # noqa: E402
                               DetectionConfig, DetectionPipeline)
from repro.core.extractor import init_extractor  # noqa: E402
from repro.core.rs.codec import DEFAULT_CODE  # noqa: E402
from repro.launch.mesh import make_detection_mesh  # noqa: E402


def main():
    devs = jax.devices()
    assert len(devs) == 4, f"expected 4 forced CPU devices, got {len(devs)}"
    params = init_extractor(jax.random.key(0),
                            n_bits=DEFAULT_CODE.codeword_bits,
                            channels=8, depth=2)
    cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                          mode="qrmark", rs_mode="device")
    rng = np.random.default_rng(0)

    mesh4 = make_detection_mesh(devs)
    mesh1 = make_detection_mesh(devs[:1])

    for b in (8, 6):  # divisible and ragged (6 -> padded to 8 on 4 devs)
        raw = rng.integers(0, 256, (b, 64, 64, 3), dtype=np.uint8)
        p_multi = DetectionPipeline(cfg, params)
        p_single = DetectionPipeline(cfg, params)
        out_m = p_multi.run_batch(raw, mesh=mesh4)
        out_s = p_single.run_batch(raw, mesh=mesh1)
        assert np.array_equal(out_m["message_bits"], out_s["message_bits"]), \
            f"b={b}: sharded message bits diverge"
        assert np.array_equal(out_m["ok"], out_s["ok"]), f"b={b}: ok diverge"
        assert np.array_equal(out_m["n_corrected"], out_s["n_corrected"])
        assert out_m["logits"].shape == (b, DEFAULT_CODE.codeword_bits)
        # the sharded program may reassociate float sums differently
        np.testing.assert_allclose(out_m["logits"], out_s["logits"],
                                   rtol=0, atol=CROSS_PROGRAM_LOGIT_ATOL,
                                   err_msg=f"b={b}: logits diverge")

    # tile-first fused ingest == staged full-image ingest on the 4-device
    # mesh (cfg above runs tile-first by default; rerun staged and compare)
    assert DetectionPipeline(cfg, params).tile_first
    raw = rng.integers(0, 256, (8, 64, 64, 3), dtype=np.uint8)
    key = jax.random.key(11)
    cfg_staged = dataclasses.replace(cfg, tile_first=False)
    out_tf = DetectionPipeline(cfg, params).run_batch(
        raw, mesh=mesh4, key=key)
    out_st = DetectionPipeline(cfg_staged, params).run_batch(
        raw, mesh=mesh4, key=key)
    for f in ("message_bits", "ok", "n_corrected"):
        assert np.array_equal(out_tf[f], out_st[f]), \
            f"sharded tile-first vs staged: {f} diverges"
    np.testing.assert_allclose(out_tf["logits"], out_st["logits"], rtol=0,
                               atol=CROSS_PROGRAM_LOGIT_ATOL,
                               err_msg="sharded tile-first vs staged")
    print("OK")


if __name__ == "__main__":
    main()
