"""Fused extractor decode kernel: fp32 agreement with the unfused
``extractor_forward`` under the cross-program contract (docs/api.md),
semantic parity with the conv-formulation oracle, the bf16 precision
policy, packed-params round-trip, and end-to-end engine agreement
through the detection pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.detect import CROSS_PROGRAM_LOGIT_ATOL
from repro.core.extractor import (extractor_forward, init_extractor,
                                  pack_params, unpack_params)
from repro.core.rs.codec import DEFAULT_CODE
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def assert_same_decode(a, b, err_msg=""):
    """Two programs' logits: same hard bits, values within the
    cross-program tolerance."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_array_equal(a > 0, b > 0, err_msg=err_msg)
    np.testing.assert_allclose(a, b, rtol=0, atol=CROSS_PROGRAM_LOGIT_ATOL,
                               err_msg=err_msg)


def _tiles(b, l, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(-1, 1, (b, l, l, 3)).astype(np.float32))


def _params(l, *, corr=True, n_bits=60, channels=8, depth=2, seed=0):
    return init_extractor(jax.random.key(seed), n_bits=n_bits,
                          channels=channels, depth=depth,
                          tile=l if corr else 0)


# ---------------------------------------------------------------------------
# kernel-level contracts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("corr", [True, False])
@pytest.mark.parametrize("tile", [32, 64, 128])
def test_fused_fp32_bit_exact_vs_unfused(tile, corr):
    """The tentpole contract: the fp32 kernel matches the unfused
    extractor_forward graph (same per-tap dots and epilogue) under the
    cross-program contract, with and without the correlation bank, at
    every tile size."""
    params = _params(tile, corr=corr)
    tiles = _tiles(2, tile, seed=tile)
    packed = pack_params(params)
    fused = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, packed))(tiles))
    unfused = np.asarray(jax.jit(extractor_forward)(params, tiles))
    assert_same_decode(fused, unfused)
    # and both match the original conv/einsum formulation semantically
    oracle = np.asarray(jax.jit(kref.fused_extractor_ref)(params, tiles))
    np.testing.assert_allclose(fused, oracle, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("b", [1, 3, 5])
def test_fused_ragged_batches(b):
    """Batch-stability: every row of a size-b batch matches the same
    row of a larger batch (ragged serving slices must be inert)."""
    params = _params(32)
    packed = pack_params(params)
    f = jax.jit(lambda t: kops.fused_extractor(t, packed))
    full = np.asarray(f(_tiles(5, 32)))
    part = np.asarray(f(_tiles(5, 32)[:b]))
    assert_same_decode(part, full[:b])


def test_fused_bf16_logit_tolerance():
    """bf16 packs compute the matmuls at bf16 with fp32 accumulation:
    logits stay within a small absolute tolerance of fp32 and almost
    every bit sign is preserved (RS absorbs the stragglers)."""
    params = _params(32, channels=16, depth=3)
    tiles = _tiles(4, 32, seed=3)
    f32 = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pack_params(params, "fp32")))(tiles))
    b16 = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pack_params(params, "bf16")))(tiles))
    assert b16.dtype == np.float32  # accumulation/output stay fp32
    np.testing.assert_allclose(b16, f32, atol=0.05)
    assert ((b16 > 0) == (f32 > 0)).mean() > 0.97


def test_pack_params_roundtrip():
    """pack_params -> unpack_params is exact for fp32 packs, and
    re-packing the unpacked params reproduces the pack bitwise."""
    params = _params(32, channels=16, depth=3)
    packed = pack_params(params)
    back = unpack_params(packed)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, back)
    repacked = pack_params(back)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), packed, repacked)
    # bf16 packs carry the compute dtype on every matmul operand
    p16 = pack_params(params, "bf16")
    for leaf in (p16["blocks"][0]["w"], p16["to_bits"]["w"],
                 p16["head"]["w"], p16["corr"]):
        assert leaf.dtype == jnp.bfloat16
    for leaf in (p16["blocks"][0]["b"], p16["head"]["b"],
                 p16["corr_scale"]):
        assert leaf.dtype == jnp.float32


# ---------------------------------------------------------------------------
# end-to-end engine equality through the detection pipeline
# ---------------------------------------------------------------------------


def _engine_outputs(cfg, params, raw, stream):
    from repro.core.detect import DetectionPipeline
    pipe = DetectionPipeline(cfg, params)
    try:
        out = {
            "batch": pipe.detect_batch(raw.copy(),
                                       key=jax.random.key(1)),
            "sharded": pipe.run_batch(raw, key=jax.random.key(1)),
            "lanes": {k: np.concatenate([r[k] for r in
                                         pipe.run_stream(stream,
                                                         lanes=2)
                                         ["results"]])
                      for k in ("message_bits", "ok", "logits")},
        }
    finally:
        pipe.close()
    return out


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_decode_engines_bit_identical(dtype):
    """Every engine — the fused single-jit fast path (detect_batch),
    the sharded run_batch, and the lane executor — produces equal
    message_bits/ok and logits within the cross-program tolerance for
    the same keys; in fp32 the fused kernel pipelines match the unfused
    ones the same way, and one program rerun is bitwise."""
    from repro.core.detect import DetectionConfig
    params = _params(16, n_bits=DEFAULT_CODE.codeword_bits,
                     channels=8, depth=2)
    rng = np.random.default_rng(7)
    raw = rng.integers(0, 256, (5, 64, 64, 3), dtype=np.uint8)
    stream = [rng.integers(0, 256, (4, 64, 64, 3), dtype=np.uint8)
              for _ in range(2)]

    def mk(**kw):
        base = dict(tile=16, img_size=32, resize_src=40, mode="qrmark",
                    rs_mode="device", code=DEFAULT_CODE,
                    decode_dtype=dtype)
        base.update(kw)
        return DetectionConfig(**base)

    fused = _engine_outputs(mk(), params, raw, stream)
    # detect_batch and run_batch share the key: two programs
    for f in ("message_bits", "ok"):
        np.testing.assert_array_equal(
            fused["batch"][f], fused["sharded"][f],
            err_msg=f"batch vs sharded {f} ({dtype})")
    assert_same_decode(fused["batch"]["logits"], fused["sharded"]["logits"],
                       err_msg=f"batch vs sharded ({dtype})")
    assert fused["lanes"]["logits"].shape == (8, DEFAULT_CODE.codeword_bits)
    if dtype == "fp32":
        unfused = _engine_outputs(mk(fused_decode=False), params, raw,
                                  stream)
        for eng in ("batch", "sharded", "lanes"):
            for f in ("message_bits", "ok"):
                np.testing.assert_array_equal(
                    fused[eng][f], unfused[eng][f],
                    err_msg=f"fused vs unfused {eng}/{f}")
            assert_same_decode(fused[eng]["logits"],
                               unfused[eng]["logits"],
                               err_msg=f"fused vs unfused {eng}")
    else:
        # the lane executor must reproduce the fused fast path bitwise
        # under bf16 too: rerun the stream through a fresh pipeline at a
        # different lane count and compare
        again = _engine_outputs(mk(), params, raw, stream)
        for f in ("message_bits", "ok", "logits"):
            np.testing.assert_array_equal(
                fused["lanes"][f], again["lanes"][f],
                err_msg=f"lanes rerun {f} (bf16)")
