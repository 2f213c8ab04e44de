"""Blocked decode schedule + precision ladder + autotune cache.

Contracts added by the schedule/precision PR:

* the blocked kernel (any batch_block x channel_tile point) agrees
  with the flat kernel and the unfused graph under the cross-program
  contract (same hard bits, logits within CROSS_PROGRAM_LOGIT_ATOL) —
  the schedule is a pure throughput knob;
* the int8 rung: pack-time per-channel weight scales round-trip, the
  decode path is batch-stable, and on a margin-bearing (watermarked)
  workload int8 reaches decision agreement 1.0 with fp32;
* the autotune cache: deterministic winner re-load (a hit skips the
  sweep), corrupt/stale caches fall back to flat loudly, and keys
  separate backend/dtype/tile;
* config plumbing: ``decode_schedule`` reaches every engine without
  perturbing fp32 results.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.detect import CROSS_PROGRAM_LOGIT_ATOL
from repro.core.extractor import (extractor_forward, init_encoder,
                                  init_extractor, pack_params,
                                  quantize_weight_int8, unpack_params,
                                  encoder_forward)
from repro.core.rs.codec import DEFAULT_CODE, rs_encode
from repro.kernels import autotune as autotune_lib
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.autotune import Schedule
from repro.kernels.fused_extractor import fused_extractor_blocked


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


def _margined_workload(tile=32, batch=6, channels=8, depth=2):
    """Watermarked tiles whose logits carry a real margin (encoder and
    extractor share the spread-spectrum bank) — the deployment regime
    the precision ladder is judged in (mirrors fig10's workload)."""
    code = DEFAULT_CODE
    enc = init_encoder(jax.random.key(1), n_bits=code.codeword_bits,
                       channels=4, depth=2, tile=tile)
    params = init_extractor(jax.random.key(2), n_bits=code.codeword_bits,
                            channels=channels, depth=depth, tile=tile,
                            patterns=enc["patterns"])
    params["corr_scale"] = params["corr_scale"] * 4.0
    rng = np.random.default_rng(0)
    msg = rng.integers(0, 2, code.message_bits)
    cw = jnp.asarray(rs_encode(code, msg))
    imgs = jnp.asarray(rng.uniform(-1, 1, (batch, tile, tile, 3))
                       .astype(np.float32))
    tiles, _ = encoder_forward(
        enc, imgs, jnp.broadcast_to(cw, (batch, code.codeword_bits)))
    return params, tiles, code


# ---------------------------------------------------------------------------
# blocked-schedule fp32 bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile", [32, 64, 128])
def test_blocked_fp32_bit_identical_to_flat(tile):
    """Every blocked schedule point reproduces the flat grid=(b,) kernel
    (and the unfused graph) at fp32, under the cross-program contract."""
    params = _params(tile)
    packed = pack_params(params)
    tiles = _tiles(4, tile, seed=tile)
    flat = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, packed))(tiles))
    assert_same_decode(flat, jax.jit(extractor_forward)(params, tiles))
    for bb, ct in ((1, 0), (2, 0), (4, 0), (1, 4), (2, 3)):
        blocked = np.asarray(jax.jit(
            lambda t, _bb=bb, _ct=ct: fused_extractor_blocked(
                t, packed, batch_block=_bb, channel_tile=_ct))(tiles))
        assert_same_decode(blocked, flat,
                           err_msg=f"bb={bb} ct={ct} tile={tile}")


@pytest.mark.parametrize("b", [1, 3, 5, 7])
def test_blocked_ragged_batches(b):
    """Ragged batches (b % batch_block != 0) are zero-padded and sliced;
    pad rows are inert so every row matches the flat kernel."""
    params = _params(32)
    packed = pack_params(params)
    full = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, packed))(_tiles(7, 32)))
    sched = Schedule(batch_block=4, channel_tile=0)
    part = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, packed, schedule=sched))(
            _tiles(7, 32)[:b]))
    assert_same_decode(part, full[:b])


def test_ops_schedule_dispatch():
    """kops.fused_extractor(schedule=None) runs the flat kernel;
    a Schedule runs the blocked kernel — fp32 outputs agree."""
    params = _params(32)
    packed = pack_params(params)
    tiles = _tiles(3, 32)
    a = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, packed))(tiles))
    c = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, packed, schedule=Schedule(2, 0, True)))(tiles))
    assert_same_decode(a, c)


# ---------------------------------------------------------------------------
# int8 precision rung
# ---------------------------------------------------------------------------


def test_int8_weight_scale_roundtrip():
    """Symmetric per-channel quantization: dequantized weights are
    within half a quantization step of the originals, per channel."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(72, 16)).astype(np.float32) * 0.3)
    q, scale = quantize_weight_int8(w)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == (16,)
    deq = np.asarray(q, np.float32) * np.asarray(scale)[None, :]
    np.testing.assert_allclose(deq, np.asarray(w),
                               atol=float(np.asarray(scale).max()) / 2
                               + 1e-7)


def test_int8_pack_structure_and_unpack():
    """int8 packs: conv/to_bits weights int8 + fp32 scales, head and
    corr stay fp32; unpack_params dequantizes to q * scale exactly."""
    params = _params(32, channels=16, depth=3)
    pk = pack_params(params, "int8")
    for entry in (*pk["blocks"], pk["to_bits"]):
        assert entry["w"].dtype == jnp.int8
        assert entry["scale"].dtype == jnp.float32
        assert entry["b"].dtype == jnp.float32
    assert pk["head"]["w"].dtype == jnp.float32
    assert pk["corr"].dtype == jnp.float32
    back = unpack_params(pk)
    w0 = np.asarray(pk["blocks"][0]["w"], np.float32) * \
        np.asarray(pk["blocks"][0]["scale"])[None, :]
    np.testing.assert_array_equal(
        np.asarray(back["blocks"][0]["w"]).reshape(-1, 16), w0)


def test_int8_batch_stable_and_schedules_agree():
    """The int8 path quantizes activations per ROW, so it stays
    batch-stable: a sub-batch and the flat vs blocked schedules (full
    width or channel-tiled) agree under the cross-program contract."""
    params = _params(32, channels=16, depth=3)
    pk = pack_params(params, "int8")
    tiles = _tiles(5, 32, seed=4)
    flat = jax.jit(lambda t: kops.fused_extractor(t, pk))
    full = np.asarray(flat(tiles))
    assert_same_decode(flat(tiles[:2]), full[:2])
    blocked = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pk, schedule=Schedule(2, 0, True)))(tiles))
    assert_same_decode(blocked, full)
    ct = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pk, schedule=Schedule(1, 4, True)))(tiles))
    assert_same_decode(ct, full)


def test_int8_matches_dequant_oracle():
    """int8 decode tracks the dequantized-weight fp32 oracle within the
    activation-quantization noise floor."""
    params = _params(32, channels=16, depth=3)
    pk = pack_params(params, "int8")
    tiles = _tiles(4, 32, seed=5)
    got = np.asarray(jax.jit(
        lambda t: kops.fused_extractor(t, pk))(tiles))
    want = np.asarray(jax.jit(
        lambda t: kref.fused_extractor_int8_ref(pk, t))(tiles))
    np.testing.assert_allclose(got, want, atol=0.15, rtol=0.05)


def test_int8_decision_agreement_on_margined_workload():
    """The acceptance contract for the bottom rung: on watermarked
    (margin-bearing) tiles, int8 and fp32 produce identical RS
    decisions (decision agreement 1.0) and near-identical hard bits."""
    params, tiles, code = _margined_workload()
    l32 = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pack_params(params, "fp32")))(tiles))
    l8 = np.asarray(jax.jit(lambda t: kops.fused_extractor(
        t, pack_params(params, "int8")))(tiles))
    bit_acc = float(((l8 > 0) == (l32 > 0)).mean())
    assert bit_acc > 0.98
    dev_rs = jax.jit(lambda b: kops.rs_decode(b, code=code))
    r32 = dev_rs((jnp.asarray(l32) > 0).astype(jnp.int32))
    r8 = dev_rs((jnp.asarray(l8) > 0).astype(jnp.int32))
    assert np.array_equal(np.asarray(r32["message_bits"]),
                          np.asarray(r8["message_bits"]))
    assert np.array_equal(np.asarray(r32["ok"]), np.asarray(r8["ok"]))


# ---------------------------------------------------------------------------
# autotune: Schedule strings + cache behavior
# ---------------------------------------------------------------------------


def test_schedule_string_roundtrip():
    for sc in (Schedule(1, 0, True), Schedule(2, 32, False),
               Schedule(8, 16, True)):
        assert Schedule.from_string(sc.to_string()) == sc
    assert Schedule.from_string("bb2-ct32-db") == Schedule(2, 32, True)
    assert Schedule.from_string("bb4-ct0") == Schedule(4, 0, False)
    for bad in ("", "flat", "auto", "bb2", "ctx-bb1", "bb0-ct0",
                "bbx-ct1", "bb1-ct2-xx", "bb1-ct-1"):
        with pytest.raises(ValueError):
            Schedule.from_string(bad)


def test_schedule_keys_distinguish_axes():
    base = dict(backend="cpu", dtype="fp32", tile=64, channels=64,
                depth=7, n_bits=60)
    k0 = autotune_lib.schedule_key(**base)
    for axis, val in (("backend", "tpu"), ("dtype", "int8"),
                      ("tile", 32), ("channels", 32), ("depth", 3),
                      ("n_bits", 75)):
        assert autotune_lib.schedule_key(**{**base, axis: val}) != k0


def test_autotune_cache_hit_skips_sweep(tmp_path, monkeypatch):
    """First call sweeps and persists; the second reloads the winner
    deterministically WITHOUT sweeping (sweep stubbed to explode)."""
    params = _params(16, channels=4, depth=2)
    pk = pack_params(params)
    cache = tmp_path / "sched.json"
    logs = []
    sc1 = autotune_lib.autotune(pk, tile=16, batch=2, dtype="fp32",
                                cache_path=cache, iters=1, quick=True,
                                log=logs.append)
    assert cache.exists()

    def boom(*a, **k):
        raise AssertionError("sweep must not run on a cache hit")

    monkeypatch.setattr(autotune_lib, "sweep", boom)
    logs2 = []
    sc2 = autotune_lib.autotune(pk, tile=16, batch=2, dtype="fp32",
                                cache_path=cache, iters=1, quick=True,
                                log=logs2.append)
    assert sc2 == sc1
    assert any("cache hit" in m for m in logs2)


def test_flat_can_win_the_sweep(tmp_path, monkeypatch):
    """Flat is a sweep candidate: when every blocked point times slower,
    the cached winner is "flat" and autotune returns None (the flat
    kernel) — the tuner never crowns a losing schedule."""
    params = _params(16, channels=4, depth=2)
    pk = pack_params(params)
    walls = iter([0.001] + [0.002] * 16)  # flat first, then candidates

    def fake_time(fn, *a, **k):
        return next(walls)

    monkeypatch.setattr(autotune_lib, "time_fn", fake_time)
    cache = tmp_path / "sched.json"
    sc = autotune_lib.autotune(pk, tile=16, batch=2, dtype="fp32",
                               cache_path=cache, quick=True,
                               log=lambda *a, **k: None)
    assert sc is None
    entry = json.loads(cache.read_text())["entries"]
    (rec,) = entry.values()
    assert rec["schedule"] == "flat"
    assert rec["speedup_vs_flat"] == 1.0
    # and the cached flat winner round-trips as a hit, not a miss
    logs = []
    sc2 = autotune_lib.autotune(pk, tile=16, batch=2, dtype="fp32",
                                cache_path=cache, quick=True,
                                log=logs.append)
    assert sc2 is None
    assert any("cache hit" in m for m in logs)


def test_corrupt_cache_falls_back_loudly(tmp_path, capsys):
    cache = tmp_path / "sched.json"
    cache.write_text("{not json")
    loaded = autotune_lib.load_cache(cache)
    assert loaded["entries"] == {}
    assert "corrupt" in capsys.readouterr().err


def test_stale_cache_version_falls_back_loudly(tmp_path, capsys):
    cache = tmp_path / "sched.json"
    cache.write_text(json.dumps(
        {"version": -1, "entries": {"k": {"schedule": "bb2-ct0-db"}}}))
    loaded = autotune_lib.load_cache(cache)
    assert loaded["entries"] == {}
    assert "stale" in capsys.readouterr().err


def test_invalid_cached_schedule_falls_back_loudly(tmp_path, capsys):
    cache = tmp_path / "sched.json"
    key = autotune_lib.schedule_key(
        backend=jax.default_backend(), dtype="fp32", tile=16,
        channels=4, depth=2, n_bits=60)
    cache.write_text(json.dumps(
        {"version": autotune_lib.CACHE_VERSION,
         "entries": {key: {"schedule": "garbage"}}}))
    sc = autotune_lib.resolve_schedule(
        "auto", dtype="fp32", tile=16, channels=4, depth=2, n_bits=60,
        cache_path=cache)
    assert sc is None
    assert "invalid" in capsys.readouterr().err


def test_resolve_schedule_modes(tmp_path, capsys):
    kw = dict(dtype="fp32", tile=16, channels=4, depth=2, n_bits=60)
    assert autotune_lib.resolve_schedule("flat", **kw) is None
    assert autotune_lib.resolve_schedule("", **kw) is None
    assert autotune_lib.resolve_schedule(
        "bb2-ct8-db", **kw) == Schedule(2, 8, True)
    # auto with no cache configured / an empty cache: loud flat fallback
    assert autotune_lib.resolve_schedule("auto", **kw) is None
    assert "auto" in capsys.readouterr().err
    empty = tmp_path / "none.json"
    assert autotune_lib.resolve_schedule(
        "auto", **kw, cache_path=empty) is None
    assert "no cached schedule" in capsys.readouterr().err
    with pytest.raises(ValueError):
        autotune_lib.resolve_schedule("bogus", **kw)


# ---------------------------------------------------------------------------
# engine plumbing
# ---------------------------------------------------------------------------


def test_engines_identical_under_tuned_schedule():
    """decode_schedule reaches detect_batch / run_batch / the lane
    executor without perturbing fp32 results: a tuned-schedule pipeline
    matches the flat-schedule one on every engine output (equal
    decisions, logits within the cross-program tolerance)."""
    from repro.core.detect import DetectionConfig, DetectionPipeline
    params = _params(16, n_bits=DEFAULT_CODE.codeword_bits,
                     channels=8, depth=2)
    rng = np.random.default_rng(7)
    raw = rng.integers(0, 256, (5, 64, 64, 3), dtype=np.uint8)

    def run(schedule):
        cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                              decode_schedule=schedule)
        pipe = DetectionPipeline(cfg, params)
        try:
            key = jax.random.key(1)
            return {"batch": pipe.detect_batch(raw.copy(), key=key),
                    "sharded": pipe.run_batch(raw, key=key)}
        finally:
            pipe.close()

    flat, tuned = run("flat"), run("bb2-ct0-db")
    for eng in ("batch", "sharded"):
        for f in ("message_bits", "ok"):
            np.testing.assert_array_equal(
                np.asarray(flat[eng][f]), np.asarray(tuned[eng][f]),
                err_msg=f"{eng}/{f}")
        assert_same_decode(flat[eng]["logits"], tuned[eng]["logits"],
                           err_msg=eng)


def test_config_rejects_bad_schedule():
    from repro.core.detect import DetectionConfig, DetectionPipeline
    params = _params(16, n_bits=DEFAULT_CODE.codeword_bits,
                     channels=4, depth=2)
    with pytest.raises(ValueError):
        DetectionPipeline(
            DetectionConfig(tile=16, img_size=32, resize_src=40,
                            decode_schedule="not-a-schedule"), params)
