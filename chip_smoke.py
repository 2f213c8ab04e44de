#!/usr/bin/env python3
"""Smoke run of the QRMark detection system on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the paths that span four chips

One chip, in one process: (a) the offline ``DetectionService`` serves a
few batches of 32 images; (b) a ``DetectionServer`` answers a few dozen
open-loop requests; (c) every image of (a) and (b) is decoded again by
the plain float32 reference (``kernels/ref.py`` under "highest" matmul
precision, then the numpy RS codec) and the decisions must agree.

``--chips 4`` runs only the sharded ``run_batch`` over a 4-chip mesh and
a 4-replica ``FleetRouter``, each against the one-device result on the
same images and keys.

The geometry is the ``DetectionConfig`` default (raw 288^2 cropped to
256^2, tile 64, random_grid, RS(15,12) over GF(16), on-device RS, fp32
flat decode) with the extractor at its full width (64 channels, depth
7) and its correlation bank.  Weights are random from a seed; a known
48-bit key is embedded into seeded synthetic images through the
encoder's pattern bank, which the extractor's bank is tied to, so the
run decodes real messages.

Exits non-zero, printing no result, where JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# Largest |logit| difference allowed between the system and the
# reference.  Both compute in float32 with full-precision dots, but in
# different orders: the kernel accumulates nine tap dots per conv layer
# and one dot for the correlation bank, the reference runs lax.conv and
# an einsum.  On a v5e chip that reassociation moves logits of
# magnitude ~1 by under 3e-6; a dot that ran at bf16 precision would
# move them by ~1e-2.  2e-3 sits between the two with margin on both
# sides.
LOGIT_ATOL = 2e-3
# share of images that must decode to the embedded key
MIN_KEY_MATCH = 0.9


def log(msg: str):
    print(msg, flush=True)


# -- workload ---------------------------------------------------------------


def make_workload(cfg, n_images: int, *, channels: int = 64,
                  depth: int = 7, seed: int = 0):
    """(extractor params, 48-bit key, uint8 raw images (n, R, R, 3)).

    Raw images are ``cfg.resize_src`` square, so the resize is the
    identity and the center crop's tile grid is the grid the key is
    embedded on: every tile of every image carries the RS codeword of
    the key, added through the encoder's pattern bank."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import tiling
    from repro.core.extractor import (encoder_forward, init_encoder,
                                      init_extractor)
    from repro.core.rs.codec import rs_encode
    from repro.data.pipeline import synth_image

    code, tile, img = cfg.code, cfg.tile, cfg.img_size
    raw_hw = cfg.resize_src
    off = (raw_hw - img) // 2
    enc = init_encoder(jax.random.key(seed + 1),
                       n_bits=code.codeword_bits, channels=8, depth=2,
                       tile=tile)
    params = init_extractor(jax.random.key(seed + 2),
                            n_bits=code.codeword_bits, channels=channels,
                            depth=depth, tile=tile,
                            patterns=enc["patterns"])
    # the untrained conv path adds noise of the correlation's own size
    # to every logit; a head scaled down 10x keeps it in the logits
    # (so the comparison checks it) without flipping bits
    params["head"]["w"] = params["head"]["w"] * 0.1
    key_bits = np.random.default_rng(seed).integers(
        0, 2, code.message_bits).astype(np.int32)
    cw = jnp.asarray(rs_encode(code, key_bits))
    raw = np.stack([synth_image(i, raw_hw, seed=seed)
                    for i in range(n_images)])
    x = jnp.asarray(raw[:, off: off + img, off: off + img],
                    jnp.float32) / 127.5 - 1.0
    g = img // tile

    @jax.jit
    def embed(x):
        flat = tiling.grid_partition(x, tile).reshape(-1, tile, tile, 3)
        xw, _ = encoder_forward(
            enc, flat, jnp.broadcast_to(cw, (flat.shape[0], cw.shape[0])))
        return xw.reshape(-1, g, g, tile, tile, 3).transpose(
            0, 1, 3, 2, 4, 5).reshape(-1, img, img, 3)

    xw = np.asarray(embed(x))
    raw[:, off: off + img, off: off + img] = np.clip(
        np.round((xw + 1.0) * 127.5), 0, 255).astype(np.uint8)
    return params, key_bits, raw


# -- phases -----------------------------------------------------------------


def _resolved(handle) -> bool:
    """A done request handle that holds a result, not an error."""
    try:
        handle.result(timeout=0)
        return True
    except Exception:
        return False


def run_offline(cfg, params, raw, batch: int):
    """Phase (a): DetectionService warmup + serve.  Returns (rows, per-
    image keys, report, service); rows are the per-image results in
    order."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import DetectionService

    svc = DetectionService(cfg, params)
    t0 = time.perf_counter()
    svc.warmup(raw[:batch])
    warm_s = time.perf_counter() - t0
    stages = svc.pipe.stages
    batches = [raw[i: i + batch] for i in range(0, len(raw), batch)]
    got = {}
    rep = svc.serve(batches, use_scheduler=False,
                    on_result=lambda i, res: got.__setitem__(i, res))
    keys = [stages.image_keys(stages.batch_key(i), b.shape[0])
            for i, b in enumerate(batches)]
    rows = {k: np.concatenate([got[i][k] for i in range(len(batches))])
            for k in ("message_bits", "ok", "logits")}
    svc.pipe.close()
    return rows, jnp.concatenate(keys), {
        "warmup_s": round(warm_s, 3), "images": rep.images,
        "wall_s": round(rep.wall_s, 3), "lanes": rep.lanes}, svc


def run_online(cfg, params, pool, *, n_requests: int, qps: float):
    """Phase (b): DetectionServer under open-loop Poisson load, one image
    per request.  Returns (rows, per-image keys, raw images, report) of
    the requests that completed."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import open_loop_load
    from repro.serving import BatcherConfig, DetectionServer

    srv = DetectionServer(cfg, params, batcher=BatcherConfig(
        max_batch=16, max_wait_ms=20.0, bucket=16))
    t0 = time.perf_counter()
    buckets = srv.warmup(pool[0])
    warm_s = time.perf_counter() - t0
    srv.start()
    srv.metrics.reset()
    load = open_loop_load(
        srv, qps=qps, duration_s=n_requests / qps,
        make_images=lambda k: pool[k % len(pool)][None], seed=1)
    drained = srv.drain(timeout=600.0)
    stats = srv.stats()
    srv.close()
    handles = load["handles"]
    unresolved = sum(not h.done() for h in handles)
    done = [h for h in handles if h.done() and _resolved(h)]
    reg = srv.registry
    rows = {k: np.concatenate([h.result(timeout=0)[k] for h in done])
            for k in ("message_bits", "ok", "logits")}
    keys = jnp.concatenate([reg.image_keys(reg.batch_key(h.rid), 1)
                            for h in done])
    raw = np.stack([pool[h.rid % len(pool)] for h in done])
    counters = stats["counters"]
    report = {
        "warmup_s": round(warm_s, 3), "buckets": buckets,
        "offered": load["offered"], "rejected": load["rejected"],
        "failed": int(counters.get("requests_failed", 0)),
        "completed": int(counters.get("requests_completed", 0)),
        "unresolved": int(unresolved), "drained": bool(drained),
        "latency_ms_p50": round(
            stats.get("request_latency_s", {}).get("p50", float("nan"))
            * 1e3, 3)}
    return rows, keys, raw, report


def reference(cfg, params, raw, keys, chunk: int = 32):
    """The plain float32 reference: jax.image resize + crop + tile
    slice, lax.conv extractor with the einsum correlation bank, at
    "highest" matmul precision, then the numpy RS codec per image."""
    import jax
    import numpy as np

    from repro.core import tiling
    from repro.core.rs.codec import rs_decode
    from repro.kernels import ref as kref

    @jax.jit
    def logits_ref(raw, keys):
        offs = tiling.tile_first_offsets(cfg.strategy, keys,
                                         img_size=cfg.img_size,
                                         tile=cfg.tile)
        tiles = kref.fused_tile_preprocess_ref(
            raw, offs, resize=cfg.resize_src, crop=cfg.img_size,
            tile=cfg.tile)
        return kref.fused_extractor_ref(params, tiles)

    with jax.default_matmul_precision("highest"):
        logits = np.concatenate([
            np.asarray(logits_ref(raw[i: i + chunk], keys[i: i + chunk]))
            for i in range(0, len(raw), chunk)])
    dec = [rs_decode(cfg.code, b) for b in (logits > 0).astype(np.int32)]
    return {"message_bits": np.stack([d.message_bits for d in dec]),
            "ok": np.array([d.ok for d in dec]), "logits": logits}


def compare(name: str, got, ref, key_bits=None) -> bool:
    """Equal decisions on every image and logits within LOGIT_ATOL;
    with ``key_bits``, at least MIN_KEY_MATCH of the images decode to
    the key."""
    import numpy as np

    same_msg = np.all(got["message_bits"] == ref["message_bits"], axis=1)
    same_ok = got["ok"] == ref["ok"]
    diff = float(np.max(np.abs(got["logits"] - ref["logits"])))
    n = len(same_ok)
    ok = bool(same_msg.all() and same_ok.all() and diff <= LOGIT_ATOL)
    line = (f"{name}: images={n} message_bits_equal={int(same_msg.sum())}"
            f" ok_equal={int(same_ok.sum())} max_logit_diff={diff!r}"
            f" (atol {LOGIT_ATOL})")
    if key_bits is not None:
        match = np.mean(got["ok"] & np.all(
            got["message_bits"] == key_bits[None], axis=1))
        line += f" key_match={float(match)!r}"
        ok = ok and match >= MIN_KEY_MATCH
    log(line + (" PASS" if ok else " FAIL"))
    return ok


def kernel_programs(cfg, svc, raw, keys) -> bool:
    """Each main-path program (ingest, decode, RS) must hold a compiled
    Mosaic kernel (``tpu_custom_call``): proof that none ran
    interpreted."""
    from repro.core.stages import make_device_rs

    reg = svc.pipe.stages
    x = reg.ingest_keyed(raw, keys)
    bits = reg.bits(reg.decode_keyed(x, keys))
    progs = {"ingest": reg.ingest_keyed.lower(raw, keys),
             "decode": reg.decode_keyed.lower(x, keys),
             "rs": make_device_rs(cfg.code).lower(bits)}
    ok = True
    for name, lowered in progs.items():
        n = lowered.compile().as_text().count("tpu_custom_call")
        log(f"kernel check: {name} program has {n} tpu_custom_call")
        ok = ok and n > 0
    return ok


def one_chip(cfg, params, key_bits, raw, *, batch: int, n_batches: int,
             n_requests: int, qps: float, check_kernels: bool) -> bool:
    n_off = batch * n_batches
    rows, keys, rep, svc = run_offline(cfg, params, raw[:n_off], batch)
    log(f"offline: {json.dumps(rep)}")
    ok = rep["images"] == n_off
    if check_kernels:
        ok = kernel_programs(cfg, svc, raw[:batch], keys[:batch]) and ok
    ok = compare("offline vs reference", rows,
                 reference(cfg, params, raw[:n_off], keys), key_bits) and ok

    rows, keys, oraw, rep = run_online(cfg, params, raw[n_off:],
                                       n_requests=n_requests, qps=qps)
    log(f"online: {json.dumps(rep)}")
    bad = rep["failed"] + rep["rejected"] + rep["unresolved"]
    ok = ok and bad == 0 and rep["completed"] == rep["offered"]
    ok = compare("online vs reference", rows,
                 reference(cfg, params, oraw, keys), key_bits) and ok
    return ok


def four_chips(cfg, params, key_bits, raw, *, batch: int,
               n_requests: int) -> bool:
    """Sharded run_batch over a 4-device mesh and a 4-replica fleet, each
    against the one-device staged path on the same images and keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.detect import DetectionPipeline
    from repro.launch.mesh import make_detection_mesh
    from repro.serving import BatcherConfig, FleetRouter, Replica

    devs = jax.devices()[:4]
    one = DetectionPipeline(cfg, params).stages

    def one_device(raw, keys):
        with jax.default_device(devs[0]):
            x = one.ingest_keyed(raw, keys)
            logits = one.decode_keyed(x, keys)
            msg, okv, _ = one.rs_correct(one.bits(logits))
            return {"message_bits": np.asarray(msg),
                    "ok": np.asarray(okv), "logits": np.asarray(logits)}

    # sharded run_batch
    pipe = DetectionPipeline(cfg, params)
    k = pipe.stages.batch_key(0)
    t0 = time.perf_counter()
    sharded = pipe.run_batch(raw[:batch], mesh=make_detection_mesh(devs),
                             key=k)
    log(f"sharded run_batch: {batch} images over {len(devs)} devices in "
        f"{time.perf_counter() - t0!r} s (compile included)")
    keys = pipe.stages.image_keys(k, batch)
    ok = compare("sharded vs one device", sharded,
                 one_device(raw[:batch], keys), key_bits)

    # 4-replica fleet, one replica pinned to each chip
    reps = [Replica(f"r{i}", cfg, params, device=devs[i],
                    batcher=BatcherConfig(max_batch=16, max_wait_ms=20.0,
                                          bucket=16))
            for i in range(4)]
    router = FleetRouter(reps)
    t0 = time.perf_counter()
    router.warmup(raw[0])
    log(f"fleet: warmup {time.perf_counter() - t0!r} s")
    router.start()
    pool = raw[batch: batch + n_requests]
    rkeys = [pipe.stages.batch_key(1000 + i) for i in range(len(pool))]
    handles = [router.submit(pool[i][None], key=rkeys[i])
               for i in range(len(pool))]
    drained = router.drain(timeout=600.0)
    stats = router.stats()
    per_rep = {r.name: int(r.srv.metrics.counter("requests_completed"))
               for r in reps}
    router.close()
    failed = [h for h in handles if not (h.done() and _resolved(h))]
    log(f"fleet: requests={len(handles)} drained={drained} "
        f"failed_or_unresolved={len(failed)} per_replica={per_rep} "
        f"reroutes={stats['reroutes']} spillovers={stats['spillovers']}")
    ok = ok and drained and not failed
    if not failed:
        rows = {f: np.concatenate([h.result(timeout=0)[f]
                                   for h in handles])
                for f in ("message_bits", "ok", "logits")}
        keys = jnp.concatenate([pipe.stages.image_keys(kk, 1)
                                for kk in rkeys])
        ok = compare("fleet vs one device", rows, one_device(pool, keys),
                     key_bits) and ok
    # every replica served traffic, and every chip holds buffers
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    log(f"fleet: peak_bytes_in_use per device {peaks}")
    ok = ok and min(per_rep.values()) > 0 and min(peaks) > 0
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {REPO / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1

    from repro.core.detect import DetectionConfig
    from repro.launch.compile_cache import init_compile_cache

    log(f"compile cache: {init_compile_cache()}")
    cfg = DetectionConfig()
    batch, n_batches, n_requests = 32, 3, 40
    log(f"device: {devs[0].device_kind} x{len(devs)}; geometry: raw "
        f"{cfg.resize_src}^2 -> crop {cfg.img_size}^2, tile {cfg.tile} "
        f"{cfg.strategy}, RS({cfg.code.n},{cfg.code.k}) GF(2^{cfg.code.m}),"
        f" rs_mode={cfg.rs_mode}, decode {cfg.decode_dtype} "
        f"{cfg.decode_schedule}, extractor 64 ch x depth 7 + corr bank")
    t0 = time.perf_counter()
    params, key_bits, raw = make_workload(
        cfg, batch * n_batches + n_requests, seed=args.seed)
    log(f"workload: {len(raw)} images in {time.perf_counter() - t0!r} s")
    if args.chips == 4:
        ok = four_chips(cfg, params, key_bits, raw, batch=batch,
                        n_requests=n_requests)
    else:
        ok = one_chip(cfg, params, key_bits, raw, batch=batch,
                      n_batches=n_batches, n_requests=n_requests,
                      qps=40.0, check_kernels=True)
    if not ok:
        log("chip_smoke: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
