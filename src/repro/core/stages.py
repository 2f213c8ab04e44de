"""Unified stage registry — the single definition of the detection
stage functions (QRMark §5.1/§6.2).

Every execution engine derives its compute from one
:class:`StageRegistry` built once per (config, params):

* ``DetectionPipeline.detect_batch`` — the keyed staged fns, or the
  fully fused single-jit fast path (``fused_keyed``);
* ``DetectionPipeline.build_stages`` / ``run_stream`` — the payload
  stage graph (:meth:`StageRegistry.build_stages`) for the lane
  executor;
* ``DetectionPipeline.run_batch`` — the same keyed staged fns over a
  sharded batch;
* ``serving.DetectionServer`` — the same payload stage graph, driven by
  a long-lived service-mode executor.

Before this module the ingest/decode/RS bodies were restated in four
places inside ``core/detect.py``; now they exist exactly once.

RNG-key discipline (the bit-identity contract): offline, batch k uses
``fold_in(key(seed), k)`` and image i of that batch uses
``fold_in(batch_key, i)``.  Key *derivation* is its own jitted function
(:meth:`image_keys`) and every stage function takes the derived
per-image key array as an explicit input — ``fold_in`` is integer
hashing, bit-exact wherever it runs, so a caller that supplies keys
from somewhere else (the online server derives them per *request*, not
per coalesced batch) gets results bit-identical to the offline engines
on the same images with the same keys, no matter how requests were
batched together.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import extractor as extractor_lib
from repro.core import lanes as lanes_lib, spans, tiling, transforms
from repro.core.extractor import extractor_forward
from repro.core.rs.codec import RSCode, rs_decode
from repro.core.rs import jax_rs
from repro.core.rs.cpu_pool import RSCorrectionPool

STAGE_NAMES = ("ingest", "decode", "rs")

# the payload fields a stage graph's sink makes numpy
_FETCHED = ("msg", "ok", "ncorr", "logits", "embed")

# the code the Pallas Berlekamp-Welch kernel is specialised for
_PALLAS_RS_CODE = (4, 15, 12)  # (m, n, k)


@functools.lru_cache(maxsize=None)
def make_device_rs(code: RSCode) -> Callable:
    """The on-device batched RS engine: the Pallas Berlekamp-Welch
    kernel for the code it is specialised for, ``jax_rs`` otherwise.
    Jit-able and safe to inline into a larger jitted graph — every
    engine (fused fast path, lane executor, sharded run_batch, online
    server) must use the same decoder so failure tie-breaking never
    diverges.  One jitted decoder per code and process, so every
    registry (each server, replica or pipeline) reuses its compiled
    programs instead of compiling the kernel again."""
    if (code.m, code.n, code.k) == _PALLAS_RS_CODE:
        from repro.kernels import ops as kops

        def decode(bits):
            with jax.named_scope("rs"):
                return kops.rs_decode(bits, code=code)

        # jitted so sharded inputs (run_batch) go through the SPMD
        # partitioner instead of eager multi-device dispatch
        return jax.jit(decode)
    return jax_rs.make_batch_decoder(code)


def _pad_pow2(arr, axis: int = 0):
    """Pad ``arr`` along ``axis`` up to the next power of two by
    repeating the last row; returns (padded, true_n).  Escalation
    sub-batches shrink round over round — pow2 buckets bound the number
    of jit shapes no matter how many images fail each round."""
    n = arr.shape[axis]
    target = 1
    while target < n:
        target *= 2
    if target == n:
        return arr, n
    reps = [arr] + [arr[n - 1: n]] * (target - n)
    if isinstance(arr, np.ndarray):
        return np.concatenate(reps, axis=axis), n
    return jnp.concatenate(reps, axis=axis), n


@dataclasses.dataclass(frozen=True)
class EscalationPolicy:
    """When and how far to escalate beyond the single-tile fast path
    (``DetectionConfig.escalate_tiles`` / ``escalate_margin``).

    ``max_tiles`` is the per-image tile budget (= max escalation
    rounds: round r decodes tile r of the per-image plan, so an image
    uses between 1 and ``max_tiles`` tiles).  An image escalates after
    a round when RS failed on its accumulated soft bits, or — with
    ``margin > 0`` — when the mean absolute accumulated logit is below
    ``margin`` (a thin verification margin, even if RS formally
    succeeded).  ``max_tiles == 1`` disables escalation entirely: no
    plan is derived and every engine's hot path is bit-identical to a
    pipeline built before this policy existed."""
    max_tiles: int = 1
    margin: float = 0.0

    @property
    def enabled(self) -> bool:
        return self.max_tiles > 1

    def wants_escalation(self, ok, logits) -> np.ndarray:
        """Per-image bool mask over (ok, accumulated logits)."""
        need = ~np.asarray(ok, bool)
        if self.margin > 0.0:
            need = need | (np.abs(np.asarray(logits)).mean(axis=-1)
                           < self.margin)
        return need


class StageRegistry:
    """The detection stage functions, built once per (cfg, params).

    Holds the jitted keyed stage fns, the packed decode weights, the
    configured RS engine (including the CPU pool's state), and the
    fused fast path.  Engine objects (pipeline, server) own a registry
    and derive everything from it."""

    def __init__(self, cfg, params):
        if cfg.mode not in ("sequential", "tiled", "qrmark"):
            raise ValueError(f"unknown pipeline mode {cfg.mode!r}")
        if cfg.rs_mode not in ("device", "cpu_pool", "cpu_sync"):
            raise ValueError(f"unknown rs_mode {cfg.rs_mode!r}")
        if cfg.decode_dtype not in extractor_lib.DECODE_DTYPES:
            raise ValueError(f"unknown decode_dtype {cfg.decode_dtype!r}")
        k = getattr(cfg, "escalate_tiles", 1)
        if k < 1:
            raise ValueError(f"escalate_tiles must be >= 1, got {k}")
        if getattr(cfg, "escalate_margin", 0.0) > 0.0 and k == 1:
            raise ValueError(
                "escalate_margin > 0 has no effect with "
                "escalate_tiles=1 — the margin trigger only fires "
                "when there is a tile budget to escalate into; set "
                "escalate_tiles > 1 (or margin to 0)")
        thr = getattr(cfg, "cache_embedding_threshold", 0.0)
        if not 0.0 <= thr <= 1.0:
            raise ValueError(
                f"cache_embedding_threshold must be in [0, 1] (cosine "
                f"floor; 0 disables the tier), got {thr}")
        if getattr(cfg, "cache_capacity", 1) < 1 or \
                getattr(cfg, "cache_embedding_capacity", 1) < 1:
            raise ValueError("cache capacities must be >= 1")
        if k > 1:
            if cfg.mode == "sequential":
                raise ValueError(
                    "escalate_tiles > 1 needs a tile-decoding mode "
                    "(tiled/qrmark); sequential decodes the full image")
            cap = tiling.max_escalation_tiles(
                cfg.strategy, (cfg.img_size, cfg.img_size), cfg.tile)
            if k > cap:
                raise ValueError(
                    f"escalate_tiles={k} exceeds the {cap} distinct "
                    f"{cfg.strategy!r} tiles of a {cfg.img_size}^2/"
                    f"{cfg.tile}^2 image")
        self.policy = EscalationPolicy(
            max_tiles=k, margin=getattr(cfg, "escalate_margin", 0.0))
        self.cfg = cfg
        self.params = params
        self.code = cfg.code
        self.base_key = jax.random.key(cfg.seed)
        self.tile_first = (cfg.tile_first and cfg.mode == "qrmark"
                           and cfg.fused_preprocess)
        self.fused_decode = cfg.fused_decode and cfg.mode == "qrmark"
        self._rs_pool: Optional[RSCorrectionPool] = None
        self._device_rs = None
        self._sharded: Dict[Any, Callable] = {}   # mesh -> program
        self._pool_seq = 0            # RS-pool job id counter
        self._pool_lock = threading.Lock()
        self._build()

    # -- RNG-key discipline --------------------------------------------
    def batch_key(self, seq: int):
        """Offline key for batch ``seq``: fold_in(key(cfg.seed), seq)."""
        return jax.random.fold_in(self.base_key, seq)

    def image_keys(self, key, b: int):
        """Per-image keys fold_in(key, 0..b-1) — THE derivation every
        engine shares (jitted per b; fold_in is bit-exact regardless of
        the enclosing graph, so deriving here vs inline is identical)."""
        return self._image_keys_jit(key, b)

    def content_key(self, fingerprint: int):
        """Content-addressed request key:
        ``fold_in(key(cfg.seed), fingerprint32(content digest))``.
        The serving tier uses this for keyless requests when the exact
        result cache is on — identical pixels then deterministically
        produce identical per-image keys, which is what makes a cache
        hit bitwise equal to the cold path (``fold_in`` is integer
        hashing, so this is the same contract as :meth:`batch_key`
        with content taking the place of arrival order)."""
        return jax.random.fold_in(self.base_key,
                                  np.uint32(fingerprint & 0xFFFFFFFF))

    # -- build ----------------------------------------------------------
    def _build(self):
        cfg = self.cfg

        # decode-stage extractor, one fn for every engine: the fused
        # Pallas kernel on pre-packed params (qrmark; pack once per
        # registry build, dtype = the precision policy) or the unfused
        # extractor_forward graph (the same packed math as one XLA graph)
        if self.fused_decode:
            from repro.kernels import autotune as autotune_lib
            from repro.kernels import ops as kops
            self.packed_params = extractor_lib.pack_params(
                self.params, cfg.decode_dtype)
            # kernel schedule, resolved once per registry build: "flat"
            # -> None (the flat kernel), "auto" -> the autotune cache
            # (flat fallback with a printed hint on a miss), or an
            # explicit "bb<N>-ct<N>[-db]" point.  Only flat compiles for
            # TPU; the others are refused there rather than interpreted.
            self.decode_schedule = autotune_lib.resolve_schedule(
                getattr(cfg, "decode_schedule", "flat"),
                dtype=cfg.decode_dtype, tile=cfg.tile,
                channels=self.params["blocks"][0]["w"].shape[-1],
                depth=len(self.params["blocks"]),
                n_bits=self.params["head"]["b"].shape[0],
                cache_path=getattr(cfg, "autotune_cache", ""))
            sched = self.decode_schedule
            if jax.default_backend() == "tpu" and (
                    sched is not None or cfg.decode_dtype == "int8"):
                raise ValueError(
                    f"decode_schedule={cfg.decode_schedule!r} with "
                    f"decode_dtype={cfg.decode_dtype!r} does not compile "
                    f"for TPU: the blocked decode kernel and the int8 "
                    f"rung run in interpret mode on the CPU only; use "
                    f"decode_schedule='flat' with fp32 or bf16")

            def extract(tiles):
                return kops.fused_extractor(tiles, self.packed_params,
                                            schedule=sched)

            def extract_embed(tiles):
                return kops.fused_extractor(tiles, self.packed_params,
                                            schedule=sched,
                                            with_embed=True)
        else:
            self.packed_params = None
            self.decode_schedule = None

            def extract(tiles):
                return extractor_forward(self.params, tiles)

            def extract_embed(tiles):
                return extractor_lib.extractor_forward_embed(
                    self.params, tiles)

        def preprocess(raw):
            if cfg.fused_preprocess and cfg.mode == "qrmark":
                from repro.kernels import ops as kops
                return kops.fused_preprocess(raw, resize=cfg.resize_src,
                                             crop=cfg.img_size)
            return transforms.preprocess_reference(
                raw, resize=cfg.resize_src, crop=cfg.img_size)

        # ingest consumes the per-image fold_in keys as an input — the
        # derivation itself is image_keys(), shared by every caller.
        # Tile-first: offsets from the keys (static geometry only),
        # then one kernel straight to the decode input.  Each stage
        # function names its ops with a scope of the stage's name.
        def ingest_keyed(raw, keys):
            with jax.named_scope("ingest"):
                if self.tile_first:
                    from repro.kernels import ops as kops
                    offs = tiling.tile_first_offsets(
                        cfg.strategy, keys, img_size=cfg.img_size,
                        tile=cfg.tile)
                    return kops.fused_tile_preprocess(
                        raw, offs, resize=cfg.resize_src,
                        crop=cfg.img_size, tile=cfg.tile)
                return preprocess(raw)

        def decode_keyed(x, keys):
            with jax.named_scope("decode"):
                if self.tile_first or cfg.mode == "sequential":
                    tiles = x  # tiles from ingest / full-image decode
                else:
                    tiles, _ = tiling.select_tiles_per_image(
                        cfg.strategy, keys, x, cfg.tile)
                return extract(tiles)

        # embed-emitting decode: same tile selection, extractor returns
        # (logits, gap_embedding).  The logits ops are identical —
        # asserted by tests — so the serving tier can swap this in for
        # round-0 decode whenever the near-duplicate cache is on
        # without perturbing the bit-identity contract.
        def decode_keyed_embed(x, keys):
            with jax.named_scope("decode"):
                if self.tile_first or cfg.mode == "sequential":
                    tiles = x
                else:
                    tiles, _ = tiling.select_tiles_per_image(
                        cfg.strategy, keys, x, cfg.tile)
                return extract_embed(tiles)

        self.ingest_keyed = jax.jit(ingest_keyed)
        self.decode_keyed = jax.jit(decode_keyed)
        self.decode_keyed_embed = jax.jit(decode_keyed_embed)
        self.bits = jax.jit(lambda logits: (logits > 0).astype(jnp.int32))

        # -- escalation compute (cfg.escalate_tiles > 1) ---------------
        # The per-image k-tile plan depends only on the keys and static
        # geometry; column 0 is bit-identical to the single-tile draw,
        # so round 1 IS the unmodified fast path and rounds 2..k decode
        # plan columns 1..k-1.
        def plan_fn(keys):
            return tiling.escalation_offsets(
                cfg.strategy, keys, (cfg.img_size, cfg.img_size),
                cfg.tile, self.policy.max_tiles)

        def tiles_at(raw, offs):
            """(b, 2) or (b, k, 2) offsets -> decode-ready tiles, via
            the tile-first kernel or the staged preprocess + extract."""
            if self.tile_first:
                from repro.kernels import ops as kops
                return kops.fused_tile_preprocess(
                    raw, offs, resize=cfg.resize_src, crop=cfg.img_size,
                    tile=cfg.tile)
            x = preprocess(raw)
            if offs.ndim == 3:
                return tiling.extract_tiles_k(x, offs, cfg.tile)
            return tiling.extract_tiles(x, offs, cfg.tile)

        def decode_all_fn(raw, keys):
            p = plan_fn(keys)
            b, kk = p.shape[:2]
            return extract(tiles_at(raw, p)).reshape(b, kk, -1)

        self.escalation_plan = jax.jit(plan_fn)
        # tile r of the escalation plan, decode-ready — the
        # escalation-round ingest for BOTH the inline loop and the
        # server's re-submitted micro-batches (one jitted fn, so the
        # two escalation engines cannot drift).  The round index is
        # TRACED (dynamic_index into the plan), so one compile per
        # sub-batch shape covers every round — which keeps warmup and
        # the first escalation cheap.
        self.escalation_tiles = jax.jit(
            lambda raw, keys, r: tiles_at(raw, plan_fn(keys)[:, r]))
        # decode-ready tiles -> logits (the escalation-round decode)
        self.decode_tiles = jax.jit(extract)
        # all k tiles at once -> (b, k, n_bits): the always-k baseline
        # and the (b, k, 2) kernel fast path
        self.decode_all_keyed = jax.jit(decode_all_fn)

        self._image_keys_jit = jax.jit(
            lambda key, b: jax.vmap(
                lambda i: jax.random.fold_in(key, i))(jnp.arange(b)),
            static_argnums=1)

        if cfg.rs_mode == "device":
            self._device_rs = make_device_rs(self.code)
        elif cfg.rs_mode == "cpu_pool":
            self._rs_pool = RSCorrectionPool(self.code,
                                             n_threads=cfg.rs_threads)

        # fully fused fast path (qrmark + device RS): one jitted graph.
        # The raw-batch buffer is donated — ingest is its only reader,
        # so the runtime can recycle the largest in-flight buffer while
        # decode/RS still run.  CPU cannot reuse a donated uint8 input
        # (it would only warn once per compile), so donation is applied
        # on accelerator backends only.
        if cfg.mode == "qrmark" and cfg.rs_mode == "device":
            dev_decoder = self._device_rs  # one decoder for every engine

            def fused_keyed(raw, keys):
                x = ingest_keyed(raw, keys)
                logits = decode_keyed(x, keys)
                bits = (logits > 0).astype(jnp.int32)
                return dev_decoder(bits), logits

            # escalation re-reads the raw batch after round 1, so the
            # buffer can only be donated when escalation is off
            donate = (() if jax.default_backend() == "cpu"
                      or self.policy.enabled else (0,))
            self.fused_keyed = jax.jit(fused_keyed, donate_argnums=donate)
        else:
            self.fused_keyed = None

    def sharded_round(self, mesh) -> Callable:
        """(raw, keys) sharded on ``mesh``'s "data" axis -> logits, plus
        msg, ok, ncorr when RS runs on device, all sharded like the
        batch: one program in which each device runs ingest, decode and
        RS on its own shard (``shard_map``).  Pallas kernels cannot be
        partitioned automatically, and every stage is per-image, so the
        program has no collective.  Built once per mesh."""
        fn = self._sharded.get(mesh)
        if fn is None:
            spec = jax.sharding.PartitionSpec("data")
            device_rs = self._device_rs

            def per_shard(raw, keys):
                logits = self.decode_keyed(self.ingest_keyed(raw, keys),
                                           keys)
                if device_rs is None:
                    return (logits,)
                out = device_rs(self.bits(logits))
                return (logits, out["message_bits"], out["ok"],
                        out["n_corrected"])

            # check_vma off: the kernels' out_shape structs carry no
            # mesh-axis annotation
            fn = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                                       in_specs=(spec, spec),
                                       out_specs=spec, check_vma=False))
            self._sharded[mesh] = fn
        return fn

    # -- RS correction ---------------------------------------------------
    def _rs_host(self, bits: np.ndarray):
        """(msg, ok, ncorr) via the configured host RS engine."""
        cfg = self.cfg
        b = bits.shape[0]
        msg = np.zeros((b, self.code.message_bits), np.int32)
        ok = np.zeros((b,), bool)
        ncorr = np.zeros((b,), np.int32)
        if cfg.rs_mode == "cpu_pool":
            with self._pool_lock:
                base = self._pool_seq
                self._pool_seq += b
            self._rs_pool.submit_batch(bits, base)
            for i, (mi, oki) in enumerate(
                    self._rs_pool.drain(range(base, base + b))):
                msg[i], ok[i] = mi[: self.code.message_bits], oki
        else:  # cpu_sync
            for i in range(b):
                res = rs_decode(self.code, bits[i])
                msg[i] = res.message_bits
                ok[i] = res.ok
                ncorr[i] = res.n_corrected
        return msg, ok, ncorr

    def rs_correct(self, bits):
        """(msg, ok, ncorr) via the configured RS engine.  ``bits`` stays
        a device array end-to-end on the device path (zero-copy handoff);
        host engines pull it to numpy here, at their host boundary."""
        if self.cfg.rs_mode == "device":
            rs_out = self._device_rs(bits if isinstance(bits, jax.Array)
                                     else jnp.asarray(bits))
            return (rs_out["message_bits"], rs_out["ok"],
                    rs_out["n_corrected"])
        with spans.span("wait.device", n=bits.shape[0]):
            bits = np.asarray(bits)
        return self._rs_host(bits)

    # -- adaptive multi-tile escalation --------------------------------
    def escalate_round(self, raw, keys, r: int):
        """Soft bits of escalation-plan tile ``r``: the two jitted
        escalation stage fns composed — literally the fns the server's
        re-submitted rounds run, so the inline loop and the online
        escalation path cannot drift bitwise."""
        return self.decode_tiles(self.escalation_tiles(raw, keys, r))

    def escalate(self, raw, keys, msg, ok, ncorr, logits
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                            np.ndarray, np.ndarray]:
        """Adaptive escalation after a completed round 1: images whose
        RS failed (or whose margin is thin — :class:`EscalationPolicy`)
        are re-decoded on tile r of their plan each round, soft bits
        (logits) are ACCUMULATED across tiles, and RS re-runs on the
        accumulated signs, until every image settles or the
        ``max_tiles`` budget is spent.

        Host-orchestrated: each round gathers only the still-failing
        images into a pow2-padded sub-batch (bounded jit shapes) and
        drives the same jitted tile/decode/RS engines as round 1, so
        per-image results are bit-identical no matter which engine ran
        round 1 or how failures were sub-batched (every op in the path
        is batch-stable).  Returns (msg, ok, ncorr, accumulated_logits,
        tiles_used) as numpy arrays; with ``escalate_tiles == 1`` the
        inputs pass through untouched (tiles_used all ones)."""
        b = np.asarray(ok).shape[0]
        tiles_used = np.ones(b, np.int32)
        if not self.policy.enabled:
            return (np.asarray(msg), np.asarray(ok), np.asarray(ncorr),
                    np.asarray(logits), tiles_used)
        msg = np.asarray(msg).copy()
        ok = np.asarray(ok).copy()
        ncorr = np.asarray(ncorr).copy()
        acc = np.asarray(logits, np.float32).copy()
        raw_np = np.asarray(raw)
        need = self.policy.wants_escalation(ok, acc)
        for r in range(1, self.policy.max_tiles):
            idx = np.nonzero(need)[0]
            if idx.size == 0:
                break
            sub_raw, n = _pad_pow2(raw_np[idx])
            sub_keys, _ = _pad_pow2(keys[idx])
            new_logits = np.asarray(
                self.escalate_round(sub_raw, sub_keys, r))[:n]
            acc[idx] += new_logits
            sub_acc, _ = _pad_pow2(acc[idx])
            m2, o2, c2 = self.rs_correct(
                (sub_acc > 0).astype(np.int32))
            m2, o2, c2 = (np.asarray(a)[:n] for a in (m2, o2, c2))
            msg[idx], ok[idx], ncorr[idx] = m2, o2, c2
            tiles_used[idx] = r + 1
            need[:] = False
            need[idx] = self.policy.wants_escalation(o2, acc[idx])
        return msg, ok, ncorr, acc, tiles_used

    def escalate_prefix(self, raw, keys, msg, ok, ncorr, logits,
                        true_b: Optional[int] = None):
        """:meth:`escalate` restricted to the first ``true_b`` rows of
        a padded batch: pad rows (repeats of the last real image) keep
        their round-1 results and never consume escalation rounds.
        Returns full-size arrays either way — the one scatter shared by
        ``detect_batch`` and the stage-graph rs sink."""
        b = np.asarray(ok).shape[0]
        tb = b if true_b is None else min(true_b, b)
        if tb >= b:
            return self.escalate(raw, keys, msg, ok, ncorr, logits)
        m, o, c, lg, tu = self.escalate(
            raw[:tb], keys[:tb], msg[:tb], ok[:tb], ncorr[:tb],
            logits[:tb])
        msg = np.asarray(msg).copy()
        ok = np.asarray(ok).copy()
        ncorr = np.asarray(ncorr).copy()
        logits = np.asarray(logits, np.float32).copy()
        tiles = np.ones(b, np.int32)
        msg[:tb], ok[:tb], ncorr[:tb] = m, o, c
        logits[:tb], tiles[:tb] = lg, tu
        return msg, ok, ncorr, logits, tiles

    # -- the stage graph ---------------------------------------------------
    def build_stages(self, lanes: Dict[str, int],
                     finish: Optional[Callable[[dict], Any]] = None,
                     depth: int = 2,
                     escalate_inline: bool = True,
                     emit_embed: bool = False
                     ) -> List[lanes_lib.Stage]:
        """The detection stage graph — THE payload contract every
        executor-driven engine (offline run_stream, online server)
        shares.

        Payloads are dicts carrying ``raw`` + ``keys`` (per-image
        fold_in keys, pre-derived by the feeder/batcher so stage
        functions are pure and any lane count or arrival interleaving
        is bit-identical to serial) -> ``x`` -> ``logits`` ->
        ``msg``/``ok``/``ncorr``.  Between lanes everything stays a
        device array (jitted stage fns return futures).  The rs stage
        starts the device-to-host copy of every device output it
        forwards (``copy_to_host_async``), so each item's copies run
        together as soon as the device finishes it; whoever makes them
        numpy later finds them in flight or done.  ``finish(p)``, when
        given, does that on the rs lane (the server's sink).  Without
        it the caller makes each result numpy as the executor yields
        it, as ``run_stream`` does.
        Extra payload fields (request slots, timestamps) flow through
        untouched.

        Escalation: payloads may carry ``round`` (int, default 0) and
        ``acc_logits``.  A round-r > 0 payload ingests tile r of each
        image's escalation plan and decode ADDS the new soft bits onto
        ``acc_logits`` — the form the online server's re-submitted
        escalation micro-batches take.  With ``escalate_inline=True``
        (the offline engines) round-0 payloads instead run the whole
        adaptive loop synchronously on the rs lane via
        :meth:`escalate`, annotating the payload with ``tiles_used``.

        ``emit_embed=True`` (the server with the near-duplicate cache
        on) makes round-0 decode also emit the GAP embedding as payload
        field ``embed`` — logits are bitwise unchanged."""

        def st_ingest(p):
            r = p.get("round", 0)
            raw = jax.device_put(p["raw"])
            if r > 0:
                # escalation round: ingest emits tile r of the plan
                # directly (decode-ready), whatever the ingest mode
                p["x"] = self.escalation_tiles(raw, p["keys"], r)
            else:
                p["x"] = self.ingest_keyed(raw, p["keys"])
            return p

        def st_decode(p):
            if p.get("round", 0) > 0:
                logits = self.decode_tiles(p["x"])
            elif emit_embed:
                logits, p["embed"] = self.decode_keyed_embed(
                    p["x"], p["keys"])
            else:
                logits = self.decode_keyed(p["x"], p["keys"])
            if p.get("acc_logits") is not None:
                logits = logits + jnp.asarray(p["acc_logits"])
            p["logits"] = logits
            return p

        def st_rs(p):
            p["msg"], p["ok"], p["ncorr"] = self.rs_correct(
                self.bits(p["logits"]))
            for f in _FETCHED:
                if isinstance(p.get(f), jax.Array):
                    p[f].copy_to_host_async()
            if (escalate_inline and self.policy.enabled
                    and p.get("round", 0) == 0):
                # payloads from padded feeders carry "true_b": only the
                # real rows escalate (pad rows repeat the last real
                # image — escalating them would multiply every round's
                # decode/RS work by the pad factor for nothing; the
                # consumer slices them off anyway)
                (p["msg"], p["ok"], p["ncorr"], p["logits"],
                 p["tiles_used"]) = self.escalate_prefix(
                    p["raw"], p["keys"], p["msg"], p["ok"], p["ncorr"],
                    p["logits"], p.get("true_b"))
            return finish(p) if finish is not None else p

        return [
            lanes_lib.Stage("ingest", st_ingest,
                            lanes=max(1, lanes.get("ingest", 1)),
                            depth=depth),
            lanes_lib.Stage("decode", st_decode,
                            lanes=max(1, lanes.get("decode", 1)),
                            depth=depth, gpu_intensive=True),
            lanes_lib.Stage("rs", st_rs,
                            lanes=max(1, lanes.get("rs", 1)),
                            depth=depth),
        ]

    def close(self):
        if self._rs_pool is not None:
            self._rs_pool.close()
            self._rs_pool = None
