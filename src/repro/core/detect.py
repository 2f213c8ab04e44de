"""End-to-end watermark detection pipeline (QRMark §5.1) as an explicit
stage graph.

Stages: ingest (host->device + fused preprocess) -> tiled decode
(extractor) -> RS correction.  Three pipeline modes:

* ``sequential``  — Stable-Signature-style baseline: unfused preprocess,
  full-image decode, synchronous CPU RS per batch.
* ``tiled``       — + tile-based decode (the naive-tiling midpoint the
  paper profiles at ~1.17x).
* ``qrmark``      — + tile-first fused ingest, adaptive lane allocation,
  LPT mini-batch scheduling, inter-batch interleaving, async RS
  (CPU thread pool w/ codebook, or fully on-device batched RS).

Tile-first ingest (the qrmark default, ``cfg.tile_first``): per-image
tile offsets are derived from the fold_in keys *before* ingest — they
depend only on the key and the static image geometry — and handed to
``kernels.ops.fused_tile_preprocess``, which slices the interpolation
matrices down to the selected tile's rows/columns so ingest computes
exactly the (b, tile, tile, 3) decode input and never materialises the
full preprocessed image (~4-6x fewer ingest FLOPs at 256^2/64^2,
~16x less ingest output).  Decode is then just the extractor forward.
``tile_first=False`` keeps the staged full-image preprocess +
``select_tiles_per_image`` path; both compute the same values (output
row i of the interpolation matmul depends only on row i of Ry), up to
float reassociation.

Decode (the qrmark default, ``cfg.fused_decode``) is the fused Pallas
extractor kernel (``kernels/fused_extractor.py``): the conv blocks
with fused norm/ReLU epilogues and GAP + head in one kernel launch per
tile batch, then the correlation bank's dot, on weights packed once
per pipeline build (``extractor.pack_params``).
``cfg.decode_dtype`` is the precision policy: "fp32" runs
full-precision dots and agrees with the unfused ``extractor_forward``
graph under the cross-program contract (see DetectionConfig);
"bf16" computes the matmuls at bf16 with fp32 accumulation — logit
perturbations ~1e-2, occasionally flipping a zero-margin bit, which RS
absorbs (one bit = one GF(16) symbol, within the t=1 radius); "int8"
is the lowest rung — per-channel weight scales baked in at pack time,
per-row activation quantization, int32 accumulation — whose slightly
larger perturbations RS absorbs the same way.  ``cfg.decode_schedule``
picks the kernel blocking ("flat", "auto" = the autotune cache at
``cfg.autotune_cache``, or an explicit "bb<N>-ct<N>[-db]" point); only
"flat" compiles for TPU (``kernels/autotune.py``).
Per-image fold_in keys are derived once per batch (offline) or once per
request (online) by ``StageRegistry.image_keys`` and flow to every
stage through the payload as explicit inputs.

Execution engines, all deriving their compute from ONE
:class:`repro.core.stages.StageRegistry` (the single definition of the
ingest/decode/RS stage functions, the fused fast path, and the RNG-key
discipline — nothing is restated here):

* :meth:`DetectionPipeline.detect_batch` — one batch, synchronous (plus
  a fully-fused single-jit fast path for qrmark + device RS);
* :meth:`DetectionPipeline.run_stream` — a stream of batches through the
  :class:`repro.core.lanes.LaneExecutor`: N lanes per stage (from the
  §6.2 allocator), bounded queues, multiple mini-batches in flight;
* :meth:`DetectionPipeline.run_batch` — data-parallel sharding of one
  (possibly ragged) batch across all local devices via a 1-D
  ``NamedSharding`` mesh;
* :class:`repro.serving.server.DetectionServer` — the online
  request-level runtime: the same stage graph on a persistent
  service-mode executor behind a dynamic micro-batcher.

Stage handoff is zero-copy: payloads stay device arrays between lanes
(bits are thresholded on device, ``rs_mode="device"`` feeds them
straight into the batched decoder — the Pallas Berlekamp-Welch kernel
for the default (15,12) GF(16) code, ``jax_rs`` otherwise).  The RS
lane starts every result's device-to-host copy as it dispatches, and
``run_stream`` makes them numpy (:meth:`_finish`) as it takes each
result from the executor in order, on the consumer's thread, so the
RS lane never blocks on a result copy (the online server's sink stays
on its RS lane).

RNG discipline: batch k uses ``fold_in(key(seed), k)`` and image i of a
batch uses ``fold_in(batch_key, i)``, so results are bit-identical
regardless of lane count, execution order, batch padding, or sharding.

Adaptive multi-tile escalation (``cfg.escalate_tiles > 1``, see
docs/detection.md): every engine runs the unchanged single-tile round
first, then re-decodes only RS failures (or thin-margin decodes,
``cfg.escalate_margin``) on up to k-1 additional non-colliding tiles
of the per-image plan, accumulating soft bits between RS attempts
(:meth:`repro.core.stages.StageRegistry.escalate`).  Results gain a
``tiles_used`` column; with ``escalate_tiles=1`` nothing changes, bit
for bit.

The pipeline object is the unit the benchmarks (Fig. 6-10, 12) drive.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Union

import jax
import numpy as np

from repro.core import interleave, lanes as lanes_lib, spans
# make_device_rs / STAGE_NAMES moved to repro.core.stages; re-exported
# here for callers that import them from the pipeline module
from repro.core.stages import (STAGE_NAMES, StageRegistry,  # noqa: F401
                               make_device_rs)
from repro.core.rs.codec import DEFAULT_CODE, RSCode


# Largest fp32 logit difference allowed between two programs that
# decode the same images with the same keys (see DetectionConfig);
# reassociation moves logits of magnitude ~1-10 by a few 1e-7 per op.
CROSS_PROGRAM_LOGIT_ATOL = 1e-4


@dataclasses.dataclass
class DetectionConfig:
    """Configuration shared by every detection engine.

    RNG discipline: all randomness (tile choice, escalation plans)
    derives from ``seed`` via ``fold_in`` — batch k uses
    ``fold_in(key(seed), k)``, image i of a batch ``fold_in(batch_key,
    i)`` — so the same images get the same tiles on every engine, lane
    count, padding, or sharding.  Result contract for the same images
    and keys: bitwise identical within one compiled program (same
    jitted function, shapes and device); across programs (engines,
    schedules, ingest forms, batch shapes, devices) equal
    ``message_bits`` and ``ok``, with fp32 logits within
    :data:`CROSS_PROGRAM_LOGIT_ATOL` — compilers reassociate float
    sums per program, so bitwise equality there is not promised.

    Escalation knobs (see ``stages.EscalationPolicy`` and
    ``docs/detection.md``): ``escalate_tiles`` is the per-image tile
    budget — 1 (default) disables escalation and keeps every engine
    bit-identical to the single-tile pipeline; k > 1 re-decodes failed
    images on up to k-1 additional non-colliding tiles, accumulating
    soft bits between RS attempts.  ``escalate_margin`` > 0 also
    escalates images whose mean |logit| is below the margin even when
    RS formally succeeded.

    Cache knobs (consumed by the online ``serving.DetectionServer``;
    offline engines ignore them): ``cache_exact`` enables the tier-1
    content-hash (sha256) result cache plus dedup-in-flight — and
    switches keyless requests to *content-derived* keys
    (``fold_in(key(seed), fingerprint32(sha256 digest))``), so
    identical pixels produce identical keys and a cache hit is bitwise
    what the cold path would compute.  ``cache_embedding_threshold`` > 0 enables the
    tier-2 near-duplicate cache over the extractor's GAP embedding
    (approximate by design; it only short-circuits escalation
    rounds)."""
    tile: int = 64
    img_size: int = 256
    resize_src: int = 288          # raw -> resize -> centercrop(img_size)
    strategy: str = "random_grid"
    code: RSCode = DEFAULT_CODE
    mode: str = "qrmark"           # sequential | tiled | qrmark
    rs_mode: str = "device"        # device | cpu_pool | cpu_sync
    fused_preprocess: bool = True
    tile_first: bool = True        # fuse tile selection into ingest
    fused_decode: bool = True      # Pallas fused-extractor decode kernel
    decode_dtype: str = "fp32"     # fp32 (bit-exact) | bf16 | int8
    decode_schedule: str = "flat"  # flat | auto | "bb<N>-ct<N>[-db]"
    autotune_cache: str = ""       # schedule cache path for "auto"
    interleave: bool = True
    rs_threads: int = 32
    lane_budget: int = 8
    escalate_tiles: int = 1        # max tiles/image (1 = no escalation)
    escalate_margin: float = 0.0   # mean-|logit| floor (0 = RS-only)
    # -- online result cache (serving.cache; offline engines ignore) --
    cache_exact: bool = False      # tier-1 exact sha256 cache + dedup
    cache_embedding_threshold: float = 0.0  # tier-2 cosine floor (0=off)
    cache_capacity: int = 256      # tier-1 LRU entries (requests)
    cache_embedding_capacity: int = 512  # tier-2 LRU entries (images)
    seed: int = 0


class DetectionPipeline:
    """Drives (ingest -> tile+decode -> RS) over image streams.

    The pipeline is a thin engine layer: all stage compute, the fused
    fast path, the RS engines, and the key discipline live in its
    :class:`~repro.core.stages.StageRegistry` (``self.stages``), which
    the online :class:`~repro.serving.server.DetectionServer` shares."""

    def __init__(self, cfg: DetectionConfig, extractor_params,
                 ground_truth_bits: Optional[np.ndarray] = None):
        self.cfg = cfg
        self.params = extractor_params
        self.gt = ground_truth_bits
        self.code = cfg.code
        self.stages = StageRegistry(cfg, extractor_params)
        self.tile_first = self.stages.tile_first
        self.fused_decode = self.stages.fused_decode
        self.packed_params = self.stages.packed_params
        self._seq = 0                 # batch counter (keys)
        self._stats_lock = threading.Lock()  # _finish runs on rs lanes
        self.stats: Dict[str, float] = {"batches": 0, "images": 0}

    # ------------------------------------------------------------------
    def _batch_key(self, seq: int):
        return self.stages.batch_key(seq)

    # -- staged compute, shared by detect_batch and run_batch ----------
    def _ingest(self, raw, key):
        """raw uint8 batch -> (decode input, per-image keys): the
        selected tiles directly (tile-first) or the full preprocessed
        images (staged).  The per-image fold_in keys are derived here,
        once per batch, and handed to decode."""
        keys = self.stages.image_keys(key, raw.shape[0])
        return self.stages.ingest_keyed(raw, keys), keys

    def _decode_x(self, x, keys):
        """decode input + per-image keys -> bit logits (tile selection
        already folded into ingest on the tile-first path)."""
        return self.stages.decode_keyed(x, keys)

    def _bits(self, logits):
        return self.stages.bits(logits)

    def _rs_correct(self, bits):
        """(msg, ok, ncorr) via the registry's configured RS engine."""
        return self.stages.rs_correct(bits)

    def _finish(self, msg, ok, ncorr, logits, b,
                tiles_used=None) -> Dict[str, np.ndarray]:
        """The single place device arrays become numpy, as a ``fetch``
        span: in ``run_stream``'s in-order loop over the executor's
        results, on the consumer's thread; directly in ``detect_batch``
        and ``run_batch``.  One ``device_get`` starts every copy not
        yet started before it blocks.  ``tiles_used`` (escalation round
        counts) is reported only when escalation is configured, so
        ``escalate_tiles=1`` results keep the exact pre-escalation
        schema."""
        with spans.span("fetch", n=b):
            with self._stats_lock:
                self.stats["batches"] += 1
                self.stats["images"] += b
            with spans.span("wait.device", n=b):
                msg, ok, ncorr, logits = jax.device_get(
                    (msg, ok, ncorr, logits))
            out = {"message_bits": msg, "ok": ok, "n_corrected": ncorr,
                   "logits": logits}
            if tiles_used is not None and self.stages.policy.enabled:
                out["tiles_used"] = np.asarray(tiles_used)
            if self.gt is not None:
                out["match"] = np.all(
                    msg == self.gt[None, : msg.shape[1]], axis=1)
        return out

    # ------------------------------------------------------------------
    def detect_batch(self, raw_batch, *, key=None,
                     true_b: Optional[int] = None
                     ) -> Dict[str, np.ndarray]:
        """Synchronous detection of one raw uint8 image batch.

        ``key`` defaults to the offline discipline
        (``fold_in(key(seed), batch_seq)``); per-image keys derive from
        it, so explicit keys make results independent of call order.
        With ``escalate_tiles > 1`` the adaptive escalation loop runs
        after the (unchanged) single-tile round; the result gains a
        ``tiles_used`` column and ``logits`` become the accumulated
        soft bits for escalated images.  Callers that padded the batch
        (bucket shaping) pass ``true_b`` so pad rows never escalate
        (they repeat the last real image and get sliced off anyway)."""
        b = raw_batch.shape[0]
        item = None     # the batch's sequence number, for its spans
        if key is None:
            item = self._seq
            key = self._batch_key(self._seq)
            self._seq += 1
        if self.stages.fused_keyed is not None:
            # one program for the three stages: one span
            with spans.span("stage.decode", item=item, n=b):
                keys = self.stages.image_keys(key, b)
                (rs_out, logits) = self.stages.fused_keyed(raw_batch, keys)
            msg, ok, ncorr = (rs_out["message_bits"], rs_out["ok"],
                              rs_out["n_corrected"])
        else:
            with spans.span("stage.ingest", item=item, n=b):
                x, keys = self._ingest(raw_batch, key)
            with spans.span("stage.decode", item=item, n=b):
                logits = self._decode_x(x, keys)
            with spans.span("stage.rs", item=item, n=b):
                msg, ok, ncorr = self._rs_correct(self._bits(logits))
        tiles_used = None
        if self.stages.policy.enabled:
            msg, ok, ncorr, logits, tiles_used = \
                self.stages.escalate_prefix(
                    raw_batch, keys, msg, ok, ncorr, logits, true_b)
        return self._finish(msg, ok, ncorr, logits, b, tiles_used)

    # -- stage graph ----------------------------------------------------
    def default_lanes(self) -> Dict[str, int]:
        """Static lane split within ``cfg.lane_budget`` (Algorithm 1's
        warm-start: the decode stage is the GPU-intensive one and gets
        the most lanes; use ``allocator.assign`` for the profiled
        allocation)."""
        cfg = self.cfg
        if cfg.mode != "qrmark":
            return {n: 1 for n in STAGE_NAMES}
        budget = max(3, cfg.lane_budget)
        decode = min(4, max(1, budget // 2))
        rs = min(4, max(1, budget - decode - 1))
        return {"ingest": 1, "decode": decode, "rs": rs}

    def _finish_payload(self, p: dict) -> Dict[str, np.ndarray]:
        """Registry stage-graph sink for the offline engines."""
        logits = p["logits"]
        return self._finish(p["msg"], p["ok"], p["ncorr"], logits,
                            logits.shape[0], p.get("tiles_used"))

    def build_stages(self, lanes: Optional[Dict[str, int]] = None
                     ) -> List[lanes_lib.Stage]:
        """The detection stage graph for the lane executor — the
        registry's single payload-stage definition (payloads carry
        pre-derived per-image ``keys``, so stage functions are pure and
        any lane count is bit-identical to serial; see
        :meth:`StageRegistry.build_stages`).  Its results are payloads,
        not numpy: pass each one the executor yields through
        :meth:`_finish_payload`, as ``run_stream`` does."""
        ln = {**self.default_lanes(), **(lanes or {})}
        return self.stages.build_stages(
            ln, depth=2 if self.cfg.interleave else 1)

    # ------------------------------------------------------------------
    def run_stream(self, batches: Iterable, *, scheduled: bool = True,
                   lanes: Union[None, int, Dict[str, int]] = None,
                   on_result: Optional[Callable[[int, dict], None]] = None
                   ) -> dict:
        """Detect a stream of batches; returns throughput metrics.

        RNG/bit-identity contract: batch i of the stream uses key
        ``fold_in(key(cfg.seed), seq0 + i)`` (the pipeline's running
        sequence counter), and per-image keys derive from it — so for
        ANY lane configuration the results equal serial
        :meth:`detect_batch` calls over the same stream, bitwise,
        escalation included.

        ``lanes``: None -> lane executor with :meth:`default_lanes` for
        qrmark (plain prefetch loop otherwise); int n -> n decode + n RS
        lanes; dict -> explicit per-stage lane counts.

        Stream items are raw batches, or ``(raw, true_b)`` tuples when
        the feeder padded them — pad rows then never escalate (the
        consumer is expected to slice results to ``true_b``).

        ``on_result(i, res)`` fires as result ``i`` is consumed from the
        executor — the hook latency monitors need (a completion recorded
        after the whole stream finished measures nothing)."""
        cfg = self.cfg
        use_exec = lanes is not None or cfg.mode == "qrmark"
        if isinstance(lanes, int):
            lanes = {"ingest": 1, "decode": max(1, lanes),
                     "rs": max(1, lanes)}
        n_img = 0
        results = []
        t0 = time.perf_counter()
        if use_exec:
            stages = self.build_stages(lanes)
            ex = lanes_lib.LaneExecutor(stages, name="detect")
            seq0 = self._seq

            def feed():
                for i, item in enumerate(batches):
                    raw, tb = (item if isinstance(item, tuple)
                               else (item, None))
                    # item i of the stream is the executor's sequence
                    # number i: its stage and sink spans carry it too
                    with spans.span("feed", item=i, n=raw.shape[0]):
                        bkey = self._batch_key(seq0 + i)
                        p = {"raw": raw, "seq": seq0 + i,
                             "keys": self.stages.image_keys(
                                 bkey, raw.shape[0])}
                        if tb is not None:
                            p["true_b"] = tb
                    yield p

            for r in ex.run(feed()):
                r = self._finish_payload(r)
                if on_result is not None:
                    on_result(len(results), r)
                results.append(r)
                n_img += r["logits"].shape[0]
            self._seq = seq0 + len(results)
            lane_map = {s.name: s.lanes for s in stages}
        else:
            it = interleave.interleaved(
                batches, prepare=None,
                enabled=(cfg.interleave and cfg.mode == "qrmark"))
            for item in it:
                raw, tb = (item if isinstance(item, tuple)
                           else (item, None))
                seq = self._seq       # the item of detect_batch's spans
                r = self.detect_batch(raw, true_b=tb)
                if on_result is not None:
                    with spans.span("sink", item=seq):
                        on_result(len(results), r)
                results.append(r)
                n_img += raw.shape[0]
            lane_map = {n: 1 for n in STAGE_NAMES}
        wall = time.perf_counter() - t0
        return {"images": n_img, "wall_s": wall,
                "throughput_ips": n_img / wall if wall > 0 else 0.0,
                "lanes": lane_map, "results": results}

    # ------------------------------------------------------------------
    def run_batch(self, raw_batch, *, mesh=None,
                  key=None) -> Dict[str, np.ndarray]:
        """One (possibly ragged) batch, data-parallel across devices.

        The batch is padded up to the mesh's data-axis size, sharded
        with a ``NamedSharding`` over the 1-D device mesh, run as one
        program in which every device pushes its shard through the
        staged functions (``StageRegistry.sharded_round``: every stage
        is per-image, so the program is collective-free), and sliced
        back to the true batch size.  Per-image RNG keys make the pad
        rows inert: every real image's decisions equal the
        single-device path's."""
        from repro.launch import mesh as mesh_lib
        from repro.sharding import planner

        if key is None:
            key = self._batch_key(self._seq)
            self._seq += 1
        b = raw_batch.shape[0]
        if mesh is None:
            mesh = mesh_lib.make_detection_mesh()
        ndev = mesh.devices.size
        pad = (-b) % ndev
        raw_np = np.asarray(raw_batch)
        if pad:
            raw_np = np.concatenate(
                [raw_np, np.repeat(raw_np[-1:], pad, axis=0)])
        x_in = planner.shard_detection_batch(mesh, raw_np)
        # per-image keys shard with the batch (fold_in is per-image, so
        # the sharded graph stays collective-free)
        keys = jax.device_put(
            self.stages.image_keys(key, raw_np.shape[0]),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        out = self.stages.sharded_round(mesh)(x_in, keys)
        logits = out[0]
        if self.cfg.rs_mode == "device":
            # RS ran on the padded batch (shape-stable), slice after
            msg, ok, ncorr = (a[:b] for a in out[1:])
        else:
            msg, ok, ncorr = self._rs_correct(
                np.asarray(self._bits(logits))[:b])
        logits_b = np.asarray(logits)[:b]
        tiles_used = None
        if self.stages.policy.enabled:
            # escalation runs unsharded on the true-size failing subset
            # (sub-batches are small); keys stay the padded batch's
            # per-image keys, so tile plans match the single-device path
            msg, ok, ncorr, logits_b, tiles_used = self.stages.escalate(
                raw_np[:b], keys[:b], msg, ok, ncorr, logits_b)
        return self._finish(msg, ok, ncorr, logits_b, b, tiles_used)

    def close(self):
        self.stages.close()


def verify_against_key(message_bits: np.ndarray, key_bits: np.ndarray,
                       fpr: float = 1e-6) -> np.ndarray:
    """Statistical verification: match if the bit agreement exceeds the
    threshold tau solving  P[Binomial(n, 0.5) >= tau] <= fpr."""
    n = key_bits.shape[-1]
    tau = binomial_threshold(n, fpr)
    agree = np.sum(message_bits == key_bits[None, :], axis=-1)
    return agree >= tau


def _binomial_threshold_uncached(n: int, fpr: float) -> int:
    """Smallest tau with  P[Binomial(n, 1/2) >= tau] <= fpr  (exact
    tail via the binomial coefficients).  When even full agreement
    cannot reach the target (2^-n > fpr), returns n + 1 so
    verification fails closed instead of accepting everything."""
    from math import comb
    probs = np.array([comb(n, i) for i in range(n + 1)], dtype=float)
    probs /= probs.sum()
    cum = np.cumsum(probs[::-1])[::-1]
    sat = np.nonzero(cum <= fpr)[0]
    return int(sat[0]) if sat.size else n + 1


@functools.lru_cache(maxsize=None)
def binomial_threshold(n: int, fpr: float) -> int:
    """Cached :func:`_binomial_threshold_uncached`: tau depends only on
    (n, fpr), but the exact tail rebuilds the full ``comb`` table —
    O(n) bignum work — on every call, which :func:`verify_against_key`
    sits on for every served verification batch.  The cache makes
    repeated thresholds a dict hit."""
    return _binomial_threshold_uncached(n, fpr)
