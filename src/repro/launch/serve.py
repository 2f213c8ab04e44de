"""Serving launcher: batched watermark-detection service + LM decode
service, driven by QRMark's adaptive allocator and LPT scheduler.

Two serving regimes:

* **offline** (:class:`DetectionService`) — a stream of image batches
  known up front -> ingest/tile/decode/RS with lanes allocated by
  Algorithm 1 (``allocator.assign``) and executed as real concurrency
  by the :class:`repro.core.lanes.LaneExecutor`; mini-batches are
  scheduled by Algorithm 2 with straggler mitigation.  Ragged batches
  are padded up to a shape bucket (bounding jit recompilation) and
  sliced back — per-image RNG keys make pad rows inert.
* **online** (``--online``, :class:`repro.serving.DetectionServer`) —
  per-request submissions arriving over time through an open-loop
  Poisson load generator (:func:`open_loop_load`): dynamic
  micro-batching, SLO-tiered admission control (``--classes`` /
  ``--bulk-frac``), content-addressed result caching
  (``--cache-exact`` / ``--cache-embed-threshold``) with an optional
  Zipf repeat-heavy workload (``--zipf`` / ``--pool``), and
  per-request / per-class latency percentiles.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Callable, Dict, Iterable, List, Optional, \
    Tuple  # noqa: F401

import jax
import numpy as np

from repro.core import allocator, scheduler as sched_lib, spans
from repro.core.detect import DetectionConfig, DetectionPipeline, \
    STAGE_NAMES
from repro.data import pipeline as data_lib
# pad_to_bucket moved to the serving layer (the batcher shapes its
# micro-batches with it); re-exported here for existing callers
from repro.serving.batcher import AdmissionError, pad_to_bucket  # noqa: F401


@dataclasses.dataclass
class ServiceReport:
    images: int
    wall_s: float
    throughput_ips: float
    allocation: Optional[List[int]]
    lanes: Optional[Dict[str, int]]
    lane_loads: Optional[List[float]]
    straggler_retries: int = 0


class DetectionService:
    """Adaptive, scheduled batch-stream detection service (the offline
    regime; the request-level online runtime is
    :class:`repro.serving.DetectionServer`, ``--online``)."""

    def __init__(self, det_cfg: DetectionConfig, extractor_params, *,
                 lane_budget: int = 8, mem_cap: float = 2e9,
                 lanes: int = 0, pad_bucket: int = 0):
        self.pipe = DetectionPipeline(det_cfg, extractor_params)
        self.det_cfg = det_cfg
        self.lane_budget = lane_budget
        self.mem_cap = mem_cap
        self.pad_bucket = pad_bucket
        self.allocation: Optional[allocator.Allocation] = None
        # lanes knob: 0 = adaptive (allocator.assign after warmup),
        # n >= 1 = fixed n decode/RS lanes, bypassing the allocator
        self.lanes: Optional[Dict[str, int]] = (
            None if lanes == 0 else
            {"ingest": 1, "decode": max(1, lanes), "rs": max(1, lanes)})
        self._fixed_lanes = lanes != 0
        self.warmup_stats: Dict[int, tuple] = {}

    # -- Algorithm 1: warm-up profiling + adaptive allocation -------------
    def warmup(self, sample_raw):
        """Profile the pipeline's actual stage functions (tile-first
        ingest produces the decode input directly; staged ingest the
        full preprocessed image; decode is the fused Pallas kernel when
        configured) and run Algorithm 1.

        Every stage is profiled through the engine the pipeline will
        really run — in particular RS goes through ``_rs_correct`` (the
        on-device batched decoder when ``rs_mode="device"``, the CPU
        pool or sync loop otherwise), not a host-side reference loop, so
        the lane allocation matches what serving executes."""
        cfg = self.det_cfg
        key = jax.random.key(0)
        pre = allocator.profile_stage(
            lambda b: jax.block_until_ready(self.pipe._ingest(b, key)),
            sample_raw, name="ingest")
        x, keys = self.pipe._ingest(sample_raw, key)
        dec = allocator.profile_stage(
            lambda b: jax.block_until_ready(
                self.pipe._decode_x(b, keys[: b.shape[0]])),
            x, name="decode")
        logits = self.pipe._decode_x(x, keys)
        bits = self.pipe._bits(logits)
        rs_sample = bits if cfg.rs_mode == "device" else np.asarray(bits)
        rs_prof = allocator.profile_stage(
            lambda bb: jax.block_until_ready(self.pipe._rs_correct(bb)),
            rs_sample, name="rs")
        profiles = [pre, dec, rs_prof]
        self.allocation = allocator.adaptive_allocation(
            profiles, global_batch=sample_raw.shape[0],
            stream_budget=self.lane_budget, mem_cap=self.mem_cap)
        if not self._fixed_lanes:
            self.lanes = allocator.assign(
                profiles, global_batch=sample_raw.shape[0],
                lane_budget=self.lane_budget, mem_cap=self.mem_cap)
        self.warmup_stats[cfg.tile] = (dec.t_per_sample, dec.u_per_sample)
        return self.allocation

    # -- Algorithm 2 + lane-executor streaming -----------------------------
    def _plan(self, batches: Iterable, use_scheduler: bool):
        """The work items of a serve call: each batch cut into
        LPT-placed mini-batch slices (Algorithm 2) or kept whole, each
        padded to its bucket; with the LPT per-lane predicted loads
        summed over the batches (None without the scheduler)."""
        lane_loads: Optional[List[float]] = None
        work: List[Tuple[np.ndarray, int]] = []  # (padded slice, true b)
        for raw in batches:
            raw = np.asarray(raw)
            b = raw.shape[0]
            if use_scheduler and self.warmup_stats:
                tasks = sched_lib.build_tasks(
                    [{"i": i} for i in range(b)], self.warmup_stats,
                    b0=b, select_tile=lambda m: self.det_cfg.tile,
                    group=max(1, b // 4))
                n_lanes = (sum(self.lanes.values()) if self.lanes else 4)
                sched = sched_lib.lpt_schedule(
                    tasks, n_lanes=max(n_lanes, 1), balance_slack=0.25,
                    mem_cap=self.mem_cap, b_min=1, global_batch=b)
                # accumulate the LPT per-lane predicted loads across
                # request batches — the report's lane_loads field
                if lane_loads is None:
                    lane_loads = [0.0] * len(sched.loads)
                lane_loads = [a + l for a, l in zip(lane_loads,
                                                    sched.loads)]
                off = 0
                for lane in sched.lanes:
                    for task in lane:
                        sl = raw[off: off + task.n_samples]
                        off += task.n_samples
                        if sl.shape[0]:
                            work.append(pad_to_bucket(sl, self.pad_bucket))
            else:
                work.append(pad_to_bucket(raw, self.pad_bucket))
        return work, lane_loads

    def serve(self, batches: Iterable, *,
              use_scheduler: bool = True,
              on_result: Optional[Callable[[int, dict], None]] = None
              ) -> ServiceReport:
        """Run a stream of (possibly ragged) batches through the lane
        executor.  With the scheduler on, each request batch is split
        into LPT-placed mini-batch tasks first (Algorithm 2); the task
        slices then flow through the executor as the work stream.

        ``on_result(i, res)`` receives work item ``i``'s result, pad
        rows sliced off, as it leaves the executor.  Work items are
        contiguous slices of the batches, in order; item ``i`` ran
        with the pipeline's batch key ``seq0 + i``, where ``seq0`` is
        the pipeline's batch counter when ``serve`` was called."""
        mon = sched_lib.StragglerMonitor()
        with spans.span("serve.plan"):
            work, lane_loads = self._plan(batches, use_scheduler)

        def feed():
            for tid, (sl, tb) in enumerate(work):
                mon.start(tid)
                # (padded slice, true size): pad rows stay escalation-
                # inert and consume() slices them off the results
                yield (sl, tb)

        n_img_box = [0]

        def consume(tid: int, res: dict):
            # completion is recorded HERE, as each result comes off the
            # executor — recording it after the whole stream finished
            # (the old zip loop) made every per-task latency the total
            # stream wall time, useless for straggler timeouts
            true_b = work[tid][1]
            for k, v in res.items():
                if getattr(v, "ndim", 0) >= 1:
                    res[k] = v[:true_b]   # slice pad rows off
            n_img_box[0] += true_b
            mon.complete(tid)
            if on_result is not None:
                on_result(tid, res)

        t0 = time.perf_counter()
        out = self.pipe.run_stream(feed(), lanes=self.lanes,
                                   on_result=consume)
        wall = time.perf_counter() - t0
        n_img = n_img_box[0]
        return ServiceReport(
            images=n_img, wall_s=wall,
            throughput_ips=n_img / wall if wall else 0.0,
            allocation=(self.allocation.streams if self.allocation
                        else None),
            lanes=out.get("lanes"),
            lane_loads=([round(l, 6) for l in lane_loads]
                        if lane_loads else None),
            # speculative re-executions the monitor actually recorded
            # (mark_retried) — not sink-side duplicate completions,
            # which the in-order executor can never produce
            straggler_retries=mon.retry_count)

    # -- data-parallel sharded path ----------------------------------------
    def serve_sharded(self, batches: Iterable) -> ServiceReport:
        """Shard each batch across every local device (1-D data mesh)
        instead of pipelining — the multi-chip scaling axis; combine
        with lanes by running one service per host."""
        from repro.launch.mesh import make_detection_mesh
        mesh = make_detection_mesh()
        n_img = 0
        t0 = time.perf_counter()
        for raw in batches:
            out = self.pipe.run_batch(np.asarray(raw), mesh=mesh)
            n_img += out["ok"].shape[0]
        wall = time.perf_counter() - t0
        return ServiceReport(
            images=n_img, wall_s=wall,
            throughput_ips=n_img / wall if wall else 0.0,
            allocation=None, lanes=None, lane_loads=None)


def open_loop_load(server, *, qps: float, duration_s: float,
                   make_images: Callable[[int], np.ndarray],
                   seed: int = 0,
                   priority: Optional[Callable[[int],
                                               Optional[str]]] = None
                   ) -> dict:
    """Open-loop Poisson load generator (the online serving regime).

    Request k arrives at exponential inter-arrival gaps of mean
    ``1/qps`` **regardless of completions** — unlike closed-loop
    drivers, queueing delay is exposed instead of self-throttled, so
    latency percentiles vs offered load mean something.  Rejected
    submissions (admission backpressure) are counted, not retried —
    and counted *separately* from execution failures, which surface
    later through the handles.  ``priority`` maps request index ->
    admission class (None = the server's highest class).

    Returns {handles, offered, rejected, wall_s}; call
    ``server.stats()`` after draining for the latency/throughput view.
    """
    rng = np.random.default_rng(seed)
    handles = []
    rejected = 0
    t0 = time.perf_counter()
    t_next = t0
    k = 0
    while t_next - t0 < duration_s:
        now = time.perf_counter()
        if now < t_next:
            time.sleep(t_next - now)
        try:
            handles.append(server.submit(
                make_images(k),
                priority=priority(k) if priority else None))
        except AdmissionError:
            rejected += 1
        k += 1
        t_next += rng.exponential(1.0 / qps)
    return {"handles": handles, "offered": k, "rejected": rejected,
            "wall_s": time.perf_counter() - t0}


def _lat_ms(dist: dict) -> dict:
    return {k: round(dist.get(k, float("nan")) * 1e3, 2)
            for k in ("p50", "p95", "p99", "mean")}


def run_online(cfg: DetectionConfig, params, *, qps: float,
               duration_s: float, raw_size: int, group: int = 1,
               max_batch: int = 16, max_wait_ms: float = 10.0,
               max_queue: int = 256, lanes: int = 0,
               realloc_every: int = 0, seed: int = 0,
               classes: Optional[Dict[str, float]] = None,
               bulk_frac: float = 0.0, zipf: float = 0.0,
               pool: int = 0, quiet: bool = False) -> dict:
    """Build a :class:`~repro.serving.DetectionServer`, warm it up,
    drive it with Poisson arrivals, drain, and report.

    ``classes`` enables SLO-tiered admission ({name: deadline_ms},
    first = highest priority); ``bulk_frac`` of requests are then sent
    as the *lowest* class.  ``pool`` > 0 draws each request's images
    from a fixed pool of ``pool`` synthetic images — uniformly, or
    Zipf-skewed with exponent ``zipf`` > 1 — the repeat-heavy
    workload the content cache is for."""
    from repro.serving import BatcherConfig, DetectionServer
    lane_map = (None if lanes == 0 else
                {"ingest": 1, "decode": max(1, lanes),
                 "rs": max(1, lanes)})
    srv = DetectionServer(
        cfg, params,
        batcher=BatcherConfig(max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              max_queue=max_queue, classes=classes),
        lanes=lane_map, realloc_every=realloc_every)
    buckets = srv.warmup(data_lib.synth_image(0, raw_size))
    if not quiet:
        print(f"online: warmed buckets {buckets}, lanes "
              f"{srv.lane_counts()}", flush=True)
    srv.start()
    srv.metrics.reset()

    wl_rng = np.random.default_rng(seed + 1)  # workload draws, not
    #                                           arrival gaps

    def pool_index(k: int) -> int:
        if pool <= 0:
            return k
        if zipf > 1.0:
            return int((wl_rng.zipf(zipf) - 1) % pool)
        return int(wl_rng.integers(pool))

    def make_images(k: int) -> np.ndarray:
        base = pool_index(k)
        return np.stack([data_lib.synth_image(1000 + base * group + i,
                                              raw_size)
                         for i in range(group)])

    priority = None
    if classes and bulk_frac > 0.0:
        names = list(classes)

        def priority(k: int) -> str:
            return (names[-1] if wl_rng.random() < bulk_frac
                    else names[0])

    load = open_loop_load(srv, qps=qps, duration_s=duration_s,
                          make_images=make_images, seed=seed,
                          priority=priority)
    srv.drain(timeout=120.0)
    stats = srv.stats()
    srv.close()
    failed = int(stats["counters"].get("requests_failed", 0))
    report = {
        "qps_offered": qps, "duration_s": duration_s, "group": group,
        "offered": load["offered"],
        # rejected (admission backpressure) and failed (execution
        # errors) are different outcomes — never folded together
        "rejected": load["rejected"],
        "rejection_rate": round(stats["rejection_rate"], 4),
        "failed": failed,
        "completed": int(stats["counters"].get("requests_completed", 0)),
        "throughput_rps": round(stats["throughput_rps"], 2),
        "throughput_ips": round(stats["throughput_ips"], 2),
        "latency_ms": _lat_ms(stats.get("request_latency_s", {})),
        "batch_occupancy": round(
            stats.get("batch_occupancy", {}).get("mean", float("nan")),
            3),
        "queue_depth_last": stats["gauges"].get("queue_depth", 0),
        "lanes": stats["lanes"],
        "straggler_retries": stats["straggler_retries"],
    }
    if classes:
        report["latency_ms_by_class"] = {
            c: _lat_ms(stats.get(f"request_latency_{c}_s", {}))
            for c in classes}
    if getattr(cfg, "cache_exact", False) or \
            getattr(cfg, "cache_embedding_threshold", 0.0) > 0:
        report["cache"] = {
            "hit_exact": stats["cache_hit_exact"],
            "hit_embed": stats["cache_hit_embed"],
            "miss": stats["cache_miss"],
            "dedup_coalesced": stats["dedup_coalesced"],
            "hit_rate": round(stats["cache_hit_rate"], 4),
        }
    if srv.registry.policy.enabled:
        report["escalation_rate"] = round(stats["escalation_rate"], 4)
        report["escalation_batches"] = stats["escalation_batches"]
        report["mean_tiles_per_image"] = round(
            stats.get("tiles_per_image", {}).get("mean", 1.0), 3)
    return report


def run_fleet(cfg: DetectionConfig, params, *, replicas: int,
              qps: float, duration_s: float, raw_size: int,
              group: int = 1, max_batch: int = 16,
              max_wait_ms: float = 10.0, max_queue: int = 256,
              lanes: int = 0, seed: int = 0, pin_devices: bool = True,
              fault_plans: Optional[dict] = None,
              quiet: bool = False) -> dict:
    """Build a :class:`~repro.serving.FleetRouter` over ``replicas``
    :class:`~repro.serving.Replica` instances, warm them, drive the
    fleet with Poisson arrivals THROUGH the router, drain, and report.

    ``pin_devices`` assigns replica *i* to local jax device ``i % D``
    — with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this
    is the CI-scale fleet simulation (one forced CPU device per
    replica); on a single device it is a no-op.  Requests route by
    content digest, so results are bit-identical to a single server at
    any fleet size.

    ``fault_plans`` maps replica name (``r0``..) to a
    :class:`~repro.serving.FaultPlan` — the fig14 chaos arm
    (kill-one-replica-mid-run) is this driver plus one plan entry, not
    a separate code path."""
    from repro.serving import BatcherConfig, FleetRouter, Replica
    devices = jax.local_devices()
    lane_map = (None if lanes == 0 else
                {"ingest": 1, "decode": max(1, lanes),
                 "rs": max(1, lanes)})
    reps = [Replica(
        f"r{i}", cfg, params,
        batcher=BatcherConfig(max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              max_queue=max_queue),
        lanes=lane_map,
        fault_plan=(fault_plans or {}).get(f"r{i}"),
        device=(devices[i % len(devices)] if pin_devices else None))
        for i in range(replicas)]
    router = FleetRouter(reps)
    router.warmup(data_lib.synth_image(0, raw_size))
    router.start()
    if not quiet:
        print(f"fleet: {replicas} replicas over {len(devices)} "
              f"device(s), warmed", flush=True)
    router.metrics.reset()

    def make_images(k: int) -> np.ndarray:
        return np.stack([data_lib.synth_image(1000 + k * group + i,
                                              raw_size)
                         for i in range(group)])

    load = open_loop_load(router, qps=qps, duration_s=duration_s,
                          make_images=make_images, seed=seed)
    drained = router.drain(timeout=120.0)
    stats = router.stats()
    unresolved = sum(not h.done() for h in load["handles"])
    router.close()
    lat = stats.get("request_latency_s", {})
    return {
        "replicas": replicas, "qps_offered": qps,
        "duration_s": duration_s, "group": group,
        "offered": load["offered"], "rejected": load["rejected"],
        "completed": int(stats["counters"].get("requests_completed", 0)),
        "failed": int(stats["counters"].get("requests_failed", 0)),
        "unresolved": int(unresolved), "drained": bool(drained),
        "throughput_rps": round(stats["throughput_rps"], 2),
        "latency_ms": _lat_ms(lat),
        "spillovers": stats["spillovers"],
        "reroutes": stats["reroutes"],
        "unhealthy": stats["unhealthy"],
        "straggler_retries": stats["straggler_retries"],
        "faults_injected": int(
            stats["fleet_counters"].get("faults_injected", 0)),
        "replica_table": stats["replicas"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--img", type=int, default=128)
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--mode", default="qrmark")
    ap.add_argument("--rs-mode", default="device",
                    choices=("device", "cpu_pool", "cpu_sync"))
    ap.add_argument("--lanes", type=int, default=0,
                    help="0 = adaptive (Algorithm 1); n = fixed n "
                         "decode/RS lanes")
    ap.add_argument("--ragged", action="store_true",
                    help="send odd-size batches to exercise padding")
    ap.add_argument("--sharded", action="store_true",
                    help="data-parallel run_batch over all local devices")
    ap.add_argument("--staged-ingest", action="store_true",
                    help="disable tile-first ingest (full-image "
                         "preprocess + tile select in decode)")
    ap.add_argument("--decode-dtype", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="fused-decode precision policy: fp32 = "
                         "bit-exact vs the unfused extractor, bf16 = "
                         "MXU compute with fp32 accumulation, int8 = "
                         "per-channel-quantized weights with int32 "
                         "accumulation (RS absorbs the extra bit "
                         "noise)")
    ap.add_argument("--schedule", default="flat",
                    help="decode kernel schedule: 'flat' (one image "
                         "per grid step), 'auto' (winner from the "
                         "autotune cache), or an explicit "
                         "'bb<N>-ct<N>[-db]' point")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep blocked decode schedules for this "
                         "config before building the service, persist "
                         "the winner in the autotune cache, and serve "
                         "with it (implies --schedule auto)")
    ap.add_argument("--autotune-cache",
                    default="experiments/autotune/decode_schedules.json",
                    help="schedule-cache JSON path")
    ap.add_argument("--unfused-decode", action="store_true",
                    help="disable the fused Pallas extractor kernel "
                         "(decode runs the unfused XLA graph; warmup "
                         "then profiles and allocates lanes for that)")
    ap.add_argument("--online", action="store_true",
                    help="request-level serving: DetectionServer + "
                         "open-loop Poisson load instead of the "
                         "offline batch-stream service")
    ap.add_argument("--fleet", action="store_true",
                    help="front --replicas DetectionServer replicas "
                         "with the FleetRouter (rendezvous content "
                         "routing, spill-over, crash re-execution) and "
                         "drive Poisson load through the router; "
                         "implies the online regime")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet size for --fleet (replica i pins to "
                         "local device i %% D — force a multi-device "
                         "CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "for CI-scale fleet simulation)")
    ap.add_argument("--qps", type=float, default=8.0,
                    help="offered load for --online (requests/s)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="load-generation window for --online (s)")
    ap.add_argument("--group", type=int, default=1,
                    help="images per request for --online")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="micro-batcher coalescing cap (--online)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="micro-batcher deadline for partial batches")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission-control depth bound (images)")
    ap.add_argument("--realloc-every", type=int, default=0,
                    help="re-run Algorithm 1 on measured stage "
                         "latencies every N micro-batches (0 = off)")
    ap.add_argument("--cache-exact", action="store_true",
                    help="tier-1 content-addressed result cache + "
                         "dedup-in-flight (--online); keyless requests "
                         "switch to content-derived fold_in keys so "
                         "hits are bitwise the cold-path result")
    ap.add_argument("--cache-embed-threshold", type=float, default=0.0,
                    help="tier-2 near-duplicate cache cosine threshold "
                         "over the extractor GAP embedding (0 = off; "
                         "approximate — only short-circuits "
                         "escalation rounds)")
    ap.add_argument("--classes", default="",
                    help="SLO admission classes for --online as "
                         "'name:deadline_ms,...', first = highest "
                         "priority (e.g. 'interactive:5,bulk:50'); "
                         "empty = single class at --max-wait-ms")
    ap.add_argument("--bulk-frac", type=float, default=0.0,
                    help="fraction of --online requests submitted as "
                         "the lowest class (requires --classes)")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="Zipf exponent (> 1) skewing --pool draws — "
                         "the repeat-heavy workload the content cache "
                         "targets (0 = uniform)")
    ap.add_argument("--pool", type=int, default=0,
                    help="draw --online request images from a fixed "
                         "pool of this many distinct synthetic images "
                         "(0 = every request distinct)")
    ap.add_argument("--escalate-tiles", type=int, default=1,
                    help="adaptive escalation tile budget per image "
                         "(1 = single-tile fast path only; k > 1 "
                         "re-decodes RS failures on up to k-1 extra "
                         "tiles, accumulating soft bits)")
    ap.add_argument("--escalate-margin", type=float, default=0.0,
                    help="also escalate images whose mean |logit| is "
                         "below this margin even when RS succeeded "
                         "(0 = RS-failure trigger only; requires "
                         "--escalate-tiles > 1)")
    args = ap.parse_args()

    from repro.launch.compile_cache import init_compile_cache
    print(f"compilation cache: {init_compile_cache()}")

    from repro.core.extractor import init_extractor, pack_params
    from repro.core.rs.codec import DEFAULT_CODE
    params = init_extractor(jax.random.key(0),
                            n_bits=DEFAULT_CODE.codeword_bits)

    cache_path = args.autotune_cache
    schedule = args.schedule
    if args.autotune:
        # populate (or reuse) the schedule cache before the service is
        # built, so warmup profiles the tuned kernel
        from repro.kernels import autotune as autotune_lib
        autotune_lib.autotune(
            pack_params(params, args.decode_dtype), tile=args.tile,
            batch=args.batch, dtype=args.decode_dtype,
            cache_path=cache_path)
        schedule = "auto"

    cfg = DetectionConfig(tile=args.tile, img_size=args.img,
                          resize_src=args.img + args.img // 8,
                          mode=args.mode, rs_mode=args.rs_mode,
                          tile_first=not args.staged_ingest,
                          fused_decode=not args.unfused_decode,
                          decode_dtype=args.decode_dtype,
                          decode_schedule=schedule,
                          autotune_cache=cache_path,
                          escalate_tiles=args.escalate_tiles,
                          escalate_margin=args.escalate_margin,
                          cache_exact=args.cache_exact,
                          cache_embedding_threshold=(
                              args.cache_embed_threshold))
    if args.fleet:
        if args.replicas < 1:
            raise SystemExit("--replicas must be >= 1")
        rep = run_fleet(cfg, params, replicas=args.replicas,
                        qps=args.qps, duration_s=args.duration,
                        raw_size=args.img + 32, group=args.group,
                        max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        max_queue=args.max_queue, lanes=args.lanes)
        print(json.dumps(rep, indent=1, default=str))
        return
    if args.online:
        classes = None
        if args.classes:
            classes = {}
            for part in args.classes.split(","):
                name, _, ms = part.partition(":")
                classes[name.strip()] = float(ms)
        rep = run_online(cfg, params, qps=args.qps,
                         duration_s=args.duration,
                         raw_size=args.img + 32, group=args.group,
                         max_batch=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         max_queue=args.max_queue, lanes=args.lanes,
                         realloc_every=args.realloc_every,
                         classes=classes, bulk_frac=args.bulk_frac,
                         zipf=args.zipf, pool=args.pool)
        print(json.dumps(rep, indent=1))
        return
    svc = DetectionService(cfg, params, lanes=args.lanes)
    sample = np.stack([data_lib.synth_image(i, args.img + 32)
                       for i in range(args.batch)])
    alloc = svc.warmup(sample)
    print(f"allocation: streams={alloc.streams} J*={alloc.bottleneck_s:.4f} "
          f"lanes={svc.lanes}")
    rng = np.random.default_rng(0)
    sizes = [args.batch if not args.ragged else
             int(rng.integers(1, args.batch + 1))
             for _ in range(args.batches)]
    batches = [np.stack([data_lib.synth_image(1000 + k * args.batch + i,
                                              args.img + 32)
                         for i in range(n)])
               for k, n in enumerate(sizes)]
    rep = svc.serve_sharded(batches) if args.sharded else svc.serve(batches)
    print(json.dumps(dataclasses.asdict(rep), indent=1))


if __name__ == "__main__":
    main()
