#!/usr/bin/env python3
"""Chip benchmark of the QRMark detection system: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout, on a machine whose JAX sees a TPU.
A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``).  The run makes weights and a pool of
watermarked images from ``--seed`` on the device, builds the system
under test (``src/repro``) from the configuration, warms up every shape
its window uses, measures for ``--seconds``, then checks a seeded sample
of what the window returned against the plain float32 reference
(``bench/reference.py``).

The system is driven by ``bench/drivers/<kind>.py``, where ``kind`` is
the traffic file's.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, each
read by ``bench/metrics/<metric>.py``; a traced run follows its untraced
window with a traced one of TRACE_SECONDS), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit.  The same numbers end standard error.

On a machine with one TPU chip::

    python3 bench/run.py --workload qrmark-256-t64.offline \\
        --seed 7 --seconds 10 --trace 0

As a rehearsal on the CPU it must refuse, with no result line and a
non-zero exit::

    JAX_PLATFORMS=cpu python3 bench/run.py --workload \\
        qrmark-256-t64.offline --seed 1 --seconds 2 --trace 0

The CPU self-tests (``python -m pytest bench/tests``) drive the rest of
a run at a tiny size, with the chip check switched off.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


# the traced window of a --trace 1 run: device time lines of a few
# thousand program runs
TRACE_SECONDS = 5.0


class Refused(Exception):
    """The run cannot measure here: it exits non-zero, with no result."""


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, cell, configuration, traffic) for a cell's name."""
    spec = _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = _json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = _json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, cfg, traffic


def detection_config(cfg: dict, seed: int):
    from repro.core.detect import DetectionConfig
    from repro.core.rs.codec import RSCode

    det = dict(cfg["detection"])
    det["code"] = RSCode(**det["code"])
    return DetectionConfig(**det, seed=seed)


def _counter():
    """Programs lowered while the returned box is armed (each new shape
    of a jitted function or an eager operation), by name; outside the
    window, how many came from the persistent cache and how many were
    compiled, with the seconds compiling took."""
    import jax

    box = {"armed": False, "names": [], "hits": 0, "compiled": 0,
           "compile_s": 0.0}

    def on_duration(name, dur, **kw):
        if name.endswith("jaxpr_to_mlir_module_duration") and box["armed"]:
            box["names"].append(str(kw.get("fun_name", "?")))
        elif name.endswith("backend_compile_duration"):
            box["compiled"] += 1
            box["compile_s"] += dur

    def on_event(name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            box["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return box


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gc_pauses():
    """Python's full (generation 2) collections while the returned box
    is armed: how many, and the longest and total pause.  Each stops
    every thread of the process, the server's and the load's alike."""
    box = {"armed": False, "n": 0, "max_s": 0.0, "total_s": 0.0}
    start = {}

    def cb(phase, info):
        if not box["armed"] or info["generation"] != 2:
            return
        if phase == "start":
            start["t"] = time.perf_counter()
        elif "t" in start:
            d = time.perf_counter() - start.pop("t")
            box["n"] += 1
            box["max_s"] = max(box["max_s"], d)
            box["total_s"] += d

    gc.callbacks.append(cb)
    return box


def _metric_reader(name: str):
    return _load(BENCH / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}").read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _module_s(ctx, stage: str):
    """Device seconds of a stage's program in the trace, or None where
    the configuration runs no such program or none was traced."""
    mod = ctx.config["modules"].get(stage)
    return ctx.reduce.module_s(ctx.trace, mod) if mod else None


def _roofline(ctx, stage: str):
    """Least time for the stage's work at the chip's peaks over its
    device time, in %."""
    t = _module_s(ctx, stage)
    n = ctx.window.images
    if ctx.peak is None or not t or not n:
        return None
    st, pk = ctx.stages[stage], ctx.peak
    runs = ctx.reduce.module_runs(ctx.trace, ctx.config["modules"][stage])
    work = max(st["flops"] * n / pk["bf16_flops_per_s"],
               (st["bytes"] * n + st["bytes_per_call"] * runs)
               / pk["hbm_bytes_per_s"])
    return 100.0 * work / t


def _step_mfu(ctx):
    """The whole step's share of the chips' bf16 peak (%), at the rate
    of the untraced window: matmul FLOP per image times images per
    second, over chips times the peak."""
    w = ctx.measured
    if ctx.peak is None or not w.images or not w.window_s:
        return None
    return 100.0 * ctx.step_flops * (w.images / w.window_s) / (
        ctx.chips * ctx.peak["bf16_flops_per_s"])


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def inputs(cfg: dict, traffic: dict, seed: int):
    """(sub-seeds, RS code, key bits, weights, image pool) of a run."""
    import jax
    import numpy as np

    from bench import reference, workload

    seeds = workload.sub_seeds(seed)
    ext, det = cfg["extractor"], cfg["detection"]
    code = reference.RS(**det["code"])
    key_bits = np.random.default_rng(seeds["weights"]).integers(
        0, 2, code.k * code.m).astype(np.int32)
    params = workload.make_params(
        jax.random.key(np.uint32(seeds["weights"])),
        channels=ext["channels"], depth=ext["depth"], n_bits=ext["n_bits"],
        tile=det["tile"], head_scale=ext["head_scale"])
    pool = np.asarray(workload.make_pool(
        jax.random.key(np.uint32(seeds["pool"])), params["corr"],
        np.asarray(code.encode(key_bits)), n=traffic["pool"],
        size=cfg["raw_size"], crop=det["img_size"], tile=det["tile"],
        embed_rms=cfg["embed_rms"]))
    return seeds, code, key_bits, params, pool


def context(cfg: dict, traffic: dict, seed: int, devices, *, span,
            mark=lambda what: None) -> SimpleNamespace:
    """What a driver builds its system from: the configuration, the
    traffic, and the weights and image pool made from ``seed``."""
    seeds, code, key_bits, params, pool = inputs(cfg, traffic, seed)
    det_cfg = detection_config(cfg, seeds["tiles"] & 0x7FFFFFFF)
    return SimpleNamespace(config=cfg, traffic=traffic, det_cfg=det_cfg,
                           params=params, pool=pool, seeds=seeds,
                           code=code, key_bits=key_bits, devices=devices,
                           span=span, mark=mark)


def driver(kind: str):
    """The ``Driver`` of a traffic kind, from ``bench/drivers/<kind>.py``."""
    return _load(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}"
                 ).Driver


def _e2e_value(name: str, values: dict):
    """An end-to-end metric ``<quantity>`` or ``<quantity>.<suffix>``
    reads the driver's ``<quantity>``; the suffix only splits a quantity
    whose cells need bounds of their own."""
    return values.get(name, values.get(name.split(".")[0]))


def run_cell(spec, cell, cfg, traffic, *, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object.

    The window of ``seconds`` runs untraced and gives the end-to-end
    metrics and the outputs that are checked.  With ``trace``, a second
    window of TRACE_SECONDS follows under the profiler, from which the
    per-layer metrics that need the device's time line are read; those
    that need a rate or a counter read the untraced window."""
    import jax

    from bench import check, flops, trace_reduce

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise Refused(f"JAX found no TPU (platform "
                          f"{devices[0].platform!r})")
        if len(devices) < cell["chips"]:
            raise Refused(f"the cell needs {cell['chips']} chips, JAX "
                          f"sees {len(devices)}")
    devices = devices[: cell["chips"]]
    kind = devices[0].device_kind
    peaks = _json(BENCH / "peaks.json")["devices"]
    if require_tpu and kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    peak = peaks.get(kind)

    programs = _counter()
    pauses = _gc_pauses()
    spans = trace_reduce.Spans()

    def mark(what):
        log(f"set-up: {what} at {time.perf_counter() - T_START!r} s")

    ctx = context(cfg, traffic, seed, devices, span=spans, mark=mark)
    mark(f"weights and a pool of {len(ctx.pool)} images")
    tmp = tempfile.TemporaryDirectory() if trace else None
    marks = {}

    def timed(traced=False):
        @contextlib.contextmanager
        def cm():
            if "setup_s" not in marks:
                marks["setup_s"] = time.perf_counter() - T_START
                log(f"set-up: {marks['setup_s']!r} s; programs from the "
                    f"persistent cache {programs['hits']}, compiled "
                    f"{programs['compiled']} in "
                    f"{programs['compile_s']!r} s")
            programs["armed"] = pauses["armed"] = True
            if traced:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                jax.profiler.start_trace(tmp.name, profiler_options=opts)
                spans.on = True
            try:
                with spans("bench.window"):
                    yield
            finally:
                if traced:
                    spans.on = False
                    jax.profiler.stop_trace()
                programs["armed"] = pauses["armed"] = False
        return cm()

    drv = driver(traffic["kind"])(ctx)
    try:
        win = drv.window(seconds, timed)
        traced = drv.window(TRACE_SECONDS, lambda: timed(True)) \
            if trace else None
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices)
    finally:
        drv.close()
    del drv
    gc.collect()
    for w in (win, traced):
        for note in (w.notes if w else None) or []:
            log(note)
    log(f"programs lowered inside the windows: {len(programs['names'])} "
        f"{sorted(set(programs['names']))}")
    log(f"full garbage collections inside the windows: {pauses['n']}, "
        f"longest {pauses['max_s']!r} s, total {pauses['total_s']!r} s")

    # -- the check, after the system is freed ----------------------------
    checks = check.compare(win, cfg, ctx.params, ctx.pool, ctx.code,
                           ctx.key_bits, ctx.det_cfg.seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed}
    metrics = {}
    if not trace:
        e2e = dict(win.end_to_end, setup_s=marks["setup_s"])
        for m in spec["end_to_end"]:
            v = _e2e_value(m["name"], e2e)
            if _applies(m, cell["name"]) and v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        tr = trace_reduce.load(tmp.name, spans.items)
        tmp.cleanup()
        log(f"trace: {len(tr.ops)} device operations, {len(tr.modules)} "
            f"program runs, {len(tr.spans)} benchmark spans, "
            f"{tr.host_events} other host events; the traced window "
            f"answered {traced.images} images in {traced.window_s!r} s")
        rctx = SimpleNamespace(
            trace=tr, window=traced, measured=win, config=cfg,
            traffic=traffic, stages=flops.stages(cfg),
            step_flops=flops.step_flops(cfg), peak=peak,
            chips=len(devices), reduce=trace_reduce)
        rctx.module_s = lambda stage: _module_s(rctx, stage)
        rctx.roofline = lambda stage: _roofline(rctx, stage)
        rctx.step_mfu = lambda: _step_mfu(rctx)
        for m in spec["per_layer"]:
            if _applies(m, cell["name"]):
                v = _metric_reader(m["name"])(rctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr, 10),
            "idle_gaps": trace_reduce.idle_gaps(tr, 10)}
    result["metrics"] = metrics
    result["device"] = device
    if "breakdown" in result:            # keep "checks" last
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, cell, cfg, traffic = load_cell(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"no system under test at {ROOT / 'src'}")
        # the compile cache lives at a fixed path inside the checkout,
        # set before JAX is imported so the program's own set-up takes it
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from repro.launch.compile_cache import init_compile_cache
        init_compile_cache()
        result = run_cell(spec, cell, cfg, traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace))
    except Refused as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
