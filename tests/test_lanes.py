"""Lane-executor and sharded-batch correctness: any lane count must be
bit-identical to serial execution, order must be preserved, failures
must surface, and the data-parallel ``run_batch`` must match the
single-device path on a forced multi-device CPU mesh."""
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.detect import DetectionConfig, DetectionPipeline
from repro.core.extractor import init_extractor
from repro.core.lanes import LaneExecutor, Stage, lanes_from_allocation
from repro.core.rs.codec import DEFAULT_CODE
from repro.launch.serve import pad_to_bucket


# ---------------------------------------------------------------------------
# executor unit tests (plain python stages)
# ---------------------------------------------------------------------------


def test_executor_preserves_order_with_many_lanes():
    def jitter(x):
        time.sleep(0.001 * (x % 5))  # out-of-order completion
        return x * 2

    ex = LaneExecutor([Stage("a", jitter, lanes=4, depth=3),
                       Stage("b", lambda x: x + 1, lanes=3, depth=3)])
    assert ex.map(range(40)) == [i * 2 + 1 for i in range(40)]


def test_executor_propagates_stage_error_in_order():
    seen = []

    def boom(x):
        if x == 5:
            raise ValueError("boom")
        return x

    ex = LaneExecutor([Stage("s", boom, lanes=2, depth=2)])
    with pytest.raises(ValueError, match="boom"):
        for x in ex.run(range(10)):
            seen.append(x)
    assert seen == [0, 1, 2, 3, 4]  # everything before the failure


def test_executor_propagates_source_error_after_fed_items():
    def src():
        yield from range(3)
        raise RuntimeError("source died")

    ex = LaneExecutor([Stage("s", lambda x: x, lanes=2)])
    seen = []
    with pytest.raises(RuntimeError, match="source died"):
        for x in ex.run(src()):
            seen.append(x)
    assert seen == [0, 1, 2]


def test_executor_stage_concurrency_actually_overlaps():
    """With 4 lanes, 4 concurrent payloads must be in flight at once."""
    peak = [0]
    live = [0]
    lock = threading.Lock()

    def fn(x):
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.02)
        with lock:
            live[0] -= 1
        return x

    ex = LaneExecutor([Stage("s", fn, lanes=4, depth=4)])
    ex.map(range(12))
    assert peak[0] >= 2, f"no overlap observed (peak in-flight {peak[0]})"


def test_executor_is_single_use():
    ex = LaneExecutor([Stage("s", lambda x: x)])
    assert ex.map(range(3)) == [0, 1, 2]
    with pytest.raises(RuntimeError, match="single-use"):
        ex.map(range(3))


def test_executor_bounds_in_flight_work():
    """A stalled consumer must backpressure the graph: the stage can't
    run arbitrarily far ahead of the sink (bounded queues end to end)."""
    prepared = []
    ex = LaneExecutor([Stage("s", lambda x: (prepared.append(x), x)[1],
                             depth=2)])
    gen = ex.run(range(100))
    next(gen)
    time.sleep(0.2)  # consumer stalls; worker should fill queues & block
    in_flight = len(prepared)
    ex.close()
    assert in_flight < 20, \
        f"stage ran {in_flight} items ahead of a stalled consumer"


def test_lanes_from_allocation():
    assert lanes_from_allocation(("ingest", "decode", "rs"), [1, 4, 0]) == \
        {"ingest": 1, "decode": 4, "rs": 1}


# ---------------------------------------------------------------------------
# detection pipeline through the executor
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    return init_extractor(jax.random.key(0),
                          n_bits=DEFAULT_CODE.codeword_bits,
                          channels=8, depth=2)


def _batches(n=5, b=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (b, 64, 64, 3), dtype=np.uint8)
            for _ in range(n)]


def _collect(results):
    return (np.concatenate([r["message_bits"] for r in results]),
            np.concatenate([r["ok"] for r in results]),
            np.concatenate([r["logits"] for r in results]))


def test_lane_executor_matches_sequential_mode(tiny_params):
    """lanes>1 through the executor == the plain sequential-mode loop,
    bit for bit, on the same inputs."""
    cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                          mode="sequential", rs_mode="cpu_sync")
    data = _batches()
    serial = DetectionPipeline(cfg, tiny_params)
    ref = [serial.detect_batch(raw) for raw in data]
    laned = DetectionPipeline(cfg, tiny_params)
    out = laned.run_stream(data, lanes=3)
    assert out["lanes"] == {"ingest": 1, "decode": 3, "rs": 3}
    m0, ok0, lg0 = _collect(ref)
    m1, ok1, lg1 = _collect(out["results"])
    assert np.array_equal(m0, m1)
    assert np.array_equal(ok0, ok1)
    assert np.array_equal(lg0, lg1)


@pytest.mark.parametrize("rs_mode", ["device", "cpu_sync", "cpu_pool"])
def test_qrmark_lane_count_is_bit_identical(tiny_params, rs_mode):
    """qrmark with many lanes == qrmark with one lane per stage."""
    cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                          mode="qrmark", rs_mode=rs_mode, rs_threads=2)
    data = _batches(n=6)
    p1 = DetectionPipeline(cfg, tiny_params)
    p4 = DetectionPipeline(cfg, tiny_params)
    try:
        out1 = p1.run_stream(data, lanes=1)
        out4 = p4.run_stream(data, lanes=4)
        m0, ok0, lg0 = _collect(out1["results"])
        m1, ok1, lg1 = _collect(out4["results"])
        assert np.array_equal(m0, m1)
        assert np.array_equal(ok0, ok1)
        assert np.array_equal(lg0, lg1)
    finally:
        p1.close()
        p4.close()


_SERVICES: dict = {}


@pytest.mark.parametrize("engine", ["run_stream", "serve"])
@pytest.mark.parametrize("escalate_tiles", [1, 2])
@pytest.mark.parametrize("rs_mode", ["device", "cpu_sync"])
@pytest.mark.parametrize("lanes", [(1, 1, 1), (1, 3, 2)])
def test_stream_engines_match_serial_detect_batch(
        tiny_params, lanes, rs_mode, escalate_tiles, engine):
    """run_stream and DetectionService.serve, which make each result
    numpy as it leaves the executor in order, equal serial detect_batch
    calls on the same work items bitwise.  Batches of 3 are padded to
    4: pad rows stay inert and are sliced off.  One service per
    (rs_mode, escalate_tiles) runs every case, so each program compiles
    once; its pipeline's running sequence number keys each run."""
    from repro.launch.serve import DetectionService

    svc = _SERVICES.get((rs_mode, escalate_tiles))
    if svc is None:
        cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                              mode="qrmark", rs_mode=rs_mode,
                              escalate_tiles=escalate_tiles)
        svc = _SERVICES[(rs_mode, escalate_tiles)] = DetectionService(
            cfg, tiny_params)
    pipe = svc.pipe
    data = _batches(n=4, b=3, seed=5)
    padded = [pad_to_bucket(raw) for raw in data]
    ln = dict(zip(("ingest", "decode", "rs"), lanes))
    seq0 = pipe._seq
    if engine == "serve":
        svc.lanes = ln
        got = {}
        rep = svc.serve(data, use_scheduler=False,
                        on_result=lambda i, r: got.__setitem__(i, r))
        assert rep.lanes == ln
        out = [got[i] for i in range(len(data))]
    else:
        res = pipe.run_stream(padded, lanes=ln)
        assert res["lanes"] == ln
        out = [{k: v[:tb] for k, v in r.items()}
               for r, (_, tb) in zip(res["results"], padded)]
    assert pipe._seq == seq0 + len(data)
    ref = [{k: v[:tb] for k, v in pipe.detect_batch(
                raw, key=pipe._batch_key(seq0 + i), true_b=tb).items()}
           for i, (raw, tb) in enumerate(padded)]
    fields = ("message_bits", "ok", "n_corrected", "logits") + \
        (("tiles_used",) if escalate_tiles > 1 else ())
    for r0, r1 in zip(ref, out):
        for f in fields:
            np.testing.assert_array_equal(r0[f], r1[f], err_msg=f)
            assert isinstance(r1[f], np.ndarray), f


def test_run_batch_ragged_padding_is_inert(tiny_params):
    """Per-image keys: a padded ragged batch must give every real image
    the same result as the unpadded single-device run."""
    cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                          mode="qrmark", rs_mode="device")
    raw7 = _batches(n=1, b=7)[0]
    pa = DetectionPipeline(cfg, tiny_params)
    pb = DetectionPipeline(cfg, tiny_params)
    padded, true_b = pad_to_bucket(raw7)   # -> 8 rows
    assert padded.shape[0] == 8 and true_b == 7
    out_a = pa.run_batch(raw7, key=jax.random.key(9))
    out_b = pb.run_batch(padded, key=jax.random.key(9))
    assert np.array_equal(out_a["message_bits"],
                          out_b["message_bits"][:7])
    assert np.array_equal(out_a["logits"], out_b["logits"][:7])


def test_run_stream_default_lanes_qrmark(tiny_params):
    cfg = DetectionConfig(tile=16, img_size=32, resize_src=40,
                          mode="qrmark", rs_mode="device", lane_budget=6)
    pipe = DetectionPipeline(cfg, tiny_params)
    out = pipe.run_stream(_batches(n=3))
    assert out["images"] == 12
    assert sum(out["lanes"].values()) <= 6
    assert out["lanes"]["decode"] >= 1


# ---------------------------------------------------------------------------
# sharded run_batch on a forced 4-device CPU mesh (separate process:
# XLA_FLAGS must be set before jax initialises)
# ---------------------------------------------------------------------------


def test_sharded_run_batch_matches_single_device():
    script = Path(__file__).with_name("sharded_check.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
