"""Program spans (``repro.core.spans``): off unless a profiler session
is active or recording was enabled, nested per thread with the work
item's id, inert on results; the batcher's ``queue_wait_s`` counter;
the names the stage programs give their kernels and ops."""
import time

import jax
import numpy as np
import pytest

from canary import deadline
from repro.core import spans
from repro.core.detect import DetectionConfig, DetectionPipeline
from repro.core.extractor import init_extractor
from repro.core.lanes import LaneExecutor, Stage
from repro.core.rs.codec import DEFAULT_CODE
from repro.core.stages import make_device_rs
from repro.serving import BatcherConfig, DetectionServer
from repro.serving.metrics import MetricsRegistry

_FIELDS = ("message_bits", "ok", "n_corrected", "logits")


@pytest.fixture
def recorded():
    """Spans recorded inside the test, with recording on throughout."""
    spans.take()
    spans.enable()
    yield spans.take
    spans.disable()
    spans.take()


@pytest.fixture(scope="module")
def tiny_params():
    return init_extractor(jax.random.key(0),
                          n_bits=DEFAULT_CODE.codeword_bits,
                          channels=8, depth=2)


def _cfg():
    return DetectionConfig(tile=16, img_size=32, resize_src=40,
                           mode="qrmark", rs_mode="device")


def _images(n, b, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (b, 64, 64, 3), dtype=np.uint8)
            for _ in range(n)]


def _by_id(recorded_spans):
    return {s.id: s for s in recorded_spans}


def test_recorder_is_off_by_default():
    spans.take()
    assert not spans.profiling() and not spans.recording()
    with spans.span("stage.decode", item=1, n=4) as s:
        assert s is None
    spans.record("batcher.wait", 0, 1, item=2)
    # off, every site gets the one shared no-op: nothing is allocated
    assert spans.span("a") is spans.span("b", item=3, n=4)
    assert spans.take() == []


def test_records_inside_a_profiler_session_and_stops_after(tmp_path):
    """Pins the session helper to where JAX keeps its profiler state:
    if JAX moves it, nothing records inside the session and this
    fails."""
    from jax._src import profiler as jax_profiler

    assert spans._PROFILE_STATE is jax_profiler._profile_state
    spans.take()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert spans.profiling() and spans.recording()
        with spans.span("sink", item=7, n=4):
            with spans.span("wait.device"):
                pass
    finally:
        jax.profiler.stop_trace()
    t1 = time.time_ns()
    assert not spans.profiling()
    with spans.span("after"):
        pass
    with jax.profiler.trace(str(tmp_path / "again")):
        with spans.span("feed", item=8):
            pass
    inner, outer, feed = spans.take()
    assert [s.name for s in (inner, outer, feed)] == \
        ["wait.device", "sink", "feed"]
    assert inner.parent == outer.id and outer.parent is None
    assert inner.item == 7 and outer.n == 4 and inner.n is None
    assert inner.thread == outer.thread
    assert t0 <= outer.start <= inner.start <= inner.end <= outer.end <= t1
    assert feed.item == 8
    assert spans.take() == []


def test_enable_and_record(recorded):
    spans.record("batcher.wait", 100, 250, item=5, n=1)
    spans.disable()
    spans.record("batcher.wait", 100, 250, item=6, n=1)
    (s,) = recorded()
    assert (s.name, s.start, s.end, s.item, s.n, s.parent) == \
        ("batcher.wait", 100, 250, 5, 1, None)


@deadline(60)
def test_lane_spans_nest_and_carry_the_item(recorded):
    """Run mode and service mode: every stage call is a
    ``stage.<name>`` span with its item's id, on the worker's thread,
    and a span the stage opens nests under it."""
    def work(x):
        with spans.span("inner"):
            time.sleep(0.001 * (x % 3))
        return x + 1

    ex = LaneExecutor([Stage("a", work, lanes=3, depth=2),
                       Stage("b", lambda x: x * 2, lanes=2, depth=1)])
    assert ex.map(range(12)) == [(i + 1) * 2 for i in range(12)]
    got = recorded()
    ids = _by_id(got)
    inner = [s for s in got if s.name == "inner"]
    assert len(inner) == 12
    for s in inner:
        parent = ids[s.parent]
        assert parent.name == "stage.a" and parent.thread == s.thread
        assert s.item == parent.item
    for name in ("stage.a", "stage.b", "sink"):
        assert sorted(s.item for s in got if s.name == name) == \
            list(range(12))
    assert all(s.parent is None and s.item is None
               for s in got if s.name == "wait.queue")

    ex = LaneExecutor([Stage("a", work, lanes=2)]).start()
    try:
        tickets = [ex.submit(i, item=100 + i) for i in range(4)]
        assert [t.result(10) for t in tickets] == [1, 2, 3, 4]
    finally:
        ex.close()
    got = recorded()
    assert sorted(s.item for s in got if s.name == "stage.a") == \
        [100, 101, 102, 103]
    assert sorted(s.item for s in got if s.name == "inner") == \
        [100, 101, 102, 103]


@deadline(300)
def test_run_stream_is_bit_identical_with_spans_and_spans_share_items(
        tiny_params, recorded):
    data = _images(4, 4)
    spans.disable()
    off = DetectionPipeline(_cfg(), tiny_params).run_stream(data, lanes=2)
    spans.enable()
    on = DetectionPipeline(_cfg(), tiny_params).run_stream(data, lanes=2)
    for r0, r1 in zip(off["results"], on["results"]):
        for f in _FIELDS:
            np.testing.assert_array_equal(r0[f], r1[f])
    got = recorded()
    for name in ("feed", "stage.ingest", "stage.decode", "stage.rs",
                 "sink", "fetch", "wait.device"):
        assert sorted(s.item for s in got if s.name == name) == \
            [0, 1, 2, 3], name
    ids = _by_id(got)
    for s in got:
        if s.name == "wait.device":
            assert ids[s.parent].name == "fetch"
        if s.name == "fetch":
            assert ids[s.parent].name == "sink" and s.n == 4
    threads = {s.name: s.thread for s in got}
    assert threads["feed"] != threads["sink"] != threads["stage.decode"]


@deadline(420)
def test_server_is_bit_identical_with_spans_and_observes_queue_wait(
        tiny_params, recorded):
    """Requests sent one at a time, so that each micro-batch holds one
    request whose deadline ships it: results equal with spans on and
    off, one ``queue_wait_s`` observation per request, each at least
    ``max_wait_ms`` and not far above it, and the same wait recorded as
    the request's ``batcher.wait`` span."""
    max_wait_ms = 20.0
    reqs = _images(4, 2, seed=1)
    keys = [jax.random.key(40 + i) for i in range(len(reqs))]
    srv = DetectionServer(_cfg(), tiny_params,
                          batcher=BatcherConfig(max_batch=4,
                                                max_wait_ms=max_wait_ms))
    srv.warmup(reqs[0][0])
    srv.start()
    runs = []
    try:
        for on in (False, True):
            (spans.enable if on else spans.disable)()
            srv.metrics.reset()
            runs.append([srv.submit(r, key=k).result(120)
                         for r, k in zip(reqs, keys)])
            if not on:
                snap = srv.stats()
    finally:
        srv.close()
    for r0, r1 in zip(*runs):
        for f in _FIELDS:
            np.testing.assert_array_equal(r0[f], r1[f])
    wait = snap["queue_wait_s"]
    assert wait["n"] == len(reqs) and wait["dropped"] == 0
    slack_s = 0.5
    assert max_wait_ms / 1e3 <= wait["p50"] <= wait["p99"] \
        <= max_wait_ms / 1e3 + slack_s
    got = recorded()
    names = {s.name for s in got}
    assert {"submit", "batcher.wait", "batcher.form", "dispatch",
            "stage.ingest", "stage.decode", "stage.rs", "wait.device",
            "scatter"} <= names
    waits = [s for s in got if s.name == "batcher.wait"]
    assert len(waits) == len(reqs)
    for s in waits:
        assert max_wait_ms * 1e6 <= s.end - s.start \
            <= (max_wait_ms / 1e3 + slack_s) * 1e9
    assert sorted(s.item for s in waits) == \
        sorted(s.item for s in got if s.name == "submit")
    formed = sorted(s.item for s in got if s.name == "batcher.form")
    assert len(formed) == len(reqs)
    for name in ("dispatch", "stage.decode", "scatter"):
        assert sorted(s.item for s in got if s.name == name) == formed


def test_snapshot_reports_what_the_reservoir_dropped():
    from repro.serving import metrics

    m = MetricsRegistry()
    for i in range(metrics._RESERVOIR + 5):
        m.observe("queue_wait_s", float(i))
    d = m.snapshot()["queue_wait_s"]
    assert (d["n"], d["dropped"]) == (metrics._RESERVOIR, 5)
    m.reset()
    m.observe("queue_wait_s", 1.0)
    assert m.snapshot()["queue_wait_s"]["dropped"] == 0


@pytest.mark.parametrize("stage,kernel", [
    ("ingest", "fused_tile_preprocess"),
    ("decode", "fused_extractor"),
    ("rs", "rs_decode"),
])
def test_stage_programs_name_their_kernel_and_scope(tiny_params, stage,
                                                    kernel):
    """Each stage program keeps its name (the benchmark finds its
    device time by it), names its Pallas kernel, and puts its ops
    under a scope of the stage's name."""
    from repro.core.stages import StageRegistry

    reg = StageRegistry(_cfg(), tiny_params)
    raw = _images(1, 2)[0]
    keys = reg.image_keys(reg.base_key, 2)
    if stage == "ingest":
        fn, args, module = reg.ingest_keyed, (raw, keys), "jit_ingest_keyed"
    elif stage == "decode":
        fn, args = reg.decode_keyed, (reg.ingest_keyed(raw, keys), keys)
        module = "jit_decode_keyed"
    else:
        fn, args = make_device_rs(DEFAULT_CODE), (
            np.zeros((2, DEFAULT_CODE.codeword_bits), np.int32),)
        module = "jit_decode"
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @{module} " in text
    assert f"/{stage}/{kernel}/" in text
