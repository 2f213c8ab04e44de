"""The readers of the program's own spans and counters on the CPU:
the arithmetic on planted spans, their silence where the program
records none, and a tiny traced offline and online run in one
process."""
import sys
from types import SimpleNamespace

import pytest

from bench import program_spans, run
from repro.core import spans
from test_faults import _tiny


def _span(name, start, end, thread):
    return spans.Span(name, start, end, thread, 0, None, None, None)


def _ctx(recorded, images=10):
    return SimpleNamespace(program_spans=recorded,
                           window=SimpleNamespace(images=images))


def test_work_is_spans_less_their_waits():
    recorded = [
        _span("stage.rs", 0, 100_000, 1),
        _span("wait.device", 40_000, 90_000, 1),
        _span("feed", 0, 30_000, 2),
        _span("wait.queue", 30_000, 80_000, 2),
        _span("feed", 80_000, 100_000, 2),
        _span("batcher.wait", 0, 1_000_000, 2),
    ]
    assert program_spans.work_s_by_thread(_ctx(recorded)) == \
        {1: pytest.approx(50e-6), 2: pytest.approx(50e-6)}
    pace = run._metric_reader("host_pace_us_per_image")(_ctx(recorded))
    work = run._metric_reader("host_work_us_per_image")(_ctx(recorded))
    assert pace == pytest.approx(5.0) and work == pytest.approx(10.0)


def test_readers_are_silent_without_program_spans(monkeypatch):
    for name in ("host_pace_us_per_image", "host_work_us_per_image"):
        assert run._metric_reader(name)(_ctx([])) is None
    # a program without the recorder, as before it existed
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    ctx = SimpleNamespace(window=SimpleNamespace(images=10))
    assert run._metric_reader("host_pace_us_per_image")(ctx) is None
    ctx = SimpleNamespace(measured=SimpleNamespace(counters={}))
    assert run._metric_reader("batch_wait_ms")(ctx) is None


def test_traced_offline_and_online_runs_read_the_program(monkeypatch):
    seen = {}
    reader = run._metric_reader

    def keep_ctx(name):
        read = reader(name)

        def wrapped(ctx):
            seen[name] = ctx
            return read(ctx)
        return wrapped

    monkeypatch.setattr(run, "_metric_reader", keep_ctx)
    got = {}
    for cell in ("qrmark-256-t64.offline", "qrmark-256-t64.online"):
        spans.take()
        res = run.run_cell(*_tiny(cell), seed=11, seconds=1.0, trace=True,
                           require_tpu=False)
        assert res["correct"], res["checks"]
        got.update({k: v["value"] for k, v in res["metrics"].items()})
    pace, work = got["host_pace_us_per_image"], got["host_work_us_per_image"]
    w = seen["host_pace_us_per_image"].window
    assert 0 < pace <= work
    assert pace <= 1.05 * 1e6 * w.window_s / w.images
    max_wait = _tiny("qrmark-256-t64.online")[3]["batcher"]["max_wait_ms"]
    # thread wake-ups on a loaded CPU add some ms to the deadline
    assert 0 < got["batch_wait_ms"] <= max_wait + 10
