"""The ``fetch_us_per_image`` reader on planted spans: the program's
``fetch`` spans summed per image, their nested ``wait.device`` counted
once with them, and silence where there is nothing to read."""
import sys
from types import SimpleNamespace

import pytest

from bench import run
from repro.core import spans


def _span(name, start, end, thread):
    return spans.Span(name, start, end, thread, 0, None, None, None)


def _ctx(recorded, images=10):
    return SimpleNamespace(program_spans=recorded,
                           window=SimpleNamespace(images=images))


def test_fetch_is_summed_once_per_image():
    read = run._metric_reader("fetch_us_per_image")
    recorded = [
        _span("sink", 0, 60_000, 1),
        _span("fetch", 10_000, 50_000, 1),
        _span("wait.device", 20_000, 45_000, 1),
        _span("stage.rs", 0, 30_000, 2),
        _span("wait.device", 5_000, 25_000, 2),
        _span("fetch", 100_000, 120_000, 3),
    ]
    assert read(_ctx(recorded)) == pytest.approx(6.0)
    assert read(_ctx(recorded, images=0)) is None
    assert read(_ctx([s for s in recorded if s.name != "fetch"])) is None


def test_silent_without_program_spans(monkeypatch):
    read = run._metric_reader("fetch_us_per_image")
    assert read(_ctx([])) is None
    # a program without the recorder, as before it existed
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert read(SimpleNamespace(window=SimpleNamespace(images=10))) is None
