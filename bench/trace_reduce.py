"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler.trace`` writes an ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into device operations, each with the XLA
module it belongs to.  The benchmark's own host spans are not profiler
events: ``Spans`` records them on the host clock, which costs a list
append, so the profiler can run with its host tracer off (at host tracer
level 1 the runtime's own events, millions in a few seconds, slowed the
host by half).  ``load`` puts them on the trace's time line through the
profile's start time.  The window is the span named ``bench.window``.

* A TPU appears as a plane ``/device:TPU:<n>``.  Its "XLA Ops" line holds
  the operations; its "XLA Modules" line holds one event per program
  run, which gives each operation its module.
* The CPU backend has no device plane: its operations are the host
  events that carry an ``hlo_module`` stat, counted as one device.  That
  is what the self-test records; no chip number comes from it.

Busy time is the union of a device's operation intervals inside the
window; the idle share is one minus busy over the window, averaged over
the devices.  A stage's device time is the time its module's runs cover
inside the window, summed over devices.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Op:
    device: int
    module: str
    name: str
    start: float   # ns
    end: float


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    modules: List[Op]        # one per program run (name = module)
    spans: List[Span]
    window: Tuple[float, float]
    devices: int
    host_events: int = 0     # host events that are neither (volume)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


class Spans:
    """Host spans on the wall clock (ns since the epoch, the clock of
    the profile's start time), recorded only while ``on``."""

    def __init__(self):
        self.on = False
        self.items: List[Span] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        t = time.time_ns()
        try:
            yield
        finally:
            self.items.append(Span(name, t, time.time_ns()))


def _module_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(trace_dir: str, spans: List[Span]) -> Trace:
    """The trace under ``trace_dir``, with ``spans`` (wall clock) moved
    onto its time line."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops: List[Op] = []
    modules: List[Op] = []
    device_ids = set()
    start = None
    host_ops: List[Op] = []
    host_events = 0
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            device_ids.add(dev)
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = [Op(dev, _module_name(e.name), _module_name(e.name),
                       e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines.get("XLA Modules", [])]
            modules += mods
            mods.sort(key=lambda o: o.start)
            starts = [o.start for o in mods]
            for e in lines.get("XLA Ops", []):
                mod = _stats(e).get("hlo_module")
                if mod is None:
                    mod = _enclosing(mods, starts, e.start_ns)
                ops.append(Op(dev, str(mod), e.name, e.start_ns,
                              e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    mod = _stats(e).get("hlo_module")
                    if mod is not None:
                        host_ops.append(Op(0, str(mod), e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
                    else:
                        host_events += 1
        else:
            start = dict(_plane_stats(plane)).get("profile_start_time",
                                                  start)
    if start is None:
        raise ValueError("trace has no profile_start_time")
    spans = [Span(s.name, s.start - start, s.end - start) for s in spans]
    if not device_ids:             # the CPU backend: one host "device"
        ops = host_ops
        device_ids = {0}
        modules = _modules_from_ops(ops)
    win = [s for s in spans if s.name == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w = max(win, key=lambda s: s.end - s.start)
    return Trace(ops=ops, modules=modules, spans=spans,
                 window=(w.start, w.end), devices=len(device_ids),
                 host_events=host_events)


def _plane_stats(plane) -> list:
    try:
        return list(plane.stats)
    except (TypeError, ValueError):
        return []


def _enclosing(mods: List[Op], starts: List[float], t: float) -> str:
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].start <= t <= mods[i].end:
        return mods[i].name
    return "?"


def _modules_from_ops(ops: List[Op]) -> List[Op]:
    """One interval per run of consecutive ops of a module (CPU)."""
    out: List[Op] = []
    for o in sorted(ops, key=lambda o: o.start):
        last = out[-1] if out and out[-1].module == o.module else None
        if last is not None and o.start <= last.end + 1e3:
            last.end = max(last.end, o.end)
        else:
            out.append(Op(o.device, o.module, o.module, o.start, o.end))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ops, window) -> List[Tuple[int, float, float]]:
    w0, w1 = window
    return [(o.device, max(o.start, w0), min(o.end, w1)) for o in ops
            if o.end > w0 and o.start < w1]


def busy_intervals(tr: Trace) -> Dict[int, List[Tuple[float, float]]]:
    per: Dict[int, List[Tuple[float, float]]] = {}
    for dev, a, b in _clip(tr.ops, tr.window):
        per.setdefault(dev, []).append((a, b))
    return {d: _union(v) for d, v in per.items()}


def busy_s(tr: Trace) -> float:
    """Seconds with an operation running, averaged over the devices."""
    per = busy_intervals(tr)
    total = sum(b - a for v in per.values() for a, b in v)
    return total * 1e-9 / max(tr.devices, 1)


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def _runs(tr: Trace, module: str) -> List[Op]:
    """Runs of the module named ``module`` (exactly, or followed by a
    character that cannot continue a name) overlapping the window."""
    pat = re.compile(re.escape(module) + r"(?![A-Za-z0-9_])")
    w0, w1 = tr.window
    return [o for o in tr.modules
            if pat.match(o.name) and o.end > w0 and o.start < w1]


def module_runs(tr: Trace, module: str) -> int:
    return len(_runs(tr, module))


def module_s(tr: Trace, module: str) -> Optional[float]:
    """Device seconds the runs of ``module`` cover inside the window,
    summed over devices; None where no run of it was traced."""
    runs = _runs(tr, module)
    if not runs:
        return None
    per: Dict[int, List[Tuple[float, float]]] = {}
    for dev, a, b in _clip(runs, tr.window):
        per.setdefault(dev, []).append((a, b))
    return sum(b - a for v in per.values() for a, b in _union(v)) * 1e-9


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """[[module/op, seconds]] of the operations that took most time
    (the op's HLO name, without its text)."""
    tot: Dict[str, float] = {}
    for o in tr.ops:
        a, b = max(o.start, tr.window[0]), min(o.end, tr.window[1])
        if b > a:
            key = f"{o.module}/{o.name.split(' = ')[0].lstrip('%')}"
            tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """[[label, seconds]] of the longest gaps with no operation on a
    device, each labelled by the innermost benchmark span open at the
    gap's middle."""
    gaps = []
    w0, w1 = tr.window
    for dev, iv in busy_intervals(tr).items():
        t = w0
        for a, b in iv + [(w1, w1)]:
            if a > t:
                gaps.append((a - t, t, a, dev))
            t = max(t, b)
    if not busy_intervals(tr):
        gaps.append((w1 - w0, w0, w1, 0))
    gaps.sort(reverse=True)
    spans = [s for s in tr.spans if s.name != WINDOW]
    out = []
    for length, a, b, dev in gaps[:n]:
        mid = 0.5 * (a + b)
        open_ = [s for s in spans if s.start <= mid <= s.end]
        label = (min(open_, key=lambda s: s.end - s.start).name
                 if open_ else WINDOW)
        out.append([f"{label}@dev{dev}", length * 1e-9])
    return out
