"""Program spans: where the host path spends its time, per thread.

A span is one stretch of host work, such as a lane's stage call or a
blocked queue operation, recorded as a :class:`Span` on the wall clock
(``time.time_ns``: the clock of a profile's ``profile_start_time``, so
``span.start - profile_start_time`` places a span on the trace's time
line).  ``parent`` is the id of the enclosing span on the same thread;
``item`` identifies the work item (offline) or the request or
micro-batch (online), and a span without one inherits its parent's, so
the spans of one item share it; ``n`` is the number of images covered.

Recording is off unless a JAX profiler session is active
(``jax.profiler.trace`` or ``start_trace`` ... ``stop_trace``) or
:func:`enable` was called.  Off, a span site costs one call and one
attribute read.  Spans go into one in-memory buffer of ``CAPACITY``
spans; ``take()`` returns them and empties it::

    with jax.profiler.trace(log_dir):
        service.serve(batches)
    recorded = spans.take()

The profiler's host tracer can stay at level 0: these spans are not
profiler events.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import List, Optional


class _Idle:
    profile_session = None


class _Forced:
    profile_session = True


try:   # where jax 0.9 keeps the profiler's session (None when idle)
    from jax._src.profiler import _profile_state as _PROFILE_STATE
except ImportError:                                  # pragma: no cover
    _PROFILE_STATE = _Idle
if not hasattr(_PROFILE_STATE, "profile_session"):   # pragma: no cover
    _PROFILE_STATE = _Idle

# what a span site reads: the profiler's state, or _Forced after
# enable(); one attribute read either way
_gate = _PROFILE_STATE

CAPACITY = 1 << 18     # spans kept between two take() calls; the rest
#                        are not recorded


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    start: int                # time.time_ns()
    end: int
    thread: int               # threading.get_ident()
    id: int
    parent: Optional[int]
    item: Optional[int]
    n: Optional[int]


_buf: List[Span] = []
_ids = itertools.count()
_local = threading.local()


def profiling() -> bool:
    """Whether a JAX profiler session is active."""
    return _PROFILE_STATE.profile_session is not None


def enable():
    """Record whether or not the profiler runs, until :func:`disable`."""
    global _gate
    _gate = _Forced


def disable():
    global _gate
    _gate = _PROFILE_STATE


def recording() -> bool:
    return _gate.profile_session is not None


def _append(s: Span):
    buf = _buf
    if len(buf) < CAPACITY:
        buf.append(s)          # atomic under the GIL: no lock


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Open:
    __slots__ = ("name", "item", "n", "id", "parent", "start", "stack")

    def __init__(self, name, item, n):
        self.name, self.item, self.n = name, item, n

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.stack = stack
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.item is None:
                self.item = top.item
        else:
            self.parent = None
        self.id = next(_ids)
        stack.append(self)
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        stack = self.stack
        if stack[-1] is self:
            stack.pop()
        else:                  # a generator's span closed out of order
            stack.remove(self)
        _append(Span(self.name, self.start, end, threading.get_ident(),
                     self.id, self.parent, self.item, self.n))
        return False


def span(name: str, item: Optional[int] = None, n: Optional[int] = None):
    """Context manager recording one span named ``name`` while
    recording is on; a shared no-op otherwise."""
    if _gate.profile_session is not None:
        return _Open(name, item, n)
    return _OFF


def record(name: str, start: int, end: int, item: Optional[int] = None,
           n: Optional[int] = None):
    """Record a span whose times were taken elsewhere (a request's wait
    in a queue), with no parent, while recording is on."""
    if recording():
        _append(Span(name, start, end, threading.get_ident(), next(_ids),
                      None, item, n))


def take() -> List[Span]:
    """The spans recorded since the last call, oldest first; empties
    the buffer."""
    global _buf
    out, _buf = _buf, []
    return out
