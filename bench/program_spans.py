"""The program's own spans (``repro.core.spans``) from a traced window.

The program records them while a profiler session is active, which is
the traced window of a ``--trace 1`` run; ``taken`` takes them once,
after the window, and keeps them on the readers' context.  A program
without the recorder (``repro.core.spans`` missing) gives none.

A thread's work is the time its spans cover, less the time it blocks
on a queue (``wait.queue``) or on the device (``wait.device``).
``batcher.wait`` is a request's time in a queue, not a thread's work,
and is left out."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

WAITS = ("wait.queue", "wait.device")
NOT_WORK = ("batcher.wait",)


def taken(ctx) -> list:
    if not hasattr(ctx, "program_spans"):
        try:
            from repro.core import spans
        except ImportError:
            ctx.program_spans = []
        else:
            ctx.program_spans = spans.take()
    return ctx.program_spans


def _covered_ns(intervals: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in trace_reduce._union(intervals))


def work_s_by_thread(ctx) -> Optional[Dict[int, float]]:
    """Seconds of work per program thread, or None where the program
    recorded no span."""
    spans = [s for s in taken(ctx) if s.name not in NOT_WORK]
    if not spans:
        return None
    per: Dict[int, Tuple[list, list]] = {}
    for s in spans:
        every, waits = per.setdefault(s.thread, ([], []))
        every.append((s.start, s.end))
        if s.name in WAITS:
            waits.append((s.start, s.end))
    return {t: (_covered_ns(every) - _covered_ns(waits)) * 1e-9
            for t, (every, waits) in per.items()}
