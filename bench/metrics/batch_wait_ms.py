"""Median wait of a request in the batcher's queue (ms), from its
enqueue to the formation of its micro-batch: the server's
``queue_wait_s`` observations over the run's untraced window.  Moves
latency_p50_ms."""


def read(ctx):
    snap = ctx.measured.counters or {}
    dist = snap.get("queue_wait_s")
    if not dist or not dist.get("n"):
        return None
    return 1e3 * dist["p50"]
