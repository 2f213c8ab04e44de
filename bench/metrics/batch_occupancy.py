"""Mean micro-batch size over the batcher's max_batch (%), from the
server's own ``batch_images`` observations over the run's untraced
window.  Moves latency_p95_ms."""


def read(ctx):
    snap = ctx.measured.counters or {}
    dist = snap.get("batch_images")
    if not dist or not dist.get("n"):
        return None
    return 100.0 * dist["mean"] / ctx.traffic["batcher"]["max_batch"]
