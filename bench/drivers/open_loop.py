"""Open-loop traffic: Poisson arrivals at a fixed rate into
``DetectionServer.submit``, one request per arrival, keys from the
server's own per-request sequence.

Set-up warms the server's buckets, the batcher's key joins for every
micro-batch size, and a short run of the same traffic, then drains.
``bench/sweep.py`` drives the same ``Driver`` at other rates."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import loadgen
from bench.window import Window, sample

FIELDS = ("message_bits", "ok", "logits")


def warm_key_joins(srv, traffic: dict):
    """Run, once for every micro-batch size, the eager array operations
    with which the server's batcher joins its requests' key arrays and
    pads them to a bucket (``serving/batcher.py``, ``next_batch``).
    Each new (requests, pad) pair is a program of its own, and one
    compiled inside the window stalls every request behind it."""
    import jax.numpy as jnp

    from repro.serving.batcher import pad_to_bucket

    reg, bc = srv.registry, traffic["batcher"]
    per = traffic.get("images_per_request", 1)
    one = reg.image_keys(reg.batch_key(0), per)
    for k in range(1, bc["max_batch"] // per + 1):
        keys = one if k == 1 else jnp.concatenate([one] * k)
        n = k * per
        pad = pad_to_bucket(np.zeros((n, 1)), bc["bucket"])[0].shape[0] - n
        if pad:
            keys = jnp.concatenate([keys, jnp.repeat(keys[-1:], pad,
                                                     axis=0)])
        keys.block_until_ready()


class Driver:
    def __init__(self, ctx):
        from repro.serving import BatcherConfig, DetectionServer
        from repro.serving.batcher import AdmissionError

        self.ctx, tr = ctx, ctx.traffic
        self.refused = AdmissionError
        self.rate = tr["rate_per_s"]
        self.per = tr.get("images_per_request", 1)
        self.srv = DetectionServer(ctx.det_cfg, ctx.params,
                                   batcher=BatcherConfig(**tr["batcher"]))
        self.srv.warmup(ctx.pool[0])
        self.srv.start()
        warm_key_joins(self.srv, tr)
        ctx.mark("batcher key joins warmed")
        warm = loadgen.poisson_schedule(
            np.random.default_rng(ctx.seeds["warm"]), rate=self.rate,
            seconds=tr["warm_requests"] / self.rate, pool=len(ctx.pool),
            images_per_request=self.per)
        self._send(warm)
        self.warm_drained = self.srv.drain(timeout=tr["drain_s"])
        ctx.mark(f"warm traffic drained: {self.warm_drained}")
        self.rng = np.random.default_rng(ctx.seeds["traffic"])

    def _send(self, sched, keep=()):
        return loadgen.send(sched, loadgen.requests(sched, self.ctx.pool),
                            lambda _k, images: self.srv.submit(images),
                            self.refused, span=self.ctx.span, keep=keep)

    def window(self, seconds: float, timed, rate: float = None) -> Window:
        ctx, srv, per = self.ctx, self.srv, self.per
        rate = rate or self.rate
        self.srv.metrics.reset()
        sched = loadgen.poisson_schedule(
            self.rng, rate=rate, seconds=seconds, pool=len(ctx.pool),
            images_per_request=per)
        n = len(sched.due_s)
        # the checked sample, drawn from the seed before the window
        keep = set(sample(np.random.default_rng(ctx.seeds["sample"]), n,
                          ctx.config["check"]["sample_images"] // per
                          ).tolist())
        with timed():
            t0 = time.perf_counter()
            sent = self._send(sched, keep)
            with ctx.span("bench.drain"):
                drained = srv.drain(timeout=ctx.traffic["drain_s"])
            wall = time.perf_counter() - t0
        stats = srv.stats()
        lat = loadgen.latencies_ms(sent)
        answered = loadgen.answered(sent)
        refused_n = int(sent.refused.sum())
        wrong = int(np.sum(~sent.refused & ~answered))
        pick = [k for k in sorted(keep) if answered[k]]
        rows: Dict[str, List] = {f: [] for f in FIELDS}
        pool_rows, key_index, key_pos = [], [], []
        for k in pick:
            h = sent.kept[k]
            res = h.result(timeout=0)
            for f in rows:
                rows[f].append(res[f])
            pool_rows += list(sched.pool_index[k])
            key_index += [h.rid] * per      # the server keys request rid
            key_pos += list(range(per))
        rows = ({f: np.concatenate(v) for f, v in rows.items()}
                if pick else {})
        lateness = np.sort(sent.late_s) * 1e3
        return Window(
            end_to_end={"latency_p50_ms": loadgen.quantile(lat, 0.50),
                        "latency_p95_ms": loadgen.quantile(lat, 0.95)},
            attempted=n, failed=int(np.sum(~np.isfinite(lat))),
            wrong_outcome=wrong, rows=rows,
            pool_index=np.asarray(pool_rows, int),
            key_index=np.asarray(key_index, int),
            key_pos=np.asarray(key_pos, int),
            images=int(answered.sum()) * per, window_s=wall,
            counters=stats, latency_ms=lat,
            notes=[
                f"offered {n} requests at {rate!r}/s, refused "
                f"{refused_n}, errors or unanswered {wrong}, drained "
                f"{drained}",
                f"generator lateness ms p50 "
                f"{loadgen.quantile(lateness, 0.5)!r} p99 "
                f"{loadgen.quantile(lateness, 0.99)!r} max "
                f"{float(lateness[-1]) if n else 0.0!r}"])

    def close(self):
        self.srv.close()
