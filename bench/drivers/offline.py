"""Offline traffic: closed streams of batches through one
``DetectionService.serve`` call per window.

Batches are cut from the image pool at seeded offsets.  Set-up runs two
warm serves of the traffic's batch size, so that the window meets the
same LPT slices and programs: the first compiles them, the second gives
the steady rate from which each window's batch count is sized to fill
its seconds."""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench.window import Window, sample

FIELDS = ("message_bits", "ok", "logits")


class Driver:
    def __init__(self, ctx):
        from repro.launch.serve import DetectionService

        self.ctx, tr = ctx, ctx.traffic
        self.svc_cfg = ctx.config["service"]
        self.b = tr["batch"]
        self.svc = DetectionService(ctx.det_cfg, ctx.params,
                                    lanes=self.svc_cfg["lanes"])
        self.svc.warmup(ctx.pool[: self.b])
        ctx.mark(f"Algorithm 1 warm-up, lanes {self.svc.lanes}")
        self.rng = np.random.default_rng(ctx.seeds["traffic"])
        # work items served so far: item i of a serve runs with the
        # pipeline's batch key seq + i
        self.seq = 0
        for phase in ("compile", "rate"):
            _, warm = self._stream(tr["warm_batches"])
            t = time.perf_counter()
            rep = self._serve(warm, None)
            self.rate = rep.images / (time.perf_counter() - t)
            ctx.mark(f"warm serve ({phase}) at {self.rate!r} images/s")

    def _stream(self, n):
        pool = self.ctx.pool
        starts = self.rng.integers(0, len(pool) - self.b + 1, n)
        return starts, [pool[s: s + self.b] for s in starts]

    def _serve(self, batches, keep):
        def on_result(i, res):
            self.seq += 1
            if keep is not None:
                keep(i, res)

        return self.svc.serve(batches,
                              use_scheduler=self.svc_cfg["scheduler"],
                              on_result=on_result)

    def window(self, seconds: float, timed) -> Window:
        ctx, b = self.ctx, self.b
        n_batches = max(1, int(round(self.rate * seconds / b)))
        starts, batches = self._stream(n_batches)
        seq0 = self.seq
        got: Dict[int, dict] = {}

        def keep(i, res):
            with ctx.span("bench.result"):
                got[i] = {k: res[k] for k in FIELDS}

        with timed():
            t0 = time.perf_counter()
            with ctx.span("bench.serve"):
                self._serve(batches, keep)
            wall = time.perf_counter() - t0

        attempted = n_batches * b
        tids = sorted(got)
        done = sum(got[i]["ok"].shape[0] for i in tids)
        complete = tids == list(range(len(tids))) and done == attempted
        if complete:
            sizes = [got[i]["ok"].shape[0] for i in tids]
            key_index = np.repeat(seq0 + np.arange(len(tids)), sizes)
            key_pos = np.concatenate([np.arange(s) for s in sizes])
            pool_rows = (np.repeat(starts, b)
                         + np.tile(np.arange(b), n_batches))
            rows = {k: np.concatenate([got[i][k] for i in tids])
                    for k in FIELDS}
            pick = sample(np.random.default_rng(ctx.seeds["sample"]), done,
                          ctx.config["check"]["sample_images"])
            rows = {k: v[pick] for k, v in rows.items()}
            checked = (pool_rows[pick], key_index[pick], key_pos[pick])
        else:
            rows, checked = {}, (np.zeros(0, int),) * 3
        return Window(
            end_to_end={"images_per_s": done / wall},
            attempted=attempted, failed=attempted - done,
            wrong_outcome=0 if complete else max(1, attempted - done),
            rows=rows, pool_index=checked[0], key_index=checked[1],
            key_pos=checked[2], images=done, window_s=wall,
            notes=[f"rate {self.rate!r} images/s -> {n_batches} batches "
                   f"of {b}; {len(tids)} work items from key {seq0}"])

    def close(self):
        self.svc.pipe.close()
