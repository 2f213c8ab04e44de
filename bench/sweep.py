"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 bench/sweep.py --workload qrmark-256-t64.online --seed 5 \\
        --rates 200 220 240 260 280 300 --seconds 10

One process builds and warms the cell's system through the cell's own
driver (``bench/drivers/<kind>.py``), exactly as a run does, then runs
one window at each rate in turn, for ``--seconds`` each.  For each rate
it prints the requests offered and refused, p50/p95 latency from due
time, the mean micro-batch, and the backlog: the median latency of the
last tenth of the requests against that of the first tenth.  A rate is
sustained with no refusal, no error, no growing backlog (the last
tenth's median under twice the first's and under it plus 100 ms), and
a tail within TAIL_SLACK of the tail at the sweep's lowest rate, which
should lie well below the knee.  Near capacity a stall of the process
(a full garbage collection, a slow host) takes long to drain, so the
tail swings from run to run long before requests are refused.  The
knee is the highest rate sustained with every lower rate of the sweep
sustained too.  The cell then runs at a fixed rate of about 4/5 of it,
written into its traffic file.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

TAIL_SLACK = 1.1     # p95 at most 10% over its value at the lowest rate


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench import loadgen, run
    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    _, cell, cfg, traffic = run.load_cell(args.workload)
    ctx = run.context(cfg, traffic, args.seed, jax.devices()[:1],
                      span=lambda name: contextlib.nullcontext(),
                      mark=run.log)
    drv = run.driver(traffic["kind"])(ctx)
    knee, broken, base_p95 = None, False, None
    try:
        for rate in sorted(args.rates):
            win = drv.window(args.seconds, contextlib.nullcontext,
                             rate=rate)
            lat = win.latency_ms
            tenth = max(1, len(lat) // 10)
            first = float(np.median(lat[:tenth]))
            last = float(np.median(lat[-tenth:]))
            growing = not (last < 2 * first and last < first + 100.0)
            occ = (win.counters or {}).get("batch_images", {}).get("mean")
            p95 = loadgen.quantile(lat, 0.95)
            base_p95 = base_p95 or p95
            row = {"rate_per_s": rate, "offered": win.attempted,
                   "failed": win.failed,
                   "p50_ms": loadgen.quantile(lat, 0.5), "p95_ms": p95,
                   "first_tenth_median_ms": first,
                   "last_tenth_median_ms": last,
                   "mean_batch_images": occ,
                   "sustained": (win.failed == 0 and not growing
                                 and p95 <= TAIL_SLACK * base_p95),
                   "notes": win.notes}
            print(json.dumps(row), flush=True)
            broken = broken or not row["sustained"]
            if not broken:
                knee = rate
    finally:
        drv.close()
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "rate_at_four_fifths": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
