"""The control of the check: the reference in the program's place,
computed one precision below the configuration's.

    python3 bench/control.py --config qrmark-256-t64 \\
        --traffic offline-stream-b32 --seeds 1 2 3

For each seed it makes the run's weights and image pool, draws a
sample of images and keys as a window's check does (the configuration's
``check.sample_images``), decodes them with the reference at "highest"
and at PRECISION, and prints the numbers the check compares, beside
the configuration's limits.  The
control has to fail: a check it passes could not see that precision
drop.  Needs the chip: on the CPU every precision is float32.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# every configuration states float32 with each dot at HIGHEST; the rung
# below is "high", three bfloat16 passes
PRECISION = "high"


def readings(cfg: dict, traffic: dict, seed: int) -> dict:
    import numpy as np

    from bench import check, reference, run

    seeds, code, _, params, pool = run.inputs(cfg, traffic, seed)
    rng = np.random.default_rng(seeds["sample"])
    n = cfg["check"]["sample_images"]
    rows = rng.integers(0, len(pool), n)
    keys = reference.image_keys(seeds["tiles"] & 0x7FFFFFFF,
                                rng.integers(0, 1 << 20, n),
                                rng.integers(0, 32, n))
    raw = pool[rows]
    ref = check.reference_logits(cfg, params, raw, keys)
    low = check.reference_logits(cfg, params, raw, keys, PRECISION)
    msg, ok = code.decode((low > 0).astype(np.int32))
    got = check.numbers({"logits": low, "message_bits": msg, "ok": ok},
                        ref, code)
    got["min_abs_logit"] = float(np.min(np.abs(ref)))
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run

    cfg = run._json(ROOT / "bench" / "configs" / f"{args.config}.json")
    traffic = run._json(ROOT / "bench" / "traffic" / f"{args.traffic}.json")
    limit = cfg["check"]["max_logit_diff"]
    fails = True
    for seed in args.seeds:
        r = readings(cfg, traffic, seed)
        fails = fails and r["max_logit_diff"] > limit
        print(json.dumps({"seed": seed, "precision": PRECISION,
                          **r, "limit": limit}), flush=True)
    print(f"control {'fails the check on every seed' if fails else 'PASSES the check on some seed'}")
    return 0 if fails else 1


if __name__ == "__main__":
    sys.exit(main())
