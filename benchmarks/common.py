"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import json
import pickle
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
SRC = str(REPO / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

EXTRACTOR_DIR = REPO / "experiments" / "extractor"
DRYRUN_DIR = REPO / "experiments" / "dryrun"
OUT_DIR = REPO / "experiments" / "bench"


def load_extractor(tile: int):
    """Trained (params, cfg) for a tile size, or None if not trained."""
    p = EXTRACTOR_DIR / f"tile{tile}_params.pkl"
    if not p.exists():
        return None
    with open(p, "rb") as f:
        d = pickle.load(f)
    return d["params"], d["cfg"]


def trained_tiles():
    return sorted(int(p.stem.split("_")[0][4:])
                  for p in EXTRACTOR_DIR.glob("tile*_params.pkl"))


def load_or_init_extractor(tile: int):
    """(params, cfg, trained) — the trained artifact when present, else a
    freshly initialised extractor.  Throughput benchmarks only need the
    compute graph, not a converged model, so a fresh checkout can still
    run fig6/fig7/fig8 end-to-end (accuracy tables DO require training —
    they stay artifact-gated)."""
    for t in (tile, *trained_tiles()):
        loaded = load_extractor(t)
        if loaded is not None:
            return loaded[0], loaded[1], True
    import jax
    from repro.core.extractor import init_encoder, init_extractor
    from repro.core.train_extractor import ExtractorTrainConfig
    cfg = ExtractorTrainConfig(tile=tile)
    n_bits = cfg.code.codeword_bits
    params = {"dec": init_extractor(jax.random.key(0), n_bits=n_bits,
                                    tile=tile),
              "enc": init_encoder(jax.random.key(1), n_bits=n_bits,
                                  tile=tile)}
    return params, cfg, False


def cost_analysis(fn, *args):
    """(flops, bytes accessed) of a jitted fn per XLA ``cost_analysis``."""
    c = fn.lower(*args).compile().cost_analysis()
    return (float(c.get("flops", 0.0)),
            float(c.get("bytes accessed", 0.0)))


def timeit(fn, *args, iters=3, warmup=1):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def emit(name: str, seconds_per_call: float, derived: str):
    """The `name,us_per_call,derived` CSV contract of benchmarks.run."""
    print(f"{name},{seconds_per_call * 1e6:.1f},{derived}", flush=True)


def save_json(name: str, obj):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{name}.json").write_text(json.dumps(obj, indent=1,
                                                     default=str))


def ber_model():
    """Measured bit-error-rate vs bits-per-pixel from the trained
    extractors (used to extrapolate untrained cells; documented in
    EXPERIMENTS.md)."""
    pts = []
    for t in trained_tiles():
        rep = EXTRACTOR_DIR / f"tile{t}_report.json"
        if not rep.exists():
            continue
        r = json.loads(rep.read_text())
        ba = r["eval"].get("none", {}).get("bit_acc")
        if ba is None:
            continue
        n_bits = r["config"]["code"][0] * r["config"]["code"][1]
        pts.append((n_bits / (t * t), 1.0 - ba))
    return sorted(pts)
