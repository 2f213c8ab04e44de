"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints ``name,us_per_call,derived`` CSV lines (plus human-readable
headers) and writes JSON artifacts to experiments/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MODULES = [
    ("table2_accuracy", "Table 2: accuracy/PSNR/TPR vs tile size"),
    ("table3_strategies", "Table 3: tiling strategies under attacks"),
    ("table4_tile_sizes", "Table 4: strategies x tile sizes"),
    ("table5_bitlengths", "Table 5: payload length sweep"),
    ("fig6_throughput", "Fig 6: throughput vs batch"),
    ("fig7_latency", "Fig 7: latency vs batch"),
    ("fig8_breakdown", "Fig 8: optimization breakdown"),
    ("fig9_tile_ingest", "Fig 9: staged vs tile-first ingest"),
    ("fig10_decode", "Fig 10: unfused vs fused decode, "
                     "fp32/bf16/int8 x flat/tuned schedules"),
    ("fig11_online_serving",
     "Fig 11: online serving — offered load vs latency percentiles"),
    ("fig12_escalation",
     "Fig 12: adaptive multi-tile escalation under attacks"),
    ("fig13_cache",
     "Fig 13: content cache + SLO admission under Zipf load"),
    ("fig14_fleet",
     "Fig 14: fleet scaling (sustained qps vs replicas) + chaos arm"),
    ("alloc_adaptivity", "§3: stream-allocation adaptivity"),
    ("kernel_fusion", "App B.1: preprocess kernel fusion"),
    ("roofline", "§Roofline: per-stage achieved vs roofline FLOPs"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    print("name,us_per_call,derived", flush=True)
    failures = []
    for mod_name, desc in MODULES:
        if args.only and args.only not in mod_name:
            continue
        print(f"# --- {mod_name}: {desc} ---", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main(quick=args.quick)
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:
            failures.append(mod_name)
            print(f"# {mod_name} FAILED: {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}", flush=True)
        return 1
    print("# all benchmarks complete", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
