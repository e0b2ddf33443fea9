"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement):

  table1_latency    — Table 1: generation time vs optimized fraction
  fig1_window       — Fig. 1: window-placement sensitivity (PSNR)
  fig3_threshold    — Fig. 3: 20% threshold over the Table-2 prompt set
  fig4_gs_tuning    — Fig. 4: guidance-scale retuning after 40% optimization
  serve_throughput  — beyond-paper: guided AR serving tokens/s vs fraction
  roofline_report   — §Roofline table from the dry-run JSONL

Usage: PYTHONPATH=src python -m benchmarks.run [--only table1,fig1,...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = {
    "table1": "benchmarks.table1_latency",
    "fig1": "benchmarks.fig1_window",
    "fig3": "benchmarks.fig3_threshold",
    "fig4": "benchmarks.fig4_gs_tuning",
    "serve": "benchmarks.serve_throughput",
    "roofline": "benchmarks.roofline_report",
}

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = list(BENCHES) if not args.only else args.only.split(",")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    print("name,us_per_call,derived")
    summary = {}
    failed = []
    for name in names:
        mod_name = BENCHES[name]
        t0 = time.time()
        try:
            import importlib
            mod = importlib.import_module(mod_name)
            out = mod.run()
            summary[name] = out
            print(f"{name}/_wall_s,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name}/_error,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    with open(os.path.join(RESULTS_DIR, "bench_summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
