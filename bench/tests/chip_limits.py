"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tests/chip_limits.py --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 [--control control_fp8] [--seconds 0] \
        [--out limits.jsonl]

For each seed it makes one benchmark run of the cell (``run_cell``: the
cell's own driver and timed path, a window of ``--seconds``, at least one
batch, then the reference comparison) and prints its ``correct`` and the
numbers compared. With ``--control-seeds`` it makes the same run with one
of the configuration's controls in the program's place (``--control``: a
precision below the one the configuration states); each of those has to
come out not correct. One JSON line per run. All runs share one process,
so programs compile once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="control",
                    help="the configuration's control variant to run")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    cell = R.Cell(json.loads((ROOT / "BENCHMARK.json").read_text()), a.workload)
    out = open(a.out, "a") if a.out else None
    runs = [(int(s), "program") for s in a.seeds.split(",") if s] + \
           [(int(s), a.control) for s in a.control_seeds.split(",") if s]
    for seed, variant in runs:
        t = time.perf_counter()
        res = R.run_cell(cell, seed, a.seconds, False, t_start=R.boot_clock(),
                         variant=variant)
        line = json.dumps({"workload": cell.name, "seed": seed, "variant": variant,
                           "correct": res["correct"],
                           "checks": {k: v["value"] for k, v in res["checks"].items()},
                           "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                           "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                           "wall_s": time.perf_counter() - t})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
