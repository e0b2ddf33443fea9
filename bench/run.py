"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. Everything about a cell is found by name from ``BENCHMARK.json``:
the configuration's file ``bench/configs/<config>.json`` and its module
``bench/configs/<config>.py`` (the system under test as the deployment
builds it, and the plain reference), the traffic mix
``bench/traffic/<traffic>.json`` and the driver that its ``driver`` key
names (``bench/drivers/<driver>.py``), and one reader per per-layer metric
(``bench/layer_metrics/<metric>.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. Off a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.monitoring

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def boot_clock() -> float:
    return time.clock_gettime(time.CLOCK_BOOTTIME)


def process_start() -> float:
    """This process's start on the boot clock (``/proc/self/stat``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return int(fields[19]) / os.sysconf("SC_CLK_TCK")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything one run needs, resolved from BENCHMARK.json by name."""

    def __init__(self, spec: dict, workload: str, root: Path = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"known: {sorted(cells)}")
        self.spec = spec
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.cfg = json.loads((root / conf["file"]).read_text())
        self.config = load_module(root / "bench" / "configs" / f"{conf['name']}.py",
                                  f"bench_config_{conf['name']}")
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.driver = load_module(
            root / "bench" / "drivers" / f"{self.traffic['driver']}.py",
            f"bench_driver_{self.traffic['driver']}")
        self.root = root

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name] if m["moves"] in mine else [])]


def require_chips(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r}; "
                         "this benchmark measures the chip only")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Tracer:
    """Marks the measured window: counts the programs compiled or loaded
    inside it (there should be none), and with tracing on starts and stops
    the profiler around it, with a host span ``bench.window`` marking the
    window inside the trace."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._ann = None
        self._open = False
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if self._open and "compile" in name:
            self.compiles += 1

    def start(self):
        self._open = True
        if not self.enabled:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self):
        self._open = False
        if not self.enabled or self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    @staticmethod
    def span(name: str):
        if os.environ.get("REPRO_PROFILE") != "1":
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)


def read_per_layer(cell: Cell, result: dict, reduced: dict, devs) -> dict:
    from bench import peaks as P
    ctx = {"cell": cell.name, "cfg": cell.cfg, "traffic": cell.traffic,
           "counters": result["counters"], "trace": reduced,
           "peaks": P.peaks_for(devs[0].device_kind), "chips": len(devs)}
    out = {}
    for m in cell.per_layer():
        reader = load_module(BENCH / "layer_metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             variant: str = "program") -> dict:
    """One run of one cell; returns the result object (the last line).
    ``variant`` puts one of the configuration's controls in the program's
    place (the limits' tests only)."""
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a size limit (the environment may set one) makes the cache evict
    # entries while it writes, and loses them
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = require_chips(cell.chips) if require_chip else jax.devices()[: cell.chips]
    if trace:
        os.environ["REPRO_PROFILE"] = "1"
    tracer = Tracer(trace)
    ctx = dict(cfg=cell.cfg, traffic=cell.traffic, config=cell.config,
               seed=seed, seconds=seconds, tracer=tracer, variant=variant,
               setup_clock=lambda: boot_clock() - t_start)
    result = cell.driver.run(ctx)
    print(f"programs compiled or loaded in the window: {tracer.compiles}",
          file=sys.stderr)
    device = device_record(devs)
    result["release"]()
    gc.collect()
    checks = result["check"]()
    correct = all(c["value"] <= c["limit"] for c in checks.values()
                  if c["limit"] is not None)
    out = {"correct": bool(correct),
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if trace:
        from bench import trace_reduce as TR
        reduced = TR.reduce_dir(TRACE_DIR, n_devices=len(devs),
                                kernels=result.get("kernels", {}))
        out["metrics"] = read_per_layer(cell, result, reduced, devs)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["top_gaps"]}
    else:
        e2e = result["metrics"]
        out["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end()}
        out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(spec, args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
