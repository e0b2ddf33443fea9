import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory/cost analysis, dump roofline JSON.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape decode_32k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count on first init) — hence the unusual module layout.
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import SHAPES, get_config, list_archs              # noqa: E402
from repro.launch import steps as ST                                  # noqa: E402
from repro.launch.mesh import chips, make_production_mesh             # noqa: E402
from repro import roofline as RL                                      # noqa: E402


def _custom_mesh(spec: str):
    axes_s, _, shape_s = spec.partition("=")
    axes = tuple(axes_s.split(","))
    shape = tuple(int(x) for x in shape_s.split(","))
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            variant: str = "full", verbose: bool = True,
            mesh_spec: str | None = None) -> dict:
    if arch == "sd-unet":
        return run_sd(multi_pod=multi_pod, variant=variant, verbose=verbose)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = ST.skip_reason(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": mesh_spec or ("2x16x16" if multi_pod else "16x16")}
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = _custom_mesh(mesh_spec) if mesh_spec else \
        make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        bundle = ST.build(cfg, shape, mesh, variant=variant)
        with jax.set_mesh(mesh):
            lowered = jax.jit(bundle.fn,
                              in_shardings=bundle.in_shardings,
                              out_shardings=bundle.out_shardings,
                              donate_argnums=bundle.donate,
                              ).lower(*bundle.in_specs)
            compiled = lowered.compile()
            # cost lowering: scans unrolled so cost analysis counts every
            # layer (while bodies are otherwise counted once — see
            # roofline.py). Uses lowered.cost_analysis() — the UNOPTIMISED,
            # UNPARTITIONED module (global semantics; fast: no XLA passes) —
            # and divides by chip count for the idealised per-device terms.
            # The multi-pod pass is compile-proof only.
            cost = None
            os.environ["REPRO_COST_MODE"] = "1"
            try:
                if not multi_pod:
                    cost_bundle = ST.build(cfg, shape, mesh, variant=variant)
                    ca = jax.jit(
                        cost_bundle.fn, in_shardings=cost_bundle.in_shardings,
                        out_shardings=cost_bundle.out_shardings,
                        donate_argnums=cost_bundle.donate
                        ).lower(*cost_bundle.in_specs).cost_analysis() or {}
                    cost = {"flops": float(ca.get("flops", 0.0)) / chips(mesh),
                            "bytes": float(ca.get("bytes accessed", 0.0))
                            / chips(mesh)}
            finally:
                del os.environ["REPRO_COST_MODE"]
        mem = compiled.memory_analysis()
        supp = ST.recurrent_supplement(cfg, shape)
        rl = RL.analyze(bundle.name, compiled, chips(mesh),
                        ST.model_flops(cfg, shape),
                        cost=cost, supplement=supp)
        rec.update(status="ok",
                   compile_s=round(time.time() - t0, 1),
                   memory_analysis={
                       "argument_size": mem.argument_size_in_bytes,
                       "output_size": mem.output_size_in_bytes,
                       "temp_size": mem.temp_size_in_bytes,
                       "code_size": mem.generated_code_size_in_bytes,
                   },
                   roofline=rl.to_dict())
        if verbose:
            print(f"[ok] {bundle.name} mesh={rec['mesh']} "
                  f"compile={rec['compile_s']}s", flush=True)
            print(f"     memory_analysis: {mem}", flush=True)
            ca = compiled.cost_analysis() or {}
            print(f"     cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}", flush=True)
            print(f"     roofline: compute={rl.compute_s:.3e}s "
                  f"memory={rl.memory_s:.3e}s collective={rl.collective_s:.3e}s "
                  f"dominant={rl.dominant} useful={rl.useful_ratio:.2f}", flush=True)
    except Exception as e:  # noqa: BLE001 — a dry-run failure IS the signal
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] {arch}:{shape_name} {rec['error']}", flush=True)
    return rec


def run_sd(*, multi_pod: bool = False, variant: str = "full",
           verbose: bool = True) -> dict:
    """One guided denoising step of the production-scale SD UNet — the
    paper's own workload in the dry-run harness."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": "sd-unet", "shape": "denoise", "variant": variant,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    t0 = time.time()
    try:
        bundle = ST.build_sd_denoise(mesh, variant=variant)
        with jax.set_mesh(mesh):
            compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                               out_shardings=bundle.out_shardings,
                               donate_argnums=bundle.donate
                               ).lower(*bundle.in_specs).compile()
        mem = compiled.memory_analysis()
        rl = RL.analyze(bundle.name, compiled, chips(mesh))
        rec.update(status="ok", compile_s=round(time.time() - t0, 1),
                   memory_analysis={
                       "argument_size": mem.argument_size_in_bytes,
                       "output_size": mem.output_size_in_bytes,
                       "temp_size": mem.temp_size_in_bytes,
                       "code_size": mem.generated_code_size_in_bytes},
                   roofline=rl.to_dict())
        if verbose:
            print(f"[ok] {bundle.name} mesh={rec['mesh']} "
                  f"compile={rec['compile_s']}s", flush=True)
            ca = compiled.cost_analysis() or {}
            print(f"     cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}", flush=True)
            print(f"     memory: args={mem.argument_size_in_bytes/1e9:.2f}GB "
                  f"temp={mem.temp_size_in_bytes/1e9:.2f}GB", flush=True)
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] sd-unet {rec['error']}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="full", choices=["full", "cond"])
    ap.add_argument("--mesh", default=None,
                    help="custom mesh 'axes=shape', e.g. "
                         "'data,expert,model=16,8,2' (§Perf experiments)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    jobs = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            jobs.append((a, s))

    results = []
    for a, s in jobs:
        rec = run_one(a, s, multi_pod=args.multi_pod, variant=args.variant,
                      mesh_spec=args.mesh)
        results.append(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary: {ok} ok, {sk} skipped, {err} errors "
          f"of {len(results)}")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
