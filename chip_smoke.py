#!/usr/bin/env python3
"""Smoke run of the guided-serving path and the paper pipeline on a TPU.

    python chip_smoke.py             # one chip: kernels, serve, pipeline
    python chip_smoke.py --chips 4   # the sharded paged arena on four chips,
                                     # against the same engine on one chip

Everything runs in this one process: a chip belongs to the process that
first touches it. Weights and data are random, made from ``--seed``; no
file outside the committed tree is read. Models run at published widths
(``llama3.2-1b`` for serving, ``sd_unet.PRODUCTION`` for the pipeline).

Every phase checks its own results; a failed check raises, and the
script exits non-zero. With no TPU the script prints why on stderr and
exits 2 before any phase runs. Lines starting ``[...]`` are progress;
wall times and token rates on them are smoke readings, not metrics. The
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache`` at the checkout root.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.sd_unet import PRODUCTION as SD_PRODUCTION  # noqa: E402
from repro.core.pipeline import SDPipeline  # noqa: E402
from repro.core.selective import GuidancePlan  # noqa: E402
from repro.data.prompts import PAPER_PROMPTS  # noqa: E402
from repro.kernels import paged_decode_attention as PDA  # noqa: E402
from repro.kernels import ref as REF  # noqa: E402
from repro.kernels.cfg_combine import (apg_combine_pallas,  # noqa: E402
                                       apg_combine_ref, cfg_combine_pallas)
from repro.kernels.quant import quantize_kv  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve import ServeMetrics  # noqa: E402

# per-kernel tolerance on max |kernel - oracle|, elementwise
KERNEL_TOL = {"ragged_bf16": 2e-2, "ragged_int8": 2e-2,
              "cfg_combine": 1e-5, "apg_combine": 1e-3}
# host-clock entries of ServeMetrics.summary(); every other entry is a
# count that the sharded and the one-device engine must agree on
TIMING_KEYS = ("wall_s", "tick_s")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def require(ok, detail) -> None:
    """Fail the phase unless ``ok`` (an ``assert`` would vanish under
    ``python -O``)."""
    if not ok:
        raise SmokeFailure(detail)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------


def _ragged_inputs(cfg, *, rows: int, num_pages: int, page_size: int,
                   nb: int, seed: int):
    """A mixed pass list: live rows at ragged positions over shuffled
    pages, padding rows (phase 0) with out-of-range tables, as the
    engine stages them."""
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (rows, H, hd), jnp.float32)
    kf = jax.random.normal(jax.random.fold_in(key, 1),
                           (num_pages, page_size, K, hd), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(key, 2),
                           (num_pages, page_size, K, hd), jnp.float32)
    phase = rng.choice([0, 1, 1, 2], size=rows).astype(np.int32)
    phase[0] = 1
    pos = rng.integers(0, nb * page_size, size=rows).astype(np.int32)
    bt = np.full((rows, nb), num_pages, np.int32)
    for r in range(rows):
        if phase[r]:
            used = pos[r] // page_size + 1
            bt[r, :used] = rng.choice(num_pages, size=used, replace=False)
    pos[phase == 0] = 0
    return q, kf, vf, jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(phase)


def kernel_phase(cfg, *, rows: int, num_pages: int, page_sizes, nb_pos: int,
                 vocab_rows: int, latent_shape, seed: int) -> dict:
    """Each main-path kernel at ``cfg``'s widths against its jnp oracle."""
    interpret = not on_tpu()
    errs = {}

    def check(name, out, ref):
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        require(np.isfinite(err), name)
        errs[name] = max(err, errs.get(name, 0.0))
        log(f"[kernel] {name:12s} shape={tuple(out.shape)} "
            f"max_abs_err={err:.3e} tol={KERNEL_TOL[name]:.0e}")
        require(err <= KERNEL_TOL[name], (name, err))

    for ps in page_sizes:
        q, kf, vf, bt, pos, phase = _ragged_inputs(
            cfg, rows=rows, num_pages=num_pages, page_size=ps,
            nb=nb_pos // ps, seed=seed + ps)
        kb, vb = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        out = jax.jit(functools.partial(
            PDA.ragged_paged_decode_attention_pallas,
            interpret=interpret))(q, kb, vb, bt, pos, phase)
        ref = REF.ref_ragged_paged_decode_attention(q, kb, vb, bt, pos, phase)
        require(bool(jnp.all(out[phase == 0] == 0)), "padding rows not zero")
        check("ragged_bf16", out, ref)
        (kq, ks), (vq, vs) = quantize_kv(kf), quantize_kv(vf)
        out = jax.jit(functools.partial(
            PDA.ragged_paged_decode_attention_int8_pallas,
            interpret=interpret))(q, kq, ks, vq, vs, bt, pos, phase)
        ref = REF.ref_ragged_paged_decode_attention_int8(q, kq, ks, vq, vs,
                                                         bt, pos, phase)
        require(bool(jnp.all(out[phase == 0] == 0)), "padding rows not zero")
        check("ragged_int8", out, ref)

    key = jax.random.PRNGKey(seed + 1)
    for shape in ((vocab_rows, cfg.vocab_size), tuple(latent_shape)):
        u = jax.random.normal(key, shape, jnp.float32)
        c = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
        out = jax.jit(functools.partial(cfg_combine_pallas, scale=7.5,
                                        interpret=interpret))(u, c)
        check("cfg_combine", out, REF.ref_cfg_combine(u, c, 7.5))
        apg = functools.partial(apg_combine_pallas, scale=7.5, eta=0.2,
                                threshold=2.0, interpret=interpret)
        out = jax.jit(apg)(u, c)
        check("apg_combine", out, apg_combine_ref(u, c, 7.5, eta=0.2,
                                                  threshold=2.0))
    return errs


# ---------------------------------------------------------------------------
# guided serving through the launcher's engine
# ---------------------------------------------------------------------------


def serve_args(cfg, *, requests: int, prompt_len: int, max_new: int,
               fraction: float, batch: int, seed: int):
    """The launcher's own option set for a continuous paged/lazy/ragged
    run with synchronous ticks."""
    ap = serve_cli.build_parser()
    args = ap.parse_args([
        "--arch", cfg.name, "--mode", "continuous", "--kv", "paged",
        "--reservation", "lazy", "--step", "ragged",
        "--requests", str(requests), "--rate", "1.0",
        "--batch", str(batch), "--prompt-len", str(prompt_len),
        "--max-new", str(max_new), "--fraction", str(fraction),
        "--seed", str(seed)])
    serve_cli.check_args(ap, args)
    return args


def _renamed(reqs, tag: str):
    return [dataclasses.replace(r, uid=f"{tag}{r.uid}") for r in reqs]


def _ragged_step_text(eng) -> str:
    """The lowered ragged decode step, as the engine last staged it."""
    st = eng._ragged_staging()
    return eng._ragged_step_fn().lower(
        eng.params, eng._pool_p,
        *[jnp.asarray(st[k]) for k in ("bt", "tok", "pos", "scale", "temp",
                                       "rkey", "lstep", "u_idx", "phase")]
    ).as_text()


def serve_phase(cfg, args) -> dict:
    """Serve a Poisson trace twice (warm-up, then measured) through the
    engine ``repro.launch.serve`` builds; check completion, the plan's
    pass arithmetic, no recompile, and which attention form compiled."""
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(args.seed)))
    eng = serve_cli._make_engine(params, cfg, args)
    reqs, arrivals = serve_cli._trace_requests(args)
    n, steps, f = len(reqs), args.max_new, args.fraction
    plan = GuidancePlan.suffix(steps, f, args.guidance_scale)

    t0 = time.perf_counter()
    warm = eng.serve_trace(_renamed(reqs, "w"), arrivals)
    warm_s = time.perf_counter() - t0
    require(len(warm) == n, (len(warm), n))
    compiles_warm = eng.metrics.step_compiles
    jit_keys = set(eng._jit)

    eng.metrics = ServeMetrics()
    t0 = time.perf_counter()
    out = eng.serve_trace(_renamed(reqs, "m"), arrivals)
    wall = time.perf_counter() - t0
    m = eng.metrics
    tokens = sum(len(v) for v in out.values())
    require(len(out) == n and m.completed == n, (len(out), m.completed, n))
    require(all(len(v) == steps for v in out.values()),
            sorted(len(v) for v in out.values()))
    # plan arithmetic: baseline 2T, selective 2T(1-f) + Tf per request
    selective = n * plan.denoiser_passes()
    baseline = n * 2 * steps
    require(selective == n * (2 * steps - plan.optimized_steps), selective)
    require(m.denoiser_passes == selective, (m.denoiser_passes, selective))
    require(m.passes_saved() == baseline - selective, m.passes_saved())
    require(m.step_compiles == 0 and set(eng._jit) == jit_keys,
            (m.step_compiles, set(eng._jit) ^ jit_keys))
    text = _ragged_step_text(eng)
    kernel = "tpu_custom_call" in text
    require(kernel == on_tpu(), "paged attention form does not match platform")
    log(f"[serve] requests={n} T={steps} f={f} completed={m.completed} "
        f"tokens={tokens} denoiser_passes={m.denoiser_passes} "
        f"(baseline {baseline}, selective {selective}) "
        f"prefill_passes={m.prefill_passes} warm_step_compiles="
        f"{compiles_warm} measured_step_compiles={m.step_compiles} "
        f"ragged_rows={eng.ragged_rows} pallas_in_step={kernel}")
    log(f"[serve] smoke wall: warm-up {warm_s:.3f}s, measured {wall:.3f}s, "
        f"{tokens / wall:.1f} tok/s (smoke readings, not metrics)")
    return {"passes": m.denoiser_passes, "baseline": baseline,
            "tokens": tokens, "pallas_in_step": kernel}


# ---------------------------------------------------------------------------
# the paper's pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _CountingPipeline(SDPipeline):
    """``SDPipeline`` whose denoiser reports, as it runs on the device,
    how many rows each call evaluated."""

    rows: list = dataclasses.field(default_factory=list)

    def eps_fn(self, unet_params=None):
        fn = super().eps_fn(unet_params)

        def counted(latents, t, text):
            n = latents.shape[0]
            jax.debug.callback(lambda: self.rows.append(n))
            return fn(latents, t, text)

        return counted


def pipeline_phase(ucfg, *, batch: int, steps: int, fractions, combines,
                   seed: int) -> dict:
    """DDIM with each combine at each selective fraction: finite latents
    of the right shape, and the denoiser passes that ran equal the
    plan's 2T(1-f) + Tf. The variants' first calls (compiles) run in
    threads, since each compile of a full-size UNet takes minutes."""
    base = SDPipeline.init(ucfg, jax.random.PRNGKey(seed))
    pipe = _CountingPipeline(base.cfg, base.params, base.sched)
    del base
    prompts = [PAPER_PROMPTS[i % len(PAPER_PROMPTS)] for i in range(batch)]
    cond = pipe.encode_prompts(prompts)
    uncond = pipe.null_embedding(batch)
    shape = (batch, ucfg.latent_size, ucfg.latent_size, ucfg.in_channels)
    key = jax.random.PRNGKey(seed + 1)
    x0 = jax.random.normal(key, shape, jnp.float32)
    plans = {(c, f): GuidancePlan.suffix(steps, f, 7.5)
             for c in combines for f in fractions}
    runs = {v: pipe.generate_jit(plan, combine=v[0])
            for v, plan in plans.items()}
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(runs)) as pool:
        list(pool.map(lambda run: jax.block_until_ready(
            run(cond, uncond, x0, key)), runs.values()))
    jax.effects_barrier()
    log(f"[pipeline] {len(runs)} variants compiled and run once in "
        f"{time.perf_counter() - t0:.1f}s")
    out = {}
    for (combine, f), run in runs.items():
        plan = plans[(combine, f)]
        pipe.rows.clear()
        t0 = time.perf_counter()
        lat = jax.block_until_ready(run(cond, uncond, x0, key))
        wall = time.perf_counter() - t0
        jax.effects_barrier()
        passes = sum(pipe.rows) // batch
        require(lat.shape == shape, lat.shape)
        require(bool(jnp.all(jnp.isfinite(lat))), (combine, f))
        require(passes == plan.denoiser_passes()
                == 2 * steps - plan.optimized_steps, (combine, f, passes))
        out[(combine, f)] = passes
        log(f"[pipeline] combine={combine} f={f} T={steps} B={batch} "
            f"passes={passes} (full CFG {2 * steps}) latents={shape} "
            f"finite=True smoke wall {wall:.3f}s (not a metric)")
    return out


# ---------------------------------------------------------------------------
# the sharded paged arena on several chips
# ---------------------------------------------------------------------------


def _bytes_per_device(tree) -> dict:
    held = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) \
                + shard.data.nbytes
    return held


def multichip_phase(cfg, args, *, devices, pool_bytes: int) -> dict:
    """The same greedy trace through ``ContinuousEngine(mesh=...)`` on a
    ``data`` mesh over ``devices`` and on a one-device mesh: tokens and
    counters must be identical, and the page pool must split evenly."""
    from repro.serve.state import kv_page_bytes
    n = len(devices)
    num_pages = pool_bytes // kv_page_bytes(cfg, args.page_size)
    num_pages -= num_pages % n
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(args.seed)))
    reqs, arrivals = serve_cli._trace_requests(args)
    results = {}
    for tag, devs in (("sharded", devices), ("one_device", devices[:1])):
        mesh = Mesh(np.array(devs), ("data",))
        p = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        eng = serve_cli._make_engine(p, cfg, args, mesh=mesh,
                                     num_pages=num_pages)
        eng._init_paged_pool()
        placed = _bytes_per_device(eng._pool_p)
        t0 = time.perf_counter()
        out = eng.serve_trace(reqs, arrivals)
        wall = time.perf_counter() - t0
        held = _bytes_per_device(eng._pool_p)
        total = sum(held.values())
        summary = {k: v for k, v in eng.metrics.summary().items()
                   if k not in TIMING_KEYS}
        require(len(out) == len(reqs), (tag, len(out)))
        require(set(held) == set(devs) and placed == held,
                (tag, placed, held))
        require(max(held.values()) == total // len(devs), (tag, held))
        log(f"[multichip] {tag}: devices={len(devs)} pages={num_pages} "
            f"pool_bytes={total} per_device=" + ",".join(
                f"{d.id}:{b}" for d, b in sorted(held.items(),
                                                 key=lambda x: x[0].id))
            + f" completed={summary['completed']} "
            f"denoiser_passes={summary['denoiser_passes']} smoke wall "
            f"{wall:.3f}s (not a metric)")
        results[tag] = (out, summary, held)
        del eng, p
        gc.collect()
    (out_s, sum_s, held_s), (out_1, sum_1, _) = (results["sharded"],
                                                 results["one_device"])
    require(out_s == out_1, "sharded tokens differ from the one-device run")
    require(sum_s == sum_1, {k: (sum_s[k], sum_1.get(k)) for k in sum_s
                             if sum_s[k] != sum_1.get(k)})
    log(f"[multichip] tokens identical ({sum(len(v) for v in out_s.values())}"
        f" tokens) and {len(sum_s)} counters identical across "
        f"{len(devices)} devices and one")
    return {"per_device": sorted(held_s.values())}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="1: kernels, serve and pipeline on one chip; 4: "
                         "only the sharded paged arena vs one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_info()
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{info['platform']}", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; {info['count']} found", file=sys.stderr)
        return 2
    log(f"[cache] {enable_compile_cache()}")

    cfg = get_config("llama3.2-1b")
    t_all = time.perf_counter()
    if args.chips == 4:
        sargs = serve_args(cfg, requests=8, prompt_len=64, max_new=16,
                           fraction=0.5, batch=8, seed=args.seed)
        multichip_phase(cfg, sargs, devices=jax.devices()[:4],
                        pool_bytes=2 << 30)
    else:
        phases = (
            ("kernels", lambda: kernel_phase(
                cfg, rows=16, num_pages=512, page_sizes=(8, 16),
                nb_pos=256, vocab_rows=8, latent_shape=(2, 64, 64, 4),
                seed=args.seed)),
            ("serve", lambda: serve_phase(cfg, serve_args(
                cfg, requests=8, prompt_len=64, max_new=16, fraction=0.5,
                batch=8, seed=args.seed))),
            ("pipeline", lambda: pipeline_phase(
                SD_PRODUCTION, batch=2, steps=10, fractions=(0.0, 0.5),
                combines=("cfg", "apg"), seed=args.seed)),
        )
        for name, phase in phases:
            t0 = time.perf_counter()
            phase()
            gc.collect()
            log(f"[phase] {name} done in {time.perf_counter() - t0:.1f}s "
                f"(compiles included)")
    log(f"[phase] all done in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
