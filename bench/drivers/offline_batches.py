"""Offline batches: one caller submits a batch of prompts, waits for the
images, and submits the next, for as long as the window lasts.

Traffic keys: ``batch`` (prompts per call), ``fraction`` (selective
guidance fraction of every request), ``sample`` (images per run compared
with the reference, half from each half of one batch).

The window opens after the warm-up: batches of the window's own shapes (the
first compiles or loads every program from the cache) until two in a row
take times within ``WARM_AGREE`` of each other, at most ``WARM_MAX``. It
closes at the end of the first batch that ends after ``seconds``: a whole
number of batches. Each batch's time is printed on standard error. ``images_per_s`` is the
images finished over the window, prompt encoding included.
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np

WARM_AGREE = 0.01
WARM_MAX = 6


def timed(system, inputs) -> float:
    t = time.perf_counter()
    jax.block_until_ready(system(*inputs))
    return time.perf_counter() - t


def run(ctx: dict) -> dict:
    cfg, traffic, mod, seed = ctx["cfg"], ctx["traffic"], ctx["config"], ctx["seed"]
    tracer = ctx["tracer"]
    B = int(traffic["batch"])
    system = mod.System(cfg, seed, traffic, variant=ctx.get("variant", "program"))
    inputs = mod.batch_inputs(cfg, seed, 0, B)
    warm = [timed(system, inputs)]
    while len(warm) < WARM_MAX and (
            len(warm) < 2 or abs(warm[-1] - warm[-2]) > WARM_AGREE * warm[-2]):
        warm.append(timed(system, inputs))
    print("warm-up batches (s): " + " ".join(f"{w:.4f}" for w in warm),
          file=sys.stderr)
    setup_s = ctx["setup_clock"]()

    outs, ends, index = [], [], 1
    tracer.start()
    t0 = time.perf_counter()
    while True:
        with tracer.span("bench.batch"):
            with tracer.span("bench.inputs"):
                tokens, x0 = mod.batch_inputs(cfg, seed, index, B)
            out = jax.block_until_ready(system(tokens, x0))
        outs.append(out)
        ends.append(time.perf_counter() - t0)
        index += 1
        if ends[-1] >= ctx["seconds"]:
            break
    window = ends[-1]
    tracer.stop()
    print("window batches (s): " + " ".join(
        f"{b - a:.4f}" for a, b in zip([0.0] + ends, ends)), file=sys.stderr)
    n = len(outs)
    counters = {"images": n * B, "window_s": window,
                "image_flops": system.image_flops()}

    rng = np.random.default_rng([seed, 1 << 20])
    k = int(rng.integers(0, n))
    half = B // 2
    n_sample = int(traffic.get("sample", 2))
    picks = sorted({int(rng.integers(0, half)) for _ in range(n_sample // 2)}
                   | {int(rng.integers(half, B)) for _ in range(n_sample - n_sample // 2)})
    sample_out = np.asarray(outs[k], np.float32)[picks]
    tokens, x0 = mod.batch_inputs(cfg, seed, k + 1, B)

    def release():
        outs.clear()
        system.release()

    def check():
        ref = mod.reference(cfg, seed, traffic)(tokens[picks], x0[picks])
        err = mod.rel_err(sample_out, ref)
        return {"latent_rel_err": {"value": err,
                                   "limit": cfg["limits"]["latent_rel_err"]}}

    return {"metrics": {"images_per_s": n * B / window, "setup_s": setup_s},
            "counters": counters, "attempted": n * B, "failed": 0,
            "release": release, "check": check}
