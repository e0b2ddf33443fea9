"""Record the small chip trace of named-scope loops that
``test_scope_reduce.py`` checks.

    python3 bench/tests/record_scan_trace.py <out_dir>

One program of two ``lax.scan`` loops named as the pipeline names its
steps: 5 steps under ``sd.step.full`` over a 1024x1024 bf16 carry, then 3
under ``sd.step.cond`` over its first 512 rows. In each step the matrix
product runs under ``unet`` and the update under ``sd.update`` (on a v5e
the two fuse into one op, named by ``unet``). Inside one
``bench.window`` span: a 20 ms host sleep (``bench.sleep``), then one run
of the program (``bench.batch``, its dispatch inside ``sd.generate``).
"""

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

FULL_STEPS, COND_STEPS = 5, 3


def step(scope):
    def body(x, w):
        with jax.named_scope(scope):
            with jax.named_scope("unet"):
                y = x @ w
            with jax.named_scope("sd.update"):
                return jnp.tanh(y) * 0.5 + x * 0.5, None
    return body


@jax.jit
def run(x, w):
    ws = jnp.broadcast_to(w, (FULL_STEPS,) + w.shape)
    x, _ = jax.lax.scan(step("sd.step.full"), x, ws)
    y, _ = jax.lax.scan(step("sd.step.cond"), x[:512], ws[:COND_STEPS])
    return y


def main(out: str):
    out = Path(out)
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.eye(1024, dtype=jnp.bfloat16)
    run(x, w).block_until_ready()
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.batch"):
            with jax.profiler.TraceAnnotation("sd.generate"):
                y = run(x, w)
            y.block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
