"""Record the small chip trace that ``test_trace_reduce.py`` checks.

    python3 bench/tests/record_trace.py <out_dir>

Inside one ``bench.window`` span: three runs of a 1024x1024 bf16 matrix
product chain, each inside a ``bench.batch`` span, with a host sleep of
20 ms (inside a ``bench.sleep`` span) between them, so the device is idle
for known stretches that the reduction must name by their host span.
"""

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str):
    out = Path(out)
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda a: (a @ a) @ a)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    f(a).block_until_ready()
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.batch"):
                f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
