import os
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)                       # benchmarks.* imports
sys.path.insert(0, os.path.join(_ROOT, "src"))  # repro.* without PYTHONPATH

import jax
import pytest

# Tests run on the single real CPU device (the dry-run manages its own
# 512-device world in a separate process). Keep x64 off (TPU-realistic).
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
