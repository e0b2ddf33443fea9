"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a benchmark run (driver, release, reference
comparison) at a tiny size on the CPU, skipping only the harness's look
for a chip, once with the program as it is (correct) and once with one
fault planted (not correct). The faults are those each cell can have: a
step that returns its state unchanged, half of the batch left out, an
image altered where it is produced. A cell on one chip has no exchange
between chips to leave out.
"""

import json

import jax.numpy as jnp
import pytest

from bench import run as R

SEED = 2**31 + 977

TINY_SD = dict(base_channels=32, channel_mults=[1, 2], num_res_blocks=1,
               attn_resolutions=[1], num_heads=2, text_dim=64, text_len=16,
               latent_size=8, time_dim=64, norm_groups=8, steps=6,
               text_encoder={"layers": 4, "vocab": 4096, "heads": 2, "ff": 256})


SPEC = json.loads((R.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def cell(name, cfg_update):
    c = R.Cell(SPEC, name)
    c.cfg.update(cfg_update)
    return c


def run(c, seconds=0.5):
    return R.run_cell(c, SEED, seconds, False, t_start=R.boot_clock(),
                      require_chip=False)


def _sd_fault(kind):
    def broken(call):
        def f(self, tokens, x0):
            out = call(self, tokens, x0)
            if kind == "state_unchanged":        # every step returns x as is
                return jnp.asarray(x0, out.dtype)
            if kind == "half_batch":             # second half never computed
                half = out.shape[0] // 2
                return out.at[half:].set(out[:half])
            if kind == "answer_altered":         # one image changed at the end
                return out.at[-1].multiply(-1.0)
            raise ValueError(kind)
        return f
    return broken


@pytest.mark.parametrize("name", CELLS)
def test_sd_sound_run_is_correct(name):
    assert run(cell(name, TINY_SD))["correct"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
def test_sd_fault_is_caught(kind, name, monkeypatch):
    c = cell(name, TINY_SD)
    monkeypatch.setattr(c.config.System, "__call__",
                        _sd_fault(kind)(c.config.System.__call__))
    out = run(c)
    assert not out["correct"], out["checks"]



@pytest.mark.parametrize("name", CELLS)
def test_sd_fp8_control_is_not_correct(name):
    out = R.run_cell(cell(name, TINY_SD), SEED, 0.5, False, t_start=R.boot_clock(),
                     require_chip=False, variant="control_fp8")
    assert not out["correct"], out["checks"]
