"""The SD3 cell's run with the timed path broken underneath must come out
not correct; as ``test_faults.py``, at a tiny MMDiT and tiny text towers
on the CPU (2 blocks, 8x8x4 latents, 3 + 4 text tokens, 23 joint tokens),
with the harness's look for a chip skipped."""

import json

import jax.numpy as jnp
import pytest

from bench import run as R
from bench.reference import mmdit as RM

SEED = 2**31 + 977
CELL = "sd3m_f050_b2"
TINY_SD3 = dict(sample_size=8, patch_size=2, in_channels=4, out_channels=4,
                num_layers=2, num_attention_heads=2, attention_head_dim=16,
                joint_attention_dim=48, caption_projection_dim=32,
                pooled_projection_dim=24, pos_embed_max_size=12, clip_len=3,
                t5_len=4, steps=6,
                text_encoders={"vocab": 64, "layers": {"clip_l": 1, "clip_g": 1, "t5": 1}})
# CLIP-L + bigG = the pooled width, T5 = the context width
TINY_TOWERS = {"clip_l": (16, 2, 32), "clip_g": (8, 2, 16), "t5": (48, 2, 64)}


@pytest.fixture()
def cell(monkeypatch):
    from repro.models import frontends as F
    monkeypatch.setattr(F, "SD3_TOWERS", TINY_TOWERS)
    monkeypatch.setattr(RM, "TOWERS", TINY_TOWERS)
    c = R.Cell(json.loads((R.ROOT / "BENCHMARK.json").read_text()), CELL)
    c.cfg.update(TINY_SD3)
    return c


def run(c, variant="program"):
    return R.run_cell(c, SEED, 0.5, False, t_start=R.boot_clock(),
                      require_chip=False, variant=variant)


def test_sd3_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] % 2 == 0 and out["attempted"] >= 2


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
def test_sd3_fault_is_caught(kind, cell, monkeypatch):
    call = cell.config.System.__call__

    def broken(self, tokens, x0):
        out = call(self, tokens, x0)
        if kind == "state_unchanged":        # every step returns x as is
            return jnp.asarray(x0, out.dtype)
        if kind == "half_batch":             # second half never computed
            return out.at[1:].set(out[:1])
        return out.at[-1].multiply(-1.0)     # one image changed at the end

    monkeypatch.setattr(cell.config.System, "__call__", broken)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_sd3_fp8_control_is_not_correct(cell):
    out = run(cell, "control_fp8")
    assert not out["correct"], out["checks"]
