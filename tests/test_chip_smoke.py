"""``chip_smoke.py``'s phases at reduced size on the CPU.

The script itself refuses to run without a TPU; its phases are functions
of their configuration, so the same code paths run here with smoke
widths, interpreted kernels and the gather attention form.
"""

import json

import jax
import pytest

import chip_smoke as CS
from repro.configs import get_smoke_config
from repro.configs.sd_unet import CONFIG as SD_SMALL


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("llama3.2-1b")


def _args(cfg):
    return CS.serve_args(cfg, requests=3, prompt_len=16, max_new=6,
                         fraction=0.5, batch=2, seed=0)


def test_main_refuses_without_a_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert CS.main([]) == 2
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_kernel_phase_small(cfg):
    errs = CS.kernel_phase(cfg, rows=4, num_pages=16, page_sizes=(4,),
                           nb_pos=16, vocab_rows=2, latent_shape=(2, 8, 8, 4),
                           seed=0)
    assert set(errs) == set(CS.KERNEL_TOL)


def test_serve_phase_small(cfg):
    out = CS.serve_phase(cfg, _args(cfg))
    # 3 requests, T=6, f=0.5: 2*6 - 3 passes each, against 2*6 with full CFG
    assert out["passes"] == 3 * 9 and out["baseline"] == 3 * 12
    assert out["pallas_in_step"] is False          # gather form off-TPU


def test_pipeline_phase_small():
    out = CS.pipeline_phase(SD_SMALL.reduced(), batch=2, steps=4,
                            fractions=(0.0, 0.5),
                            combines=("cfg", "apg"), seed=0)
    assert out == {("cfg", 0.0): 8, ("cfg", 0.5): 6,
                   ("apg", 0.0): 8, ("apg", 0.5): 6}


def test_multichip_phase_on_available_devices(cfg):
    devs = jax.devices()[:4]
    out = CS.multichip_phase(cfg, _args(cfg), devices=devs,
                             pool_bytes=2**20 * len(devs))
    assert len(out["per_device"]) == len(devs)
    assert len(set(out["per_device"])) == 1
