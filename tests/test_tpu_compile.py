"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling and
VMEM rules; the TPU compiler installed with jax can, for a chip that is
described rather than attached. Each test lowers one kernel at
llama3.2-1b widths (H=32, K=8, hd=64, vocab 128256; R=16 ragged rows,
512 pages) or at the SD-UNet latent shape, compiles it for one v5e
chip, and checks the kernel survived as a ``tpu_custom_call``. The
UNet's attention block is compiled whole at SD-1.5's 64x64 and 32x32
levels, where its self-attention must take the flash kernel and leave
no score tensor in HBM; so is SD3's joint attention over 4096 + 333
tokens, padded for the kernel. Nothing runs, so these say nothing about
results or time.

The topology is described only inside the module fixture: the TPU
library admits one loader per process at a time, so describing it while
modules are imported would make test collection differ across workers.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cfg_combine import (apg_combine_pallas, cfg_combine_pallas,
                                       cfg_combine_rowscale_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_decode_attention import (
    ragged_paged_decode_attention_int8_pallas,
    ragged_paged_decode_attention_pallas)
from repro.models import layers as L
from repro.models import mmdit as M
from repro.models import unet as U

R, H, K, HD, PAGES, VOCAB = 16, 32, 8, 64, 512, 128256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be
    # read back without one: keep these compiles out of any cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("page_size", [8, 16])
def test_ragged_paged_decode_bf16_compiles(one_chip, page_size):
    nb = 256 // page_size
    _compile(functools.partial(ragged_paged_decode_attention_pallas,
                               interpret=False), one_chip,
             ((R, H, HD), jnp.bfloat16),
             ((PAGES, page_size, K, HD), jnp.bfloat16),
             ((PAGES, page_size, K, HD), jnp.bfloat16),
             ((R, nb), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32))


@pytest.mark.parametrize("page_size", [8, 16])
def test_ragged_paged_decode_int8_compiles(one_chip, page_size):
    nb = 256 // page_size
    _compile(functools.partial(ragged_paged_decode_attention_int8_pallas,
                               interpret=False), one_chip,
             ((R, H, HD), jnp.bfloat16),
             ((PAGES, page_size, K, HD), jnp.int8),
             ((PAGES, page_size, K, 1), jnp.float32),
             ((PAGES, page_size, K, HD), jnp.int8),
             ((PAGES, page_size, K, 1), jnp.float32),
             ((R, nb), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32))


@pytest.mark.parametrize("shape", [(8, VOCAB), (2, 64, 64, 4)])
def test_cfg_combine_compiles(one_chip, shape):
    _compile(lambda u, c: cfg_combine_pallas(u, c, 7.5, interpret=False),
             one_chip, (shape, jnp.float32), (shape, jnp.float32))


@pytest.mark.parametrize("rows", [2, 8, 13])
def test_apg_combine_compiles(one_chip, rows):
    _compile(lambda u, c: apg_combine_pallas(u, c, 7.5, eta=0.2,
                                             threshold=2.0, interpret=False),
             one_chip, ((rows, VOCAB), jnp.float32),
             ((rows, VOCAB), jnp.float32))


@pytest.mark.parametrize("rows", [2, 8, 13])
def test_rowscale_combine_compiles(one_chip, rows):
    _compile(lambda u, c, s: cfg_combine_rowscale_pallas(u, c, s,
                                                         interpret=False),
             one_chip, ((rows, VOCAB), jnp.float32),
             ((rows, VOCAB), jnp.float32), ((rows,), jnp.float32))


def test_flash_attention_compiles(one_chip):
    _compile(functools.partial(flash_attention_pallas, interpret=False),
             one_chip, ((1, 2048, H, HD), jnp.bfloat16),
             ((1, 2048, K, HD), jnp.bfloat16),
             ((1, 2048, K, HD), jnp.bfloat16))


@pytest.mark.parametrize("hw,c", [(64, 320), (32, 640)])
def test_unet_attnblock_takes_flash_kernel(one_chip, monkeypatch, hw, c):
    """SD-1.5's attention block at its 64x64 and 32x32 levels, 8 rows (a
    batch of 4 with CFG), 8 heads, 77x768 text: the self-attention
    compiles to the flash kernel and no f32[8,8,N,N] score tensor is
    left (4.3 GB at 64x64 on the einsum path)."""
    # the dispatch asks the platform, which is the CPU here; the compile
    # is for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = hw * hw
    assert U._flash_blocks(n) == U.FLASH_BLOCKS
    shapes = jax.eval_shape(lambda: U.init_attnblock(
        L.ArrayMaker(jax.random.PRNGKey(0)), c, 8, 768))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    x = jax.ShapeDtypeStruct((8, hw, hw, c), jnp.float32, sharding=one_chip)
    text = jax.ShapeDtypeStruct((8, 77, 768), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda p, x, t: U.attnblock(p, x, t, 8, 32))
    hlo = fn.lower(params, x, text).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert f"f32[8,8,{n},{n}]" not in hlo


def test_mmdit_joint_attention_takes_flash_kernel(one_chip, monkeypatch):
    """SD3-Medium's joint attention at its published widths, 4 rows (a
    batch of 2 with CFG), 24 heads of 64 over 4096 image and 333 text
    tokens: it compiles to the flash kernel over the sequence padded to a
    block multiple, and leaves no f32[4,24,N,N] score tensor, at 4429 or
    at the padded length (7.5 GB at 4429 on the einsum path)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from repro.configs.registry import SD3_MEDIUM as cfg
    n, m, d = cfg.image_tokens, cfg.text_tokens, cfg.hidden
    blocks = M._joint_blocks(n + m)
    padded = M._padded(n + m, blocks)
    assert (n + m, blocks, padded) == (4429, (448, 4480), 4480)
    qx = jax.ShapeDtypeStruct((4, n, 3 * d), jnp.float32, sharding=one_chip)
    qy = jax.ShapeDtypeStruct((4, m, 3 * d), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda a, b: M.joint_attention(a, b, cfg.num_attention_heads))
    hlo = fn.lower(qx, qy).compile().as_text()
    assert "tpu_custom_call" in hlo
    for s in (n + m, padded):
        assert f"f32[4,24,{s},{s}]" not in hlo and f"bf16[4,24,{s},{s}]" not in hlo
