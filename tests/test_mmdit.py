"""SD3's MMDiT (``models/mmdit.py``) and the flow-matching loop against the
plain reference (``bench/reference/mmdit.py``) on seeded weights, at a CPU
size: 2 blocks (the last context-pre-only), 16 image and 7 text tokens,
so the joint sequence of 23 needs key padding on the kernel path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mmdit as RM
from bench.reference import transformer as RT
from repro.configs.base import MMDiTConfig
from repro.configs.registry import SD3_MEDIUM as SD3
from repro.core.pipeline import SDPipeline
from repro.core.schedules import FlowSchedule
from repro.core.selective import GuidancePlan
from repro.models import frontends as F
from repro.models import layers as L
from repro.models import mmdit as M

CFG = MMDiTConfig().reduced()
# text towers at the reduced context: CLIP-L + bigG = the pooled width
# (24), T5 = the context width (48)
TOWERS = {"clip_l": (16, 2, 32), "clip_g": (8, 2, 16), "t5": (48, 2, 64)}


def ref_cfg(cfg=CFG, **kw):
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d.update(shift=3.0, text_encoders={"vocab": 64,
                                       "layers": {"clip_l": 1, "clip_g": 1, "t5": 1}})
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def weights():
    return RM.mmdit_params(RT.Draw(jax.random.PRNGKey(7), jnp.float32), ref_cfg())


def _inputs(B=2):
    k = jax.random.PRNGKey(11)
    x = jax.random.normal(k, (B, CFG.sample_size, CFG.sample_size, CFG.in_channels))
    ctx = jax.random.normal(jax.random.fold_in(k, 1),
                            (B, CFG.text_tokens, CFG.joint_attention_dim))
    pooled = jax.random.normal(jax.random.fold_in(k, 2), (B, CFG.pooled_projection_dim))
    return x, jnp.array([987.4, 31.0]), ctx, pooled


def test_param_layout_matches_reference(weights):
    """The program's ``init_mmdit`` and the reference's draw give the same
    tree of the same shapes: one set of arrays drives both."""
    mine = M.init_mmdit(CFG, L.ArrayMaker(jax.random.PRNGKey(0)))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(weights)
    assert "out" not in mine["blocks"][-1]["y"] and "out" in mine["blocks"][0]["y"]


def test_pos_embed_matches_reference_at_published_size():
    """The centre crop of SD3's 192x192 sin-cos grid to the 64x64 patch
    grid of a 1024x1024 image; float32 against float64 sines of
    arguments up to 64 (6e-6 relative)."""
    got = np.asarray(M.pos_embed(SD3))
    want = np.asarray(RM.pos_embed(ref_cfg(SD3)))
    assert got.shape == (4096, 1536)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_forward_matches_reference_einsum(weights):
    """The CPU path (einsum attention, f32 products) against the reference
    at ``highest``: the same arithmetic up to summation order (1e-4 on
    outputs of size ~1)."""
    x, t, ctx, pooled = _inputs()
    out = M.mmdit_forward(weights, CFG, x, t, (ctx, pooled))
    with jax.default_matmul_precision("highest"):
        ref = RM.mmdit(weights, ref_cfg(), x, t, ctx, pooled)
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("blocks", [(8, 8), (16, 8), (8, 16), (8, 24), (16, 32)])
def test_forward_matches_reference_kernel(weights, monkeypatch, blocks):
    """The TPU path's flash kernel (interpreted) over the 23-token joint
    sequence padded to a block multiple (24, or 32 with a kv tile of
    padding alone at (16, 8)), with the online softmax or, over one kv
    block, the one-pass body (24 keys in one block; 32 keys, two q
    tiles): within bf16 rounding of q, k, v and p (3e-2 on outputs of size
    ~1, the kernel's own bf16 tolerance scaled through two blocks)."""
    monkeypatch.setattr(M, "_joint_blocks", lambda n: blocks)
    calls = []
    kernel = M.flash_attention_pallas

    def spy(q, k, v, **kw):
        calls.append((q.shape[1], kw["kv_len"]))
        return kernel(q, k, v, **kw)

    monkeypatch.setattr(M, "flash_attention_pallas", spy)
    x, t, ctx, pooled = _inputs()
    out = M.mmdit_forward(weights, CFG, x, t, (ctx, pooled))
    with jax.default_matmul_precision("highest"):
        ref = RM.mmdit(weights, ref_cfg(), x, t, ctx, pooled)
    padded = M._padded(23, blocks)
    assert calls == [(padded, 23)] * CFG.num_layers
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-2, atol=3e-2)


def test_joint_blocks_rule(monkeypatch):
    """Off a TPU the joint attention takes the einsum path. On one: a
    single kv tile over the sequence padded to 128 lanes, q tiles of the
    largest multiple of 8 up to 1120 dividing it."""
    assert M._joint_blocks(4429) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert M._joint_blocks(4429) == (1120, 4480)     # SD3 at 1024x1024
    assert M._padded(4429, (1120, 4480)) == 4480
    assert M._joint_blocks(4608) == (768, 4608)
    assert M._joint_blocks(1357) == (704, 1408)      # SD3 at 512x512


def test_flow_sigmas_shift3():
    """diffusers' shift-3 sigmas at 28 steps, by hand: the training grid's
    sigma_min is shift(1/1000) = 0.003/1.002, the 28 points run from 1 to
    it and are shifted again, then 0."""
    s = FlowSchedule(3.0).sigmas(28)
    smin = 0.003 / 1.002
    u = np.linspace(1.0, smin, 28)
    assert s.shape == (29,) and s[0] == 1.0 and s[-1] == 0.0
    np.testing.assert_allclose(s[:-1], 3 * u / (1 + 2 * u), rtol=1e-12)
    np.testing.assert_allclose(s[-2], 3 * smin / (1 + 2 * smin), rtol=1e-12)
    np.testing.assert_allclose(s[1], 3 * u[1] / (1 + 2 * u[1]), rtol=1e-12)
    np.testing.assert_allclose(s, RM.flow_sigmas(28, 3.0), rtol=1e-12)


@pytest.fixture()
def small_towers(monkeypatch):
    monkeypatch.setattr(F, "SD3_TOWERS", TOWERS)
    monkeypatch.setattr(RM, "TOWERS", TOWERS)


def _pipeline(rcfg, seed):
    params = RM.init_params(rcfg, seed)
    t = rcfg["text_encoders"]
    tcfgs = F.sd3_text_configs(t["vocab"], t["layers"])
    n = rcfg["clip_len"]
    encode = jax.jit(lambda p, tk: F.encode_sd3(p, tcfgs, tk[:, :n], tk[:, n:],
                                                rcfg["joint_attention_dim"]))
    pipe = SDPipeline(CFG, params, FlowSchedule(3.0))
    return params, pipe, encode


def _tokens(B=2):
    rng = np.random.default_rng(5)
    tk = np.zeros((B, CFG.text_tokens), np.int32)
    tk[:, :2] = rng.integers(4, 64, (B, 2))
    tk[:, CFG.clip_len:CFG.clip_len + 3] = rng.integers(4, 64, (B, 3))
    return jnp.asarray(tk)


def test_text_conditioning_matches_reference(small_towers):
    """The three towers' (context, pooled) against the reference's: the
    program's encoder block carries bf16 activations (its embedding is
    looked up in bf16), so 2e-2 absolute on states of size ~3."""
    rcfg = ref_cfg()
    params, _, encode = _pipeline(rcfg, 3)
    tk = _tokens()
    ctx, pooled = encode(params["text"], tk)
    with jax.default_matmul_precision("highest"):
        rctx, rpooled = RM.conditioning(params["text"], rcfg, *RM.split_tokens(rcfg, tk))
    assert ctx.shape == (2, CFG.text_tokens, CFG.joint_attention_dim)
    assert pooled.shape == (2, CFG.pooled_projection_dim)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(rctx), atol=2e-2)
    np.testing.assert_allclose(np.asarray(pooled), np.asarray(rpooled), atol=2e-2)
    # CLIP's 24 wide states are zero-padded to the 48 wide context
    np.testing.assert_array_equal(np.asarray(ctx[:, :CFG.clip_len, 24:]), 0.0)


def test_guided_flow_run_matches_reference(small_towers):
    """Six flow-matching Euler steps at guidance 7 under a selective plan
    (3 FULL, 3 COND) through ``SDPipeline.generate_jit(plan,
    stepper="flow")``, against the reference's loop on the same weights
    and conditioning: f32 on both sides, so only summation order differs
    (1e-4 relative on latents of size ~1, after guidance amplifies
    per-step differences 7x)."""
    rcfg = ref_cfg()
    params, pipe, encode = _pipeline(rcfg, 3)
    tk = _tokens()
    cond, uncond = encode(params["text"], tk), encode(params["text"], jnp.zeros_like(tk))
    x0 = jax.random.normal(jax.random.PRNGKey(4),
                           (2, CFG.sample_size, CFG.sample_size, CFG.in_channels))
    plan = GuidancePlan.suffix(6, 0.5, 7.0)
    out = pipe.generate_jit(plan, stepper="flow")(cond, uncond, x0, jax.random.PRNGKey(0))
    ref = RM.guided_flow(params["mmdit"], rcfg, cond, uncond, x0, steps=6,
                         scale=7.0, fraction=0.5)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 1e-4, rel
    # the selective plan matters: all FULL steps give other latents
    ref_full = RM.guided_flow(params["mmdit"], rcfg, cond, uncond, x0, steps=6,
                              scale=7.0, fraction=0.0)
    assert float(jnp.linalg.norm(ref_full - ref) / jnp.linalg.norm(ref)) > 1e-2


def test_scale1_full_equals_cond_bit_for_bit(small_towers):
    """At guidance 1 a FULL step's combine returns the conditional
    velocity itself: the all-FULL plan equals, bit for bit, the all-COND
    plan whose denoiser runs the same 2B rows and keeps the conditional
    half. With the denoiser at B rows the two agree to float32 rounding
    (1e-5: XLA's products over 2B and B rows round differently)."""
    from repro.core.guidance import merge_cond_uncond, split_cond_uncond
    from repro.core.sampler import sample
    params, pipe, encode = _pipeline(ref_cfg(), 1)
    tk = _tokens()
    cond, uncond = encode(params["text"], tk), encode(params["text"], jnp.zeros_like(tk))
    x0 = jax.random.normal(jax.random.PRNGKey(9),
                           (2, CFG.sample_size, CFG.sample_size, CFG.in_channels))
    v = pipe.eps_fn()

    def v_at_2b(x, t, c):
        two = v(merge_cond_uncond(x, x), jnp.concatenate([t, t]),
                merge_cond_uncond(c, uncond))
        return split_cond_uncond(two)[0]

    run = lambda plan, fn: jax.jit(lambda x: sample(fn, plan, pipe.sched, x, cond, uncond,
                                                    stepper="flow"))(x0)
    full = run(GuidancePlan.full(4, 1.0), v)
    np.testing.assert_array_equal(np.asarray(full),
                                  np.asarray(run(GuidancePlan.suffix(4, 1.0, 1.0), v_at_2b)))
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(run(GuidancePlan.suffix(4, 1.0, 1.0), v)),
                               rtol=1e-5, atol=1e-5)


def test_flow_stepper_needs_flow_schedule(weights):
    """The ``flow`` stepper steps a ``FlowSchedule`` only, and the DDIM
    family a ``NoiseSchedule`` only."""
    from repro.core.sampler import sample
    from repro.core.schedules import NoiseSchedule
    x, _, ctx, pooled = _inputs()
    fn = lambda x, t, c: x
    with pytest.raises(ValueError):
        sample(fn, GuidancePlan.full(2, 2.0), NoiseSchedule.sd_default(), x,
               (ctx, pooled), (ctx, pooled), stepper="flow")
    with pytest.raises(ValueError):
        sample(fn, GuidancePlan.full(2, 2.0), FlowSchedule(), x,
               (ctx, pooled), (ctx, pooled), stepper="ddim")
