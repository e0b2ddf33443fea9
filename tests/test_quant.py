"""Int8 KV pages (DESIGN.md §11): the bounded-exactness contract.

The bf16 default is pinned bit-identical elsewhere (``tests/test_paged.py``
— untouched); the deliberately lossy int8 path pins instead:

* quantize/dequant roundtrip error bounds over adversarial page contents
  (zeros, single-outlier rows, denormals) — hypothesis property;
* fused dequantizing kernel vs the ``ref.py`` oracle within atol for
  random block tables / mixed prompt lengths;
* :class:`PageAllocator` paired-pool refcount conservation with int8
  pages (one refcount governs values + scales; grow/cow/copy_page keep
  the pair consistent);
* greedy token identity int8 vs bf16 on short golden traces at serving
  scale (eager and lazy/shared/CoW configs);
* the ISSUE-5 roofline acceptance: pure-COND ``memory_s`` drops >= 1.4x
  at int8 and the autotuned pass budget never shrinks;
* the :class:`BudgetAutotuner` dtype-keying fix (same occupancy, two
  dtypes -> two entries, worst-of governs).

CI job ``kv-int8`` runs this file via ``-m quant``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.selective import GuidancePlan
from repro.kernels.paged_decode_attention import (
    paged_decode_attention_int8_pallas)
from repro.kernels.quant import (EPS, dequantize_kv, dequantize_page,
                                 quantize_kv, quantize_page, roundtrip_bound)
from repro.kernels.ref import (ref_paged_decode_attention,
                               ref_paged_decode_attention_int8)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import (BudgetAutotuner, ContinuousEngine, PageAllocator,
                         ServeRequest, SimRequest, kv_page_bytes, page_nbytes,
                         paged_partition_specs, pages_for,
                         pages_for_pool_bytes, simulate)

pytestmark = pytest.mark.quant


# ---------------------------------------------------------------------------
# Roundtrip bounds over adversarial page contents (hypothesis)
# ---------------------------------------------------------------------------


def _adversarial_page(seed: int, case: str, shape=(4, 2, 16)) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    if case == "zeros":
        x = np.zeros(shape, np.float32)
    elif case == "outlier":
        # one element per row dwarfs the rest: the per-row scale is set by
        # the outlier, the remaining mass quantizes near zero
        x = x * 1e-3
        x[..., 0] = rng.choice([-1.0, 1.0], shape[:-1]) * 1e4
    elif case == "denormal":
        x = x * 1e-42                       # below fp32 normal range
    elif case == "mixed":
        x[0] = 0.0
        x[1] *= 1e-42
        x[2, :, 0] = 3e4
    return x


@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.sampled_from(["random", "zeros", "outlier", "denormal", "mixed"]))
def test_quantize_roundtrip_bound(seed, case):
    """§11 contract: elementwise |x - deq(quant(x))| <= max(amax, EPS)/254
    per (position, kv-head) row, on every adversarial content class."""
    x = _adversarial_page(seed, case)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert np.isfinite(np.asarray(s)).all()
    err = np.abs(np.asarray(dequantize_kv(q, s)) - x)
    bound = np.asarray(roundtrip_bound(x))
    assert (err <= bound * (1 + 1e-5) + 1e-30).all(), \
        (case, err.max(), bound.max())


def test_quantize_exact_and_edge_cases():
    zeros = np.zeros((4, 2, 16), np.float32)
    q, s = quantize_kv(zeros)
    assert (np.asarray(dequantize_kv(q, s)) == 0).all()   # zeros: exact
    # denormal rows quantize to zero and stay under the bound
    den = np.full((2, 1, 8), 1e-42, np.float32)
    qd, sd = quantize_kv(den)
    assert (np.asarray(qd) == 0).all()
    assert np.abs(np.asarray(dequantize_kv(qd, sd)) - den).max() <= EPS
    # a single outlier is recovered to within half a step of the row amax
    out = np.zeros((1, 1, 8), np.float32)
    out[0, 0, 3] = 1234.5
    qo, so = quantize_kv(out)
    err = abs(float(dequantize_kv(qo, so)[0, 0, 3]) - 1234.5)
    assert err <= 1234.5 / 254 * (1 + 1e-5)
    # the jitted page-granular entry points match the inline forms
    qp, sp = quantize_page(jnp.asarray(out))
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qo))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(so))
    np.testing.assert_array_equal(
        np.asarray(dequantize_page(qp, sp, jnp.float32)),
        np.asarray(dequantize_kv(qo, so, jnp.float32)))


# ---------------------------------------------------------------------------
# Fused dequantizing kernel vs oracle (random block tables, mixed lengths)
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=1000),
       st.sampled_from([None, 6]))
def test_int8_kernel_matches_oracle(seed, window):
    """Kernel == dequantizing oracle within atol for random block tables
    (out-of-range padding entries included) and mixed per-row positions;
    both sit within the propagated quantization tolerance of the
    full-precision paged reference."""
    key = jax.random.PRNGKey(seed)
    P_, ps, K, hd, B, H, nb = 12, 4, 2, 16, 3, 4, 5
    kf = jax.random.normal(key, (P_, ps, K, hd), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(key, 1), (P_, ps, K, hd),
                           jnp.float32)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, H, hd), jnp.float32)
    bt = jax.random.randint(jax.random.fold_in(key, 3), (B, nb), 0, P_ + 3)
    pos = jax.random.randint(jax.random.fold_in(key, 4), (B,), 0, nb * ps)
    out_k = paged_decode_attention_int8_pallas(q, kq, ks, vq, vs, bt, pos,
                                               window=window, interpret=True)
    out_r = ref_paged_decode_attention_int8(q, kq, ks, vq, vs, bt, pos,
                                            window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=3e-5, atol=3e-5)
    out_f = ref_paged_decode_attention(q, kf, vf, bt, pos, window=window)
    # quantization tolerance: KV rel-error <= 1/254 of the row amax
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_f),
                               rtol=0.1, atol=0.1)


def test_attn_decode_paged_int8_pallas_matches_jnp(monkeypatch):
    """The kernel path (the TPU's choice; interpreted here) runs the int8
    model path through the fused kernel; outputs and the written pool
    pages (values + scales) match the jnp dequantizing path."""
    cfg = get_smoke_config("llama3.2-1b")
    key = jax.random.PRNGKey(3)
    p = A.init_attention(cfg, L.ArrayMaker(key))
    pool = A.paged_cache_spec(
        cfg, lambda shape, axes, **kw: jnp.zeros(
            shape, kw.get("dtype") or jnp.bfloat16), 8, 4, kv_dtype="int8")
    # pre-populate with quantized random history
    hist = jax.random.normal(jax.random.fold_in(key, 1),
                             (8, 4, cfg.num_kv_heads, cfg.resolved_head_dim),
                             jnp.float32)
    for name in ("k", "v"):
        vals, scales = quantize_kv(hist)
        pool[name] = vals
        pool[name + "_scale"] = scales
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 1, cfg.d_model),
                          jnp.float32)
    bt = jnp.asarray([[0, 2, 9], [5, 1, 3]], jnp.int32)   # incl. OOB pad
    pos = jnp.asarray([6, 11], jnp.int32)
    monkeypatch.setattr(A, "_paged_kernel", lambda: False)
    out_jnp, pool_jnp = A.attn_decode_paged(p, cfg, x, pool, bt, pos)
    monkeypatch.setattr(A, "_paged_kernel", lambda: True)
    out_pl, pool_pl = A.attn_decode_paged(p, cfg, x, pool, bt, pos)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_jnp),
                               rtol=3e-5, atol=3e-5)
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(pool_pl[name]),
                                      np.asarray(pool_jnp[name]))


# ---------------------------------------------------------------------------
# Specs / sharding / byte accounting
# ---------------------------------------------------------------------------


def test_int8_specs_scales_and_bf16_structure_unchanged():
    cfg = get_smoke_config("llama3.2-1b")
    spec8 = A.paged_cache_spec(cfg, L.SpecMaker(jnp.bfloat16), 8, 4,
                               kv_dtype="int8")
    assert set(spec8) == {"k", "v", "k_scale", "v_scale"}
    assert spec8["k"].dtype == jnp.int8
    assert spec8["k_scale"].dtype == jnp.float32
    assert spec8["k_scale"].shape == (8, 4, cfg.num_kv_heads, 1)
    # the bf16 default layout is byte-for-byte what it was before int8
    spec16 = A.paged_cache_spec(cfg, L.SpecMaker(jnp.bfloat16), 8, 4)
    assert set(spec16) == {"k", "v"}
    assert spec16["k"].dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        A.paged_cache_spec(cfg, L.SpecMaker(jnp.bfloat16), 8, 4,
                           kv_dtype="fp4")


def test_int8_partition_specs_shard_scales_alongside_pages():
    """Scale tensors reuse the ``pages``/``page`` logical names, so the
    §3 rule tables shard them exactly like the values — same mesh axis on
    the pool dim, every mesh axis at most once per tensor."""
    from jax.sharding import AbstractMesh, AxisType

    from repro.dist.sharding import RULES_SERVE

    cfg = get_smoke_config("llama3.2-1b")
    mesh = AbstractMesh((4, 2), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
    specs = paged_partition_specs(cfg, 16, 8, rules=RULES_SERVE, mesh=mesh,
                                  kv_dtype="int8")
    layers = [d for d in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, dict))]
    assert layers
    for layer in layers:
        assert set(layer) == {"k", "v", "k_scale", "v_scale"}
        for name in ("k", "v"):
            assert layer[name + "_scale"][:2] == layer[name][:2], \
                "scales must follow their values' pool sharding"
        for spec in layer.values():
            flat = [a for e in spec
                    for a in ((e,) if isinstance(e, str) else e or ())]
            assert len(flat) == len(set(flat))
    assert any(len(s) > 1 and s[1] == "data"
               for layer in layers for s in layer.values())


def test_kv_page_bytes_dtype_aware():
    """Spec-derived and model-free page pricing agree; int8 pages pin
    < 1/1.4 of bf16 bytes (the roofline acceptance's memory headroom)."""
    cfg = get_smoke_config("llama3.2-1b")
    for dt in ("bf16", "int8"):
        assert kv_page_bytes(cfg, 4, dt) == page_nbytes(
            4, cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers, dt)
    bf, i8 = kv_page_bytes(cfg, 4, "bf16"), kv_page_bytes(cfg, 4, "int8")
    assert bf / i8 >= 1.4
    pool_bytes = 10 * bf
    assert pages_for_pool_bytes(cfg, pool_bytes, 4, "bf16") == 10
    assert pages_for_pool_bytes(cfg, pool_bytes, 4, "int8") \
        == pool_bytes // i8 > 10


# ---------------------------------------------------------------------------
# PageAllocator paired pools (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["alloc", "grow", "free", "share",
                                           "cow"]),
                          st.integers(min_value=0, max_value=7),
                          st.integers(min_value=0, max_value=5)),
                min_size=1, max_size=50))
def test_page_allocator_paired_pool_invariants_int8(ops):
    """The int8 allocator's refcount table governs values *and* scales:
    every grant/grow/share/cow/free sequence conserves the pool exactly
    as under bf16 (one physical index addresses the pair), and ``check``
    holds after every op."""
    alloc = PageAllocator(16, page_size=4, kv_dtype="int8")
    assert alloc.kv_dtype == "int8"
    live: list[tuple[str, str]] = []
    for i, (op, owner, n) in enumerate(ops):
        uid, stream = f"r{owner}", ("c", "u")[n % 2]
        key = (uid, stream)
        if op == "alloc" and key not in alloc._owned:
            if alloc.alloc(uid, stream, n) is not None:
                live.append(key)
        elif op == "grow" and key in alloc._owned:
            alloc.grow(uid, stream, max(1, n))
        elif op == "free" and live:
            uid, stream = live.pop(n % len(live))
            alloc.free(uid, stream)
        elif op == "share" and live:
            src = live[n % len(live)]
            skey = (f"s{i}", "c")
            if skey not in alloc._owned and alloc.owned(*src):
                alloc.share(*skey, alloc.owned(*src))
                live.append(skey)
        elif op == "cow" and live:
            uid, stream = live[n % len(live)]
            owned = alloc.owned(uid, stream)
            shared = [j for j, pg in enumerate(owned)
                      if alloc.refcount(pg) > 1]
            if shared:
                alloc.cow(uid, stream, shared[0])
        alloc.check()
    for uid, stream in list(live):
        alloc.free(uid, stream)
        alloc.check()
    assert alloc.n_free == alloc.num_pages


def test_page_allocator_rejects_unknown_dtype():
    with pytest.raises(ValueError):
        PageAllocator(4, 2, kv_dtype="fp8")


# ---------------------------------------------------------------------------
# Engine: paired-pool device ops + greedy token identity (smoke model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    return cfg, params


def _engine(params, cfg, kv_dtype, **kw):
    args = dict(num_slots=4, pass_budget=4, prompt_len=8, max_new=6,
                selective_fraction=0.5, stop_on_eos=False, kv="paged",
                page_size=4, prefills_per_tick=2, kv_dtype=kv_dtype)
    args.update(kw)
    return ContinuousEngine(params, cfg, **args)


def test_int8_requires_paged(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError):
        ContinuousEngine(params, cfg, kv="slot", kv_dtype="int8")


def test_copy_page_copies_values_and_scales(small_model):
    """The CoW device copy moves the *pair*: a page's int8 payload and its
    scales travel through the same (src, dst), across stacked layers."""
    cfg, params = small_model
    eng = _engine(params, cfg, "int8")
    eng._init_paged_pool()
    rng = np.random.default_rng(0)

    def fill(leaf):
        if np.issubdtype(np.asarray(leaf).dtype, np.integer):
            return jnp.asarray(rng.integers(-127, 127, leaf.shape), leaf.dtype)
        return jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)

    eng._pool_p = jax.tree.map(fill, eng._pool_p)
    before = jax.tree.map(np.asarray, eng._pool_p)
    fn = eng._copy_page_fn()
    after = jax.tree.map(np.asarray, fn(eng._pool_p, np.int32(1), np.int32(5)))

    def one(b, a):
        if b.ndim == 5:                           # stacked (layers, P, ...)
            np.testing.assert_array_equal(a[:, 5], b[:, 1])
            np.testing.assert_array_equal(a[:, :5], b[:, :5])
        else:
            np.testing.assert_array_equal(a[5], b[1])

    jax.tree.map(one, before, after)
    layer = jax.tree.leaves(eng._pool_p,
                            is_leaf=lambda x: isinstance(x, dict))[0]
    assert set(layer) == {"k", "v", "k_scale", "v_scale"}


def test_int8_greedy_token_identity_eager(small_model):
    """ISSUE-5 acceptance: int8 greedy decode is token-identical to bf16
    on the short golden trace at serving scale (mid-flight arrivals,
    batched mixed-bucket prefills), and the pool drains balanced."""
    cfg, params = small_model
    reqs = lambda: [ServeRequest(uid=f"r{i}",
                                 prompt=f"the quick brown fox {i}",
                                 max_new_tokens=6) for i in range(4)]
    arrivals = [0, 0, 1, 3]
    out_bf = _engine(params, cfg, "bf16").serve_trace(reqs(), arrivals)
    e8 = _engine(params, cfg, "int8")
    out_i8 = e8.serve_trace(reqs(), arrivals)
    assert out_bf == out_i8
    assert all(len(v) == 6 for v in out_i8.values())
    assert e8.pages.n_free == e8.pages.num_pages
    assert e8.metrics.page_bytes == kv_page_bytes(cfg, 4, "int8")
    assert e8.metrics.peak_bytes_in_use \
        == e8.metrics.peak_pages_in_use * e8.metrics.page_bytes > 0


def test_int8_greedy_token_identity_lazy_shared_cow(small_model):
    """Same identity through the lazy path: prefix sharing, CoW
    divergence and on-demand growth all run on paired int8 pools."""
    cfg, params = small_model
    mixed = lambda: [ServeRequest(uid=f"r{i}",
                                  prompt=f"the quick brown fox {i}",
                                  max_new_tokens=6,
                                  prompt_len=(3, 5, 8, 8)[i])
                     for i in range(4)]
    arrivals = [0, 0, 1, 3]
    out_bf = _engine(params, cfg, "bf16",
                     reservation="lazy").serve_trace(mixed(), arrivals)
    e8 = _engine(params, cfg, "int8", reservation="lazy")
    out_i8 = e8.serve_trace(mixed(), arrivals)
    assert out_bf == out_i8
    m = e8.metrics
    assert m.shared_page_hits > 0 and m.cow_copies > 0 and m.pages_grown > 0
    assert e8.pages.n_free == e8.pages.num_pages


# ---------------------------------------------------------------------------
# Autotuner dtype keying + roofline acceptance
# ---------------------------------------------------------------------------


class _FakeCompiled:
    """Just enough executable surface for ``roofline.analyze``."""

    def __init__(self, byts: float):
        self._bytes = byts

    def cost_analysis(self):
        return {"flops": 0.0, "bytes accessed": self._bytes}

    def as_text(self):
        return ""

    def memory_analysis(self):
        class M:
            argument_size_in_bytes = 0
            output_size_in_bytes = 0
            temp_size_in_bytes = 0
        return M()


def test_autotuner_keys_include_kv_dtype():
    """Satellite regression: the same (n_full, n_cond) occupancy compiled
    at bf16 and int8 must keep *both* observations — keying on occupancy
    alone let the later compile overwrite the earlier one, so the
    worst-per-pass budget was priced off a stale dtype."""
    from repro.roofline import HBM_BW as hbm_bw
    t = BudgetAutotuner(target_tick_s=1.0, min_budget=2)
    t.observe((1, 0), _FakeCompiled(0.4 * hbm_bw), kv_dtype="int8")
    t.observe((1, 0), _FakeCompiled(0.8 * hbm_bw), kv_dtype="bf16")
    assert set(t.per_pass_s) == {(1, 0, "int8"), (1, 0, "bf16")}
    assert t.worst_per_pass_s == pytest.approx(0.4)       # bf16: 0.8s / 2
    assert t.budget() == 2
    assert set(t.report()["per_pass_s"]) == {"1,0,int8", "1,0,bf16"}


def test_int8_roofline_memory_drop_and_budget(small_model):
    """ISSUE-5 acceptance: roofline ``memory_s`` for the pure-COND decode
    signature drops >= 1.4x at int8, and the autotuned budget at equal
    ``target_tick_s`` is >= the bf16 budget."""
    from repro import roofline

    cfg, params = small_model

    def probe(kv_dtype):
        eng = ContinuousEngine(params, cfg, num_slots=4, pass_budget="auto",
                               prompt_len=8, max_new=4, stop_on_eos=False,
                               kv="paged", page_size=4, kv_dtype=kv_dtype,
                               target_tick_s=50e-3)
        eng.autotune_budget()
        fn = eng._paged_step_fn(0, 1)
        i32 = lambda *s: np.zeros(s, np.int32)
        f32 = lambda *s: np.zeros(s, np.float32)
        u32 = lambda *s: np.zeros(s, np.uint32)
        oob = lambda n: np.full((n, eng.nb_max), eng.num_pages, np.int32)
        args = (eng.params, eng._pool_p, oob(0), oob(0), i32(0), i32(0),
                f32(0), f32(0), u32(0, 2), i32(0), oob(1), i32(1), i32(1),
                f32(1), u32(1, 2), i32(1))
        r = roofline.analyze("cond", fn.lower(*args).compile(), 1)
        return eng.pass_budget, r.memory_s

    budget_bf, mem_bf = probe("bf16")
    budget_i8, mem_i8 = probe("int8")
    assert mem_bf / mem_i8 >= 1.4, (mem_bf, mem_i8)
    assert budget_i8 >= budget_bf


# ---------------------------------------------------------------------------
# Simulator: equal pool bytes admits more at int8
# ---------------------------------------------------------------------------


def test_sim_int8_equal_bytes_admits_more():
    """The model-free form of the benchmark assertion: at one HBM budget,
    the int8 pool holds more pages, so the lazy burst sustains strictly
    more concurrent requests (and fewer preemptions), with bytes pinned
    per tick."""
    n_req, ps, plen, steps = 8, 4, 8, 8
    plan = GuidancePlan.suffix(steps, 1.0, 4.0)
    trace = [SimRequest(f"b{i}", 0, plan, prompt_len=plen, priority=i % 2)
             for i in range(n_req)]
    pb = {dt: page_nbytes(ps, 2, 16, 2, dt) for dt in ("bf16", "int8")}
    pages_bf = n_req * pages_for(plen, ps) + 2
    pool_bytes = pages_bf * pb["bf16"]
    peak = {}
    for dt in ("bf16", "int8"):
        rep = simulate(trace, num_slots=n_req, pass_budget=n_req, kv="paged",
                       page_size=ps, num_pages=pool_bytes // pb[dt],
                       reservation="lazy", kv_dtype=dt, page_bytes=pb[dt],
                       prefills_per_tick=n_req)
        m = rep.metrics
        assert m.completed == n_req
        peak[dt] = max(r.active for r in m.records)
        assert m.peak_bytes_in_use <= pool_bytes
        assert m.records[-1].bytes_in_use == 0
    assert peak["int8"] > peak["bf16"], peak
