"""Paged KV arena tests: allocator invariants, block-table kernel vs the
contiguous oracle, model-level paged decode, sim page accounting, and the
ISSUE acceptance criteria against a real (smoke) model — greedy decode
through the paged engine is token-identical to the slot engine, and a
mixed-``prompt_len`` trace shares one pool with unconditional pages
reclaimed at FULL->COND transitions.
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
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.ref import ref_decode_attention, ref_paged_decode_attention
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import (ContinuousEngine, PageAllocator, ServeRequest,
                         SimRequest, paged_partition_specs, pages_for,
                         simulate)


# ---------------------------------------------------------------------------
# PageAllocator invariants (hypothesis)
# ---------------------------------------------------------------------------


def _check_invariants(alloc: PageAllocator):
    owned = [p for pages in alloc._owned.values() for p in pages]
    refs = alloc._ref
    # refcount balance: every grant is accounted by exactly its owners
    assert sum(len(v) for v in alloc._owned.values()) == int(refs.sum())
    # free list and refcounts partition the pool
    assert sorted(alloc._free) == sorted(
        p for p in range(alloc.num_pages) if refs[p] == 0)
    assert alloc.n_free + len(set(owned)) == alloc.num_pages
    # no double-grant: a page appears at most once per owner; cross-owner
    # duplicates exist only via share (counted by the refcount above)
    for key, pages in alloc._owned.items():
        assert len(pages) == len(set(pages)), key


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=24),
       st.lists(st.tuples(st.sampled_from(["alloc", "free", "share"]),
                          st.integers(min_value=0, max_value=9),
                          st.integers(min_value=0, max_value=6)),
                min_size=1, max_size=40))
def test_page_allocator_invariants(num_pages, ops):
    alloc = PageAllocator(num_pages, page_size=4)
    live: list[tuple[str, str]] = []
    for i, (op, owner, n) in enumerate(ops):
        uid, stream = f"r{owner}", ("c", "u")[n % 2]
        if op == "alloc" and (uid, stream) not in alloc._owned:
            free_before = alloc.n_free
            got = alloc.alloc(uid, stream, n)
            if got is None:
                assert n > free_before           # all-or-nothing grants
                assert alloc.n_free == free_before
            else:
                assert len(got) == n
                live.append((uid, stream))
        elif op == "free" and live:
            uid, stream = live.pop(n % len(live))
            alloc.free(uid, stream)
        elif op == "share" and live:
            src_uid, src_stream = live[n % len(live)]
            key = (f"s{i}", "c")
            if key not in alloc._owned:
                alloc.share(key[0], key[1],
                            alloc.owned(src_uid, src_stream))
                live.append(key)
        _check_invariants(alloc)
    for uid, stream in list(live):
        alloc.free(uid, stream)
        _check_invariants(alloc)
    assert alloc.n_free == num_pages        # everything returned


def test_page_allocator_no_partial_grant_and_no_double_own():
    alloc = PageAllocator(4, page_size=2)
    assert alloc.alloc("a", "c", 3) == [0, 1, 2]
    assert alloc.alloc("b", "c", 2) is None          # only 1 free: no partial
    assert alloc.n_free == 1
    with pytest.raises(ValueError):
        alloc.alloc("a", "c", 1)                     # already owns
    shared = alloc.share("b", "c", alloc.owned("a", "c"))
    assert shared == [0, 1, 2]
    assert alloc.free("a", "c") == 0                 # still referenced by b
    assert alloc.free("b", "c") == 3                 # last owner returns them
    assert alloc.n_free == 4


def test_pages_for():
    assert pages_for(0, 4) == 0
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


def test_page_allocator_double_free_is_refused():
    """Freeing an owner twice must be a deterministic no-op (0 pages,
    refcounts/free-list untouched) — never a second decrement that would
    corrupt a surviving sharer's pages."""
    alloc = PageAllocator(4, page_size=2)
    alloc.alloc("a", "c", 2)
    shared = alloc.share("b", "c", alloc.owned("a", "c"))
    assert alloc.free("a", "c") == 0                 # b still references
    assert alloc.free("a", "c") == 0                 # double free: no-op
    assert [alloc.refcount(p) for p in shared] == [1, 1]
    alloc.check()
    assert alloc.free("b", "c") == 2
    assert alloc.free("b", "c") == 0                 # double free after zero
    alloc.check()
    assert alloc.n_free == 4


def test_page_allocator_share_after_free_raises():
    """Sharing pages whose refcount already hit zero must raise: the
    pages may have been re-granted with different content."""
    alloc = PageAllocator(4, page_size=2)
    pages = alloc.alloc("a", "c", 2)
    alloc.free("a", "c")
    with pytest.raises(ValueError):
        alloc.share("b", "c", pages)
    with pytest.raises(ValueError):
        alloc.share("b", "c", [alloc.num_pages])     # out of range
    alloc.check()
    assert alloc.n_free == 4


def test_page_allocator_cow_refuses_unshare_to_zero():
    """cow() on an exclusively-owned page would drop its refcount to zero
    while the owner still points at it — must raise, not orphan."""
    alloc = PageAllocator(6, page_size=2)
    alloc.alloc("a", "u", 2)
    with pytest.raises(ValueError):
        alloc.cow("a", "u", 0)                       # refcount 1: refused
    with pytest.raises(ValueError):
        alloc.cow("a", "u", 5)                       # index out of table
    with pytest.raises(ValueError):
        alloc.cow("ghost", "u", 0)                   # unknown owner
    alloc.check()


def test_page_allocator_cow_detaches_shared_page():
    alloc = PageAllocator(4, page_size=2)
    pages = alloc.alloc("a", "u", 2)
    alloc.share("b", "u", pages)
    src, dst = alloc.cow("b", "u", 1)
    assert src == pages[1] and dst not in pages
    assert alloc.owned("b", "u") == [pages[0], dst]
    assert alloc.owned("a", "u") == pages            # founder untouched
    assert alloc.refcount(src) == 1 and alloc.refcount(dst) == 1
    alloc.check()
    # pool dry -> None, state unchanged
    alloc.alloc("c", "c", alloc.n_free)
    alloc.share("d", "u", alloc.owned("a", "u"))
    assert alloc.cow("d", "u", 0) is None
    alloc.check()


def test_page_allocator_grow_appends_and_refuses_unknown():
    alloc = PageAllocator(4, page_size=2)
    with pytest.raises(ValueError):
        alloc.grow("a", "c", 1)                      # no pages yet: alloc
    alloc.alloc("a", "c", 1)
    first = alloc.owned("a", "c")
    grown = alloc.grow("a", "c", 2)
    assert alloc.owned("a", "c") == first + grown
    assert alloc.grow("a", "c", 2) is None           # only 1 free: no partial
    assert alloc.n_free == 1
    alloc.check()


# ---------------------------------------------------------------------------
# Kernel: paged vs contiguous decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,K,hd,ps,nbr", [(4, 4, 64, 16, 4), (8, 2, 64, 32, 2),
                                           (8, 1, 128, 16, 3)])
@pytest.mark.parametrize("window", [None, 24])
def test_paged_kernel_matches_contiguous_reference(H, K, hd, ps, nbr, window):
    """The block-table kernel on a permuted page pool equals the dense
    decode oracle on the gathered contiguous cache, per row, across
    valid-length and sliding-window masks."""
    B = 3
    rng = np.random.default_rng(H * K + ps)
    P_ = B * nbr + 3
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P_, ps, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P_, ps, K, hd)), jnp.float32)
    perm = rng.permutation(P_)[: B * nbr].reshape(B, nbr)
    bt = np.full((B, nbr + 1), P_, np.int32)         # one padding column
    bt[:, :nbr] = perm
    pos = np.asarray([0, (nbr * ps) // 2, nbr * ps - 1], np.int32)

    out = paged_decode_attention_pallas(q, kp, vp, jnp.asarray(bt),
                                        jnp.asarray(pos), window=window)
    ref = ref_paged_decode_attention(q, kp, vp, jnp.asarray(bt),
                                     jnp.asarray(pos), window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    for b in range(B):                               # vs the dense oracle
        kc = jnp.asarray(np.asarray(kp)[perm[b]].reshape(1, nbr * ps, K, hd))
        vc = jnp.asarray(np.asarray(vp)[perm[b]].reshape(1, nbr * ps, K, hd))
        dense = ref_decode_attention(q[b:b + 1], kc, vc, int(pos[b]),
                                     window=window)
        np.testing.assert_allclose(np.asarray(out)[b], np.asarray(dense)[0],
                                   rtol=3e-5, atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=63),
       st.sampled_from([None, 8, 24, 64]),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_paged_kernel_property_random_tables(pos, window, seed):
    """Random pool layouts: any permutation of physical pages behind the
    block table leaves the attention output invariant."""
    B, H, K, hd, ps, nbr = 2, 4, 2, 32, 16, 4
    rng = np.random.default_rng(seed)
    P_ = B * nbr + 2
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P_, ps, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P_, ps, K, hd)), jnp.float32)
    perm = rng.permutation(P_)[: B * nbr].reshape(B, nbr)
    pos_v = np.asarray([pos, nbr * ps - 1 - pos], np.int32)
    out = paged_decode_attention_pallas(q, kp, vp, jnp.asarray(perm),
                                        jnp.asarray(pos_v), window=window)
    ref = ref_paged_decode_attention(q, kp, vp, jnp.asarray(perm),
                                     jnp.asarray(pos_v), window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Model layer: paged decode path + pool sharding
# ---------------------------------------------------------------------------


def test_attn_decode_paged_matches_linear_cache():
    """One decode step through the paged path equals ``attn_decode`` on the
    equivalent linear cache (write + masked attention semantics)."""
    cfg = get_smoke_config("llama3.2-1b")
    mk = L.ArrayMaker(jax.random.PRNGKey(0))
    p = A.init_attention(cfg, mk)
    B, ps, nbr = 2, 4, 4
    cap = ps * nbr
    pos = 9
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    lin = jnp.asarray(rng.normal(
        size=(B, cap, cfg.num_kv_heads, cfg.resolved_head_dim)), jnp.float32)
    lin_v = jnp.asarray(rng.normal(size=lin.shape), jnp.float32)

    out_lin, cache_lin = A.attn_decode(p, cfg, x, {"k": lin, "v": lin_v}, pos)

    P_ = B * nbr + 1
    perm = rng.permutation(P_)[: B * nbr].reshape(B, nbr)
    kp = np.zeros((P_, ps) + lin.shape[2:], np.float32)
    vp = np.zeros_like(kp)
    for b in range(B):
        kp[perm[b]] = np.asarray(lin)[b].reshape(nbr, ps, *lin.shape[2:])
        vp[perm[b]] = np.asarray(lin_v)[b].reshape(nbr, ps, *lin.shape[2:])
    pool = {"k": jnp.asarray(kp), "v": jnp.asarray(vp)}
    out_pg, pool2 = A.attn_decode_paged(
        p, cfg, x, pool, jnp.asarray(perm),
        jnp.full((B,), pos, jnp.int32))
    np.testing.assert_allclose(np.asarray(out_pg), np.asarray(out_lin),
                               rtol=2e-5, atol=2e-5)
    # the write landed where the linear cache wrote it
    for b in range(B):
        page, off = perm[b][pos // ps], pos % ps
        np.testing.assert_allclose(np.asarray(pool2["k"])[page, off],
                                   np.asarray(cache_lin["k"])[b, pos],
                                   rtol=1e-6, atol=1e-6)


def test_attn_decode_paged_pallas_route_matches_jnp(monkeypatch):
    """The kernel path (the TPU's choice; interpreted here) has the
    gather form's semantics, writes included."""
    cfg = get_smoke_config("llama3.2-1b")
    p = A.init_attention(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    B, ps, nbr = 2, 4, 4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    P_ = B * nbr + 1
    shape = (P_, ps, cfg.num_kv_heads, cfg.resolved_head_dim)
    pool = {"k": jnp.asarray(rng.normal(size=shape), jnp.float32),
            "v": jnp.asarray(rng.normal(size=shape), jnp.float32)}
    bt = jnp.asarray(rng.permutation(P_)[: B * nbr]
                     .reshape(B, nbr).astype(np.int32))
    pos = jnp.asarray([6, 11], jnp.int32)
    monkeypatch.setattr(A, "_paged_kernel", lambda: False)
    out_jnp, pool_jnp = A.attn_decode_paged(p, cfg, x, pool, bt, pos)
    monkeypatch.setattr(A, "_paged_kernel", lambda: True)
    out_pl, pool_pl = A.attn_decode_paged(p, cfg, x, pool, bt, pos)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_jnp),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(pool_pl["k"]),
                               np.asarray(pool_jnp["k"]))


def test_paged_partition_specs_follow_rule_tables():
    """The page-pool axis shards under the §3 allocator invariants (each
    mesh axis at most once per tensor, divisibility respected) with the
    ``pages`` logical name taking the data axis at serve time."""
    from jax.sharding import AbstractMesh, AxisType
    from repro.dist.sharding import RULES_SERVE

    cfg = get_smoke_config("llama3.2-1b")
    mesh = AbstractMesh((4, 2), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
    specs = paged_partition_specs(cfg, 16, 8, rules=RULES_SERVE, mesh=mesh)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert leaves
    for spec in leaves:
        flat = [a for e in spec for a in ((e,) if isinstance(e, str) else e or ())]
        assert len(flat) == len(set(flat))
    # the pages dim (after the stacked layers axis) takes the data axis
    assert any(len(s) > 1 and s[1] == "data" for s in leaves)


def test_paged_cache_specs_rejects_unpageable_stacks():
    cfg = get_smoke_config("recurrentgemma-9b")    # rglru blocks
    with pytest.raises(ValueError):
        T.paged_cache_specs(cfg, L.AxesMaker(), 8, 4)


# ---------------------------------------------------------------------------
# Simulator: page accounting
# ---------------------------------------------------------------------------


def test_sim_paged_reclaims_and_balances():
    trace = [SimRequest(f"r{i}", i // 2,
                        GuidancePlan.suffix(8, 0.5, 4.0),
                        prompt_len=3 + 2 * (i % 3))
             for i in range(9)]
    rep = simulate(trace, num_slots=4, pass_budget=6, kv="paged", page_size=4)
    m = rep.metrics
    assert m.completed == len(trace)
    assert m.denoiser_passes == sum(r.plan.denoiser_passes() for r in trace)
    assert m.pages_reclaimed > 0                    # COND transitions fired
    assert m.peak_pages_in_use > 0
    assert m.records[-1].pages_in_use == 0          # all pages returned


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=10),
                          st.integers(min_value=2, max_value=8),
                          st.floats(min_value=0.0, max_value=1.0),
                          st.integers(min_value=1, max_value=9)),
                min_size=1, max_size=15))
def test_sim_paged_page_conservation(items):
    trace = [SimRequest(f"r{i:03d}", arrival,
                        GuidancePlan.suffix(total, frac, 4.0),
                        prompt_len=plen)
             for i, (arrival, total, frac, plen) in enumerate(items)]
    rep = simulate(trace, num_slots=4, pass_budget=5, kv="paged", page_size=4)
    m = rep.metrics
    assert m.completed == len(trace)
    assert m.records[-1].pages_in_use == 0
    # uncond reclaim only exists for plans with a FULL prefix AND a COND
    # suffix; all-FULL and all-COND plans never return pages early
    mixed = [r for r in trace
             if 0 < r.full_steps < r.plan.total_steps]
    if not mixed:
        assert m.pages_reclaimed == 0


# ---------------------------------------------------------------------------
# Engine acceptance (real smoke model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    return cfg, params


def test_paged_engine_token_identical_to_slot(small_model):
    """ISSUE acceptance: greedy decode through the paged engine is
    token-identical to the slot engine on the same trace (mid-flight
    arrivals, batched k>1 prefill admissions included)."""
    cfg, params = small_model

    def mk(kv):
        return ContinuousEngine(params, cfg, num_slots=4, pass_budget=4,
                                prompt_len=8, max_new=6,
                                selective_fraction=0.5, stop_on_eos=False,
                                kv=kv, page_size=4, prefills_per_tick=2)

    reqs = lambda: [ServeRequest(uid=f"r{i}", prompt=f"trace request {i}",
                                 max_new_tokens=6) for i in range(4)]
    arrivals = [0, 0, 1, 3]
    out_slot = mk("slot").serve_trace(reqs(), arrivals)
    paged = mk("paged")
    out_paged = paged.serve_trace(reqs(), arrivals)
    assert out_slot == out_paged
    # the paged run actually reclaimed uncond pages mid-flight
    assert paged.metrics.pages_reclaimed > 0
    assert paged.pages.n_free == paged.pages.num_pages


def test_paged_engine_mixed_lengths_one_pool(small_model):
    """ISSUE acceptance: a mixed-``prompt_len`` trace (>=3 distinct
    lengths) runs in one pool; every request matches a solo slot engine
    at its own prompt length; unconditional pages are measurably
    reclaimed at the FULL->COND transition; and pow2 length buckets keep
    the prefill compile cache from recompiling per distinct length."""
    cfg, params = small_model
    lens = [3, 5, 8, 6]
    eng = ContinuousEngine(params, cfg, num_slots=4, pass_budget=6,
                           prompt_len=8, max_new=5, selective_fraction=0.4,
                           stop_on_eos=False, kv="paged", page_size=4,
                           prefills_per_tick=4)
    reqs = [ServeRequest(uid=f"m{i}", prompt=f"mixed len request {i}",
                         max_new_tokens=5, prompt_len=Lp)
            for i, Lp in enumerate(lens)]
    out = eng.serve_trace(reqs, [0, 0, 1, 2])

    in_use = [r.pages_in_use for r in eng.metrics.records]
    assert eng.metrics.pages_reclaimed > 0
    # peak is sampled post-admission too (pre same-tick frees), so it may
    # exceed any end-of-tick record
    assert eng.metrics.peak_pages_in_use >= max(in_use) > 0
    assert eng.pages.n_free == eng.pages.num_pages    # balanced at drain

    # prefill compiles per pow2 bucket, not per length: 5, 6, 8 share one
    prefill_keys = sorted(k for k in eng._jit if k[0] == "prefill")
    assert {k[1] for k in prefill_keys} == {4, 8}

    for i, Lp in enumerate(lens):
        solo = ContinuousEngine(params, cfg, num_slots=2, pass_budget=4,
                                prompt_len=Lp, max_new=5,
                                selective_fraction=0.4, stop_on_eos=False)
        ref = solo.serve([ServeRequest(uid="x",
                                       prompt=f"mixed len request {i}",
                                       max_new_tokens=5)])
        assert out[f"m{i}"] == ref["x"], f"m{i} (prompt_len={Lp})"


def test_paged_engine_all_cond_plan_never_allocates_uncond(small_model):
    """fraction=1.0: the uncond stream dies at prefill — no uncond pages
    are ever granted, so selective guidance halves HBM from tick 0."""
    cfg, params = small_model
    eng = ContinuousEngine(params, cfg, num_slots=2, pass_budget=2,
                           prompt_len=8, max_new=4, selective_fraction=1.0,
                           stop_on_eos=False, kv="paged", page_size=4)
    eng.submit(ServeRequest(uid="a", prompt="cond only", max_new_tokens=4))
    eng.tick()
    assert eng.pages.owned("a", "u") == []
    assert len(eng.pages.owned("a", "c")) == pages_for(8 + 4, 4)
    eng.drain()
    assert len(eng.results["a"]) == 4
    assert eng.metrics.pages_reclaimed == 0           # nothing granted early


def test_pass_budget_autotune_from_roofline(small_model):
    """pass_budget="auto" derives an integer budget from the roofline
    step-latency model, installs it in the scheduler, and the engine
    serves correctly under it; a larger target never shrinks the budget."""
    cfg, params = small_model
    eng = ContinuousEngine(params, cfg, num_slots=4, pass_budget="auto",
                           prompt_len=8, max_new=4, stop_on_eos=False,
                           kv="paged", page_size=4, target_tick_s=50e-3)
    out = eng.serve([ServeRequest(uid="a", prompt="tune me",
                                  max_new_tokens=4)])
    assert len(out["a"]) == 4
    report = eng._autotuner.report(eng.kv_dtype)
    assert eng.pass_budget == eng.scheduler.pass_budget == report["budget"]
    assert 2 <= eng.pass_budget <= 2 * eng.num_slots
    # the paged default is the ragged step: the only executable the
    # engine ever runs is the one observation the budget is priced off
    assert set(report["per_pass_s"]) == {"ragged,8,bf16"}
    sig = ContinuousEngine(params, cfg, num_slots=4, pass_budget="auto",
                           prompt_len=8, max_new=4, stop_on_eos=False,
                           kv="paged", page_size=4, target_tick_s=50e-3,
                           step_mode="signature")
    sig.autotune_budget()
    assert set(sig._autotuner.report()["per_pass_s"]) == \
        {"0,1,bf16", "1,0,bf16"}
    # monotonicity of the hook itself (no second engine compile needed)
    tuner = eng._autotuner
    small = type(tuner)(target_tick_s=1e-9, min_budget=2,
                        max_budget=8, per_pass_s=dict(tuner.per_pass_s))
    big = type(tuner)(target_tick_s=10.0, min_budget=2,
                      max_budget=8, per_pass_s=dict(tuner.per_pass_s))
    assert small.budget() == 2
    assert big.budget() == 8


def test_paged_engine_rejects_oversize_and_slot_rejects_mixed(small_model):
    cfg, params = small_model
    paged = ContinuousEngine(params, cfg, num_slots=2, pass_budget=2,
                             prompt_len=8, max_new=4, kv="paged",
                             page_size=4)
    assert not paged.submit(ServeRequest(uid="big", prompt="x",
                                         max_new_tokens=4, prompt_len=9))
    slot = ContinuousEngine(params, cfg, num_slots=2, pass_budget=2,
                            prompt_len=8, max_new=4)
    assert not slot.submit(ServeRequest(uid="mix", prompt="x",
                                        max_new_tokens=4, prompt_len=5))
    assert slot.metrics.rejected == 1
