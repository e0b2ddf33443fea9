"""Dynamic guidance policy suite (DESIGN.md §15), under the ``policy``
marker (CI runs ``-m policy`` as the ``guidance-dyn`` job).

Four layers:

* **bound-plan/cursor properties** — hypothesis-driven walks through
  :class:`DynamicPlanCursor`: the realized FULL-step count never exceeds
  ``policy.max_full_steps()``, the switch fires exactly once, elided-pass
  accounting balances executed + elided == bound, and the static policy's
  cursor is a plain :class:`PlanCursor` walking the plan bit for bit.
* **interval plans** — :meth:`GuidancePlan.interval` (arxiv 2404.07724)
  gives each step its mode and combine scale; the engine under the
  interval policy decodes the tokens ``guided_decode`` does on its plan.
* **combine kernels** — the APG pallas kernel (arxiv 2410.02416) vs its
  jnp oracle (interpret mode on CPU), including the ragged self-pairing
  edge (u == c rows return c exactly).
* **checkpoint-state reclaim regressions** — the uncond reclaim trigger
  is driven by checkpointed state, not the previous event's mode: a
  request preempted exactly at its FULL→COND boundary reclaims its uncond
  pages exactly once across preempt/resume, nothing double-frees, and the
  allocator is fully free at drain.
* **engine == sim parity** — a real divergence-policy engine run elides
  uncond passes; its ``policy_switch`` steps harvested into
  ``SimRequest.switch_step`` replay through the model-free simulator to
  the identical event stream, key for key.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.core.ar_decode import guided_decode
from repro.core.policy import (DivergenceGuidancePolicy, DynamicPlanCursor,
                               IntervalGuidancePolicy, ReplayGuidancePolicy,
                               StaticGuidancePolicy, make_policy)
from repro.core.selective import GuidancePlan, Mode, PlanCursor, Segment
from repro.data.tokenizer import encode
from repro.kernels.cfg_combine import apg_combine_pallas, apg_combine_ref
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import (ContinuousEngine, ServeRequest, SimRequest,
                         fold_counters, simulate)
from repro.serve.obs.trace import FOLDED_COUNTERS

pytestmark = pytest.mark.policy


# ---------------------------------------------------------------------------
# Bound-plan / cursor properties (no model)
# ---------------------------------------------------------------------------

plans = st.tuples(st.integers(min_value=1, max_value=24),
                  st.floats(min_value=0.0, max_value=1.0)).map(
    lambda tf: GuidancePlan.suffix(tf[0], tf[1], 4.0))


def _walk(cursor, divergences):
    """Run a cursor to completion, feeding one divergence per FULL step
    (the engine's observe-after-advance protocol). Returns
    (full_steps_executed, switch_events_fired)."""
    full, fired = 0, 0
    i = 0
    while not cursor.done:
        mode = cursor.mode
        cursor.advance()
        if mode is Mode.FULL:
            full += 1
            dv = divergences[i % len(divergences)] if divergences else 0.0
            i += 1
            if isinstance(cursor, DynamicPlanCursor) and cursor.observe(dv):
                fired += 1
    return full, fired


@settings(max_examples=60, deadline=None)
@given(plans, st.floats(min_value=1e-3, max_value=1e3),
       st.floats(min_value=0.0, max_value=0.9),
       st.lists(st.floats(min_value=0.0, max_value=1e3), min_size=1,
                max_size=24))
def test_switch_never_exceeds_bound(plan, threshold, momentum, divs):
    """The capacity guarantee admission prices against: no divergence
    sequence makes a cursor execute more FULL steps than
    ``max_full_steps()``, and executed + elided == the bound exactly."""
    policy = DivergenceGuidancePolicy(plan, threshold=threshold,
                                      momentum=momentum)
    cursor = policy.cursor()
    full, fired = _walk(cursor, divs)
    assert full <= policy.max_full_steps()
    assert fired <= 1
    assert full + cursor.elided_uncond_passes() == policy.max_full_steps()
    if fired:
        assert cursor.switch_step is not None
        # the switch can only move the boundary earlier, never later
        assert cursor.elided_uncond_passes() > 0


@settings(max_examples=40, deadline=None)
@given(plans)
def test_static_policy_is_plain_plan_cursor(plan):
    """``static`` must be bit-compatible with the pre-policy serve path:
    its cursor IS a PlanCursor and walks the plan identically."""
    cursor = StaticGuidancePolicy(plan).cursor()
    assert type(cursor) is PlanCursor
    ref = PlanCursor(plan)
    while not ref.done:
        assert cursor.mode is ref.mode
        assert cursor.cost == ref.cost
        cursor.advance()
        ref.advance()
    assert cursor.done
    assert cursor.passes_executed == ref.passes_executed \
        == plan.denoiser_passes()


@settings(max_examples=40, deadline=None)
@given(plans, st.integers(min_value=0, max_value=10),
       st.floats(min_value=1e-2, max_value=10.0),
       st.floats(min_value=0.0, max_value=0.9))
def test_divergence_trigger_deterministic(plan, seed, threshold, momentum):
    """Same divergence sequence -> same switch step, same elided count —
    the property the engine==sim replay contract rests on."""
    rnd = np.random.RandomState(seed)
    divs = list(rnd.uniform(0.0, 5.0, size=plan.total_steps))

    def run():
        c = DivergenceGuidancePolicy(plan, threshold=threshold,
                                     momentum=momentum).cursor()
        _walk(c, divs)
        return c.switch_step, c.elided_uncond_passes(), c.ema

    assert run() == run()


@settings(max_examples=40, deadline=None)
@given(plans, st.lists(st.floats(min_value=0.0, max_value=1e3), min_size=1,
                       max_size=24))
def test_replay_reproduces_recorded_switch(plan, divs):
    """A recorded divergence run replayed through ReplayGuidancePolicy
    (what the sim does, with zero divergences) lands on the identical
    switch step and elision count."""
    rec = DivergenceGuidancePolicy(plan, threshold=1e9).cursor()
    _walk(rec, divs)
    replay = ReplayGuidancePolicy(plan, rec.switch_step).cursor()
    if rec.switch_step is None:
        # no recorded switch -> the replay cursor IS the bound plan
        assert type(replay) is PlanCursor
        return
    _walk(replay, [0.0])
    assert replay.switch_step == rec.switch_step
    assert replay.elided_uncond_passes() == rec.elided_uncond_passes()


def test_observe_fires_exactly_once_and_respects_boundary():
    plan = GuidancePlan.suffix(8, 0.25, 4.0)       # FULL[0,6) COND[6,8)
    c = DivergenceGuidancePolicy(plan, threshold=0.5).cursor()
    c.advance()                                     # step 0 executed (FULL)
    assert c.observe(10.0) is False                 # above threshold
    c.advance()
    assert c.observe(0.1) is True                   # drops below -> switch
    assert c.switch_step == 2
    assert c.mode is Mode.COND                      # override, plan said FULL
    assert c.observe(0.1) is False                  # never fires twice
    assert c.elided_uncond_passes() == 4            # plan-FULL steps 2..5

    # at the plan boundary there is nothing left to elide: no event
    c2 = DivergenceGuidancePolicy(plan, threshold=1e9).cursor()
    for _ in range(6):
        c2.advance()
        c2.observe(0.0)
    c3 = DivergenceGuidancePolicy(plan, threshold=1e9).cursor(step=6,
                                                              passes_executed=12)
    assert c3.observe(0.0) is False
    assert c3.switch_step is None


def test_interval_policy_bound_plan_and_scale():
    """Interval guidance (arxiv 2404.07724): the bound plan is
    ``GuidancePlan.interval`` — FULL until the stop fraction (AR-legal —
    uncond KV must stay fresh), its segments carrying the scales — and a
    static pass schedule (plain PlanCursor)."""
    pol = IntervalGuidancePolicy(10, 0.2, 0.7, guidance_scale=5.0)
    assert pol.plan == GuidancePlan.interval(10, 0.2, 0.7, 5.0)
    assert pol.max_full_steps() == 7
    assert type(pol.cursor()) is PlanCursor

    made = make_policy("interval", GuidancePlan.suffix(10, 0.5, 5.0),
                       interval=(0.2, 0.7))
    assert made.plan == pol.plan                    # plan fraction ignored
    with pytest.raises(ValueError):
        make_policy("nope", GuidancePlan.full(4))
    with pytest.raises(ValueError):
        DivergenceGuidancePolicy(GuidancePlan.full(4), threshold=0.0)


@pytest.mark.parametrize("total,start,stop,segments", [
    (10, 0.2, 0.7, [(0, 2, Mode.FULL, 1.0), (2, 7, Mode.FULL, None),
                    (7, 10, Mode.COND, None)]),
    (10, 0.0, 0.5, [(0, 5, Mode.FULL, None), (5, 10, Mode.COND, None)]),
    (10, 0.3, 1.0, [(0, 3, Mode.FULL, 1.0), (3, 10, Mode.FULL, None)]),
    (8, 0.0, 1.0, [(0, 8, Mode.FULL, None)]),
    (4, 0.2, 0.3, [(0, 1, Mode.FULL, 1.0), (1, 4, Mode.COND, None)]),
])
def test_interval_plan_segments(total, start, stop, segments):
    """The one copy of the interval arithmetic: FULL at scale 1.0 before
    ``round_half_up(T * start)``, FULL at the plan's scale up to
    ``round_half_up(T * stop)``, COND after — a suffix plan, so AR-legal.
    Boundaries that round together leave out the empty guided segment."""
    plan = GuidancePlan.interval(total, start, stop, 5.0)
    assert plan.segments == tuple(Segment(*seg) for seg in segments)
    assert plan.guidance_scale == 5.0
    plan.validate_for_ar()
    for i in range(total):
        assert plan.segment_at(i).start <= i < plan.segment_at(i).stop
    with pytest.raises(IndexError):
        plan.segment_at(total)
    with pytest.raises(ValueError):
        GuidancePlan.interval(total, stop, start)


# ---------------------------------------------------------------------------
# Combine kernels vs oracles (interpret mode on CPU)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=9),
       st.integers(min_value=3, max_value=300),
       st.floats(min_value=-2.0, max_value=9.0),
       st.floats(min_value=0.0, max_value=1.0),
       st.sampled_from([0.0, 0.5, 2.5]),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_apg_kernel_matches_oracle(rows, feat, scale, eta, threshold, seed):
    rng = jax.random.PRNGKey(seed)
    u = jax.random.normal(rng, (rows, feat), jnp.float32)
    c = jax.random.normal(jax.random.fold_in(rng, 1), (rows, feat),
                          jnp.float32)
    out = apg_combine_pallas(u, c, scale, eta=eta, threshold=threshold,
                             interpret=True)
    ref = apg_combine_ref(u, c, scale, eta=eta, threshold=threshold)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_apg_self_paired_rows_return_cond_exactly():
    """Ragged decode self-pairs COND rows (u == c): APG must return c
    bit-exactly at any scale — d == 0 so the projection is a no-op."""
    rng = jax.random.PRNGKey(7)
    c = jax.random.normal(rng, (4, 77), jnp.float32)
    for scale in (0.0, 1.0, 7.5, -3.0):
        out = apg_combine_ref(c, c, scale, eta=0.3, threshold=1.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(c))
        out_k = apg_combine_pallas(c, c, scale, eta=0.3, threshold=1.0,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(c))
    # all-zero rows (padding) are safe via the norm epsilon
    z = jnp.zeros((2, 16), jnp.float32)
    assert np.isfinite(np.asarray(apg_combine_ref(z, z, 7.5,
                                                  threshold=1.0))).all()


@pytest.mark.parametrize("rows,feat", [(2, 2049), (8, 4500), (13, 260),
                                       (20, 4097)])
def test_combine_kernels_tile_rows_and_features(rows, feat):
    """Several row blocks of 8 and several feature blocks: the
    APG reductions accumulate across feature blocks, padded rows and
    lanes are sliced off, and the kernel still matches its oracle."""
    rng = jax.random.PRNGKey(rows * 1000 + feat)
    u = jax.random.normal(rng, (rows, feat), jnp.float32)
    c = jax.random.normal(jax.random.fold_in(rng, 1), (rows, feat),
                          jnp.float32)
    for threshold in (0.0, 2.5):
        out = apg_combine_pallas(u, c, 4.0, eta=0.3, threshold=threshold,
                                 interpret=True)
        ref = apg_combine_ref(u, c, 4.0, eta=0.3, threshold=threshold)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Checkpoint-state reclaim regressions (simulator, no model)
# ---------------------------------------------------------------------------

def _reclaims_per_uid(metrics):
    out = {}
    for ev in metrics.trace:
        if ev.kind == "reclaim":
            out[ev.uid] = out.get(ev.uid, 0) + 1
    return out


def test_boundary_preempt_resume_reclaims_exactly_once():
    """Regression (satellite 3): the reclaim trigger is checkpoint-state
    driven. A victim preempted exactly at its FULL→COND boundary — after
    the transition tick reclaimed its uncond pages — must not reclaim
    again on resume (double-free), and a victim preempted *before* the
    boundary must still reclaim exactly once after resume (stranded
    pages). The allocator ends fully free either way."""
    plan = GuidancePlan.suffix(6, 0.5, 4.0)         # FULL[0,3) COND[3,6)
    seen = {}

    def audit(tick, pages, sched, queue):
        pages.check()
        seen["pages"] = pages

    # strong arrivals staggered so the weak request is preempted at
    # different phases of its plan across the sweep — including exactly
    # the boundary tick
    for strong_arrival in (1, 2, 3, 4, 5):
        trace = [SimRequest("weak", 0, plan, prompt_len=8),
                 SimRequest("strong", strong_arrival, plan, prompt_len=8,
                            priority=5)]
        rep = simulate(trace, num_slots=4, pass_budget=6, kv="paged",
                       page_size=4, num_pages=7, reservation="lazy",
                       prefills_per_tick=2, on_tick=audit)
        m = rep.metrics
        counts = _reclaims_per_uid(m)
        # every request with a FULL prefix reclaims exactly once, ever
        assert counts == {"weak": 1, "strong": 1}, \
            (strong_arrival, counts)
        assert m.completed == 2
        assert seen["pages"].n_free == seen["pages"].num_pages


def test_dynamic_switch_then_preempt_drains_clean():
    """A dynamic (replayed) switch fires, reclaim follows, then the
    request is preempted and resumed: the checkpointed ``uncond_dead``
    travels with it — one reclaim total, allocator fully free at drain."""
    plan = GuidancePlan.suffix(6, 0.0, 4.0)         # all-FULL bound plan
    seen = {}

    def audit(tick, pages, sched, queue):
        pages.check()
        seen["pages"] = pages

    trace = [SimRequest("dyn", 0, plan, prompt_len=8, switch_step=2),
             SimRequest("strong", 4, plan, prompt_len=8, priority=5)]
    rep = simulate(trace, num_slots=4, pass_budget=6, kv="paged",
                   page_size=4, num_pages=8, reservation="lazy",
                   prefills_per_tick=2, on_tick=audit)
    m = rep.metrics
    assert m.preemptions >= 1                       # trace really contends
    assert m.policy_switches == 1
    assert m.uncond_passes_elided_dynamic == 4      # plan-FULL steps 2..5
    assert _reclaims_per_uid(m).get("dyn") == 1
    assert m.completed == 2
    assert seen["pages"].n_free == seen["pages"].num_pages
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=10),
                          st.integers(min_value=2, max_value=8),
                          st.floats(min_value=0.0, max_value=1.0),
                          st.integers(min_value=0, max_value=3),
                          st.integers(min_value=0, max_value=8)),
                min_size=1, max_size=10))
def test_random_dynamic_traces_reclaim_once_and_drain_clean(items):
    """Random traces with random replayed switch steps: per-request
    reclaim count is exactly 1 when the realized schedule has a FULL
    prefix, 0 otherwise; no page leaks at drain."""
    trace = []
    for i, (arrival, total, frac, prio, sw) in enumerate(items):
        plan = GuidancePlan.suffix(total, frac, 4.0)
        switch = sw if sw < total else None
        trace.append(SimRequest(f"r{i:02d}", arrival, plan, prompt_len=5,
                                priority=prio, switch_step=switch))
    seen = {}

    def audit(tick, pages, sched, queue):
        pages.check()
        seen["pages"] = pages

    rep = simulate(trace, num_slots=4, pass_budget=5, kv="paged",
                   page_size=4, num_pages=12, reservation="lazy",
                   on_tick=audit)
    m = rep.metrics
    counts = _reclaims_per_uid(m)
    for req in trace:
        full, total = req.full_steps, req.plan.total_steps
        if full == 0:
            expect = 0           # uncond never allocated
        elif full < total:
            expect = 1           # static COND tail reclaims at the boundary
        elif req.switch_step is not None and total >= 2:
            expect = 1           # all-FULL plan cut short by the switch
        else:
            expect = 0           # all-FULL to the end: freed at complete
        assert counts.get(req.uid, 0) == expect, (req.uid, full, total)
    assert seen["pages"].n_free == seen["pages"].num_pages


# ---------------------------------------------------------------------------
# Engine: static token-identity + divergence smoke + engine == sim parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    return cfg, params


def _reqs(n, total=6):
    return [ServeRequest(uid=f"p{i}", prompt=f"policy req {i}",
                         max_new_tokens=total, selective_fraction=0.5)
            for i in range(n)]


def test_engine_static_policy_token_identical(small_model):
    """Acceptance: ``guidance_policy="static"`` is the suffix-plan path —
    token-identical output and identical pass accounting to an engine
    that never heard of policies (the default)."""
    cfg, params = small_model
    base = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                            prompt_len=8, max_new=6, stop_on_eos=False)
    out_base = base.serve(_reqs(3))
    static = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                              prompt_len=8, max_new=6, stop_on_eos=False,
                              guidance_policy="static")
    out_static = static.serve(_reqs(3))
    assert out_static == out_base
    assert static.metrics.denoiser_passes == base.metrics.denoiser_passes
    assert static.metrics.policy_switches == 0
    assert static.metrics.uncond_passes_elided_dynamic == 0
    assert static.metrics.trace.keys() == base.metrics.trace.keys()


def test_engine_divergence_elides_and_matches_sim(small_model):
    """Tentpole acceptance: a divergence-policy run switches FULL→COND
    mid-flight (threshold set high: first observation triggers), executes
    strictly fewer denoiser passes than the FULL baseline, and the
    harvested switch steps replayed through the simulator reproduce the
    engine's event stream key for key — ``policy_switch`` and reclaim
    included."""
    cfg, params = small_model
    total = 6

    def reqs():
        return [ServeRequest(uid=f"d{i}", prompt=f"divergent req {i}",
                             max_new_tokens=total, selective_fraction=0.0)
                for i in range(3)]

    arrivals = [0, 0, 1]
    eng = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                           prompt_len=8, max_new=total, stop_on_eos=False,
                           kv="paged", page_size=4, num_pages=24,
                           reservation="lazy",
                           guidance_policy="divergence",
                           divergence_threshold=1e9)
    eng.serve_trace(reqs(), arrivals)
    m = eng.metrics
    assert m.policy_switches == 3
    assert m.uncond_passes_elided_dynamic > 0
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key

    base = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                            prompt_len=8, max_new=total, stop_on_eos=False,
                            kv="paged", page_size=4, num_pages=24,
                            reservation="lazy")
    base.serve_trace(reqs(), arrivals)
    assert m.denoiser_passes < base.metrics.denoiser_passes
    assert base.metrics.denoiser_passes - m.denoiser_passes \
        == m.uncond_passes_elided_dynamic

    # harvest the recorded switches -> model-free replay
    switches = {ev.uid: ev.get("step") for ev in m.trace
                if ev.kind == "policy_switch"}
    plan = GuidancePlan.suffix(total, 0.0, 4.0)
    sim_m = simulate([SimRequest(f"d{i}", arrivals[i], plan, prompt_len=8,
                                 switch_step=switches.get(f"d{i}"))
                      for i in range(3)],
                     num_slots=3, pass_budget=6, kv="paged", page_size=4,
                     num_pages=24, reservation="lazy").metrics
    assert m.trace.keys() == sim_m.trace.keys()
    assert sim_m.policy_switches == m.policy_switches
    assert sim_m.uncond_passes_elided_dynamic == m.uncond_passes_elided_dynamic


def test_engine_interval_policy_matches_guided_decode(small_model):
    """The paged, ragged engine under the interval policy asks each row's
    bound-plan segment for its scale: its greedy tokens are the ones
    ``guided_decode`` produces on ``policy.bound_plan()`` — scale 1.0
    before the interval, the request's scale inside it, COND after."""
    cfg, params = small_model
    total, scale = 10, 4.0
    prompts = [f"interval req {i}" for i in range(3)]
    eng = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                           prompt_len=8, max_new=total, stop_on_eos=False,
                           kv="paged", page_size=4, num_pages=24,
                           guidance_policy="interval", interval=(0.2, 0.7))
    assert eng.step_mode == "ragged"
    out = eng.serve([ServeRequest(uid=f"i{i}", prompt=p, max_new_tokens=total,
                                  guidance_scale=scale)
                     for i, p in enumerate(prompts)])
    plan = IntervalGuidancePolicy(total, 0.2, 0.7, scale).bound_plan()
    toks = np.asarray([encode(p, cfg.vocab_size, 8) for p in prompts],
                      np.int32)
    gen, _ = guided_decode(params, cfg, toks, plan, temperature=0.0)
    assert [out[f"i{i}"] for i in range(3)] == np.asarray(gen).tolist()
    assert eng.metrics.denoiser_passes == 3 * plan.denoiser_passes()
