"""Serve-stack observability suite (DESIGN.md §13), marker ``obs``.

Four layers:

* **event-trace contracts** — the kind set is closed, ``Event.key()``
  excludes the nondeterministic fields (seq, wall time), the ring buffer
  accounts every drop, and every ``ServeMetrics`` running counter equals
  the fold of its own event stream (``fold_counters``) on random
  simulator traces, slot and paged/lazy alike.
* **engine == sim, event for event** — the real engine and the offline
  simulator emit *identical* event-key streams on the same trace (the
  PR-4 counter-parity discipline extended to the full stream), including
  a contended mixed-priority trace that preempts.
* **histogram properties** — any reported percentile ``P`` brackets the
  exact sample quantile ``q`` as ``q <= P <= max(base, 2q)``; merge is
  exactly record-everything-into-one; SLO attainment is conservative.
* **Chrome-trace export** — valid Trace Event JSON, request spans nest
  inside the tick horizon, engine tick spans sum to ``wall_s``, and
  preemption gaps appear as ``preempted`` spans.
"""

import json
import math

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.core.selective import GuidancePlan
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import (BudgetAutotuner, ContinuousEngine, Log2Histogram,
                         ServeMetrics, ServeRequest, SimRequest, TickTiming,
                         fold_counters, simulate, to_chrome_trace,
                         write_chrome_trace)
from repro.serve.obs import EVENT_KINDS, FOLDED_COUNTERS, EventTrace
from repro.serve.obs.timing import TickTimer

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# Event-trace contracts (no model)
# ---------------------------------------------------------------------------


def test_emit_rejects_unknown_kind():
    tr = EventTrace()
    with pytest.raises(ValueError):
        tr.emit("not_a_kind", 0)
    tr.emit("tick", 0, n_full=1, n_cond=0)
    assert all(ev.kind in EVENT_KINDS for ev in tr)


def test_event_key_excludes_seq_and_wall_time():
    """Stream identity must survive re-execution: two emissions of the
    same logical event (different seq, different wall clock) compare
    equal by ``key()`` — that is what engine==sim asserts on."""
    tr = EventTrace()
    a = tr.emit("token", 3, uid="r0", cond=1)
    b = tr.emit("token", 3, uid="r0", cond=1)
    assert a.seq != b.seq and a.t_wall <= b.t_wall
    assert a.key() == b.key()
    assert a.key() != tr.emit("token", 3, uid="r0", cond=0).key()


def test_trace_seq_monotone_wall_nondecreasing():
    tr = EventTrace()
    for i in range(50):
        tr.emit("tick", i)
    evs = tr.events()
    assert [ev.seq for ev in evs] == list(range(50))
    assert all(evs[i].t_wall <= evs[i + 1].t_wall for i in range(49))


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=2, max_value=64),
       st.integers(min_value=0, max_value=200))
def test_ring_buffer_drop_accounting(capacity, n):
    """``emitted == len(buffer) + dropped`` always; the buffer keeps the
    *newest* events when it wraps."""
    tr = EventTrace(capacity=capacity)
    for i in range(n):
        tr.emit("tick", i)
    assert tr.emitted == n
    assert len(tr) == min(n, capacity)
    assert tr.dropped == n - len(tr)
    assert [ev.tick for ev in tr] == list(range(max(0, n - capacity), n))


def _sim_trace(items):
    return [SimRequest(f"r{i:03d}", arrival,
                       GuidancePlan.suffix(total, frac, 4.0),
                       prompt_len=plen, priority=prio)
            for i, (arrival, total, frac, plen, prio) in enumerate(items)]


_TRACE_ITEMS = st.lists(
    st.tuples(st.integers(min_value=0, max_value=10),
              st.integers(min_value=1, max_value=10),
              st.floats(min_value=0.0, max_value=1.0),
              st.integers(min_value=1, max_value=9),
              st.integers(min_value=0, max_value=3)),
    min_size=1, max_size=12)


@settings(max_examples=20, deadline=None)
@given(_TRACE_ITEMS, st.integers(min_value=2, max_value=6))
def test_counters_fold_from_events_slot(items, slots):
    """Tentpole invariant: every running counter is the fold of the
    event stream — counters cannot drift from events (slot arena)."""
    m = simulate(_sim_trace(items), num_slots=slots,
                 pass_budget=2 * slots).metrics
    assert m.trace.dropped == 0
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key


@settings(max_examples=15, deadline=None)
@given(_TRACE_ITEMS, st.integers(min_value=12, max_value=40))
def test_counters_fold_from_events_paged_lazy(items, num_pages):
    """Same fold invariant through the paged/lazy path, where growth,
    sharing, CoW, preemption and reclaim events all fire."""
    m = simulate(_sim_trace(items), num_slots=4, pass_budget=6, kv="paged",
                 page_size=4, num_pages=num_pages,
                 reservation="lazy").metrics
    assert m.trace.dropped == 0
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key


def test_tick_event_closes_its_tick():
    """Per-tick event order contract: among the events stamped with a
    given tick, the ``tick`` record is the last one and appears exactly
    once — consumers can treat it as the tick's commit marker."""
    m = simulate(_sim_trace([(0, 6, 0.5, 5, 0), (0, 4, 0.5, 8, 1),
                             (2, 8, 0.25, 6, 0)]),
                 num_slots=2, pass_budget=4, kv="paged", page_size=4,
                 reservation="lazy").metrics
    by_tick = {}
    for ev in m.trace:
        by_tick.setdefault(ev.tick, []).append(ev.kind)
    for tick, kinds in by_tick.items():
        assert kinds.count("tick") == 1, tick
        assert kinds[-1] == "tick", (tick, kinds)


def test_expired_requests_close_their_timelines():
    """Satellite (b): expiry is terminal. A queue that can never drain
    (ttl=0 with a saturated arena) must still leave every timeline in a
    terminal state with the counters folding."""
    trace = [SimRequest(f"e{i}", 0, GuidancePlan.suffix(12, 0.0, 4.0),
                        ttl=(None if i < 2 else 0), prompt_len=4)
             for i in range(6)]
    m = simulate(trace, num_slots=2, pass_budget=4,
                 prefills_per_tick=2).metrics
    assert m.expired > 0
    fold = fold_counters(m.trace)
    assert fold["expired"] == m.expired
    for uid, t in m.timelines.items():
        assert t.terminal, uid
        if t.completed is None:
            assert t.expired_at is not None, uid


# ---------------------------------------------------------------------------
# Histogram properties
# ---------------------------------------------------------------------------


_SAMPLES = st.lists(st.floats(min_value=0.0, max_value=1000.0),
                    min_size=1, max_size=60)


@settings(max_examples=40, deadline=None)
@given(_SAMPLES, st.sampled_from([50.0, 90.0, 95.0, 99.0]))
def test_percentile_brackets_exact_quantile(samples, p):
    """Any reported percentile P satisfies ``q <= P <= max(base, 2q)``
    where q is the exact rank-based sample quantile — one log2 bucket of
    relative error, never an underestimate."""
    h = Log2Histogram(base=1.0)
    for v in samples:
        h.record(v)
    rank = max(1, math.ceil(p / 100.0 * len(samples)))
    q = sorted(samples)[rank - 1]
    got = h.percentile(p)
    assert got >= q
    assert got <= max(h.base, 2.0 * q)


@settings(max_examples=25, deadline=None)
@given(_SAMPLES, _SAMPLES)
def test_merge_equals_recording_into_one(a, b):
    """Mergeability (the fleet-aggregation path): merge(h_a, h_b) is
    bucket-for-bucket what recording both sample sets into one histogram
    yields — no information beyond the buckets is needed."""
    ha, hb, hall = Log2Histogram(), Log2Histogram(), Log2Histogram()
    for v in a:
        ha.record(v)
        hall.record(v)
    for v in b:
        hb.record(v)
        hall.record(v)
    ha.merge(hb)
    assert ha.counts == hall.counts and ha.total == hall.total
    assert ha.summary() == hall.summary()


def test_merge_layout_mismatch_raises():
    with pytest.raises(ValueError):
        Log2Histogram(base=1.0).merge(Log2Histogram(base=1e-4))
    with pytest.raises(ValueError):
        Log2Histogram(n_buckets=32).merge(Log2Histogram(n_buckets=16))


def test_histogram_guards():
    h = Log2Histogram()
    with pytest.raises(ValueError):
        h.record(-1.0)
    assert h.percentile(50) is None           # empty
    assert h.slo_attainment(10.0) == 1.0      # vacuous SLO
    with pytest.raises(ValueError):
        h.percentile(0)
    with pytest.raises(ValueError):
        Log2Histogram(base=0.0)


@settings(max_examples=30, deadline=None)
@given(_SAMPLES, st.floats(min_value=0.5, max_value=2000.0))
def test_slo_attainment_is_conservative(samples, threshold):
    """Reported attainment never exceeds the true fraction of samples
    within the threshold (buckets straddling it don't count)."""
    h = Log2Histogram(base=1.0)
    for v in samples:
        h.record(v)
    true_frac = sum(1 for v in samples if v <= threshold) / len(samples)
    assert h.slo_attainment(threshold) <= true_frac + 1e-12
    assert h.slo_attainment(2.0 * max(max(samples), h.base) + 1) == 1.0


# ---------------------------------------------------------------------------
# Tick timing
# ---------------------------------------------------------------------------


def test_tick_timer_segments_bracketed():
    timer = TickTimer(7)
    with timer.phase("admit"):
        pass
    with timer.phase("step"):
        sum(range(1000))
    timing = timer.finish()
    assert timing.tick == 7
    assert timing.duration_s >= 0
    seg = timing.segment_s()
    assert set(seg) == {"admit", "step"}
    assert all(s >= 0 for s in seg.values())
    assert timing.overhead_s >= 0
    for _, start, end in timing.segments:
        assert timing.t0 <= start <= end <= timing.t1


def _one_tick(tick):
    timer = TickTimer(tick)
    for name in ("admit", "step"):
        with timer.phase(name):
            sum(range(1000))
    return timer.finish()


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    [path] = list(trace_dir.rglob("*.xplane.pb"))
    return {ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_tick_spans_in_profiler_trace(tmp_path):
    """While a profiler runs, a tick is a step span ``serve_tick`` in its
    trace and each segment a span ``serve.<segment>``."""
    with jax.profiler.trace(str(tmp_path)):
        _one_tick(3)
    names = _host_event_names(tmp_path)
    assert {"serve_tick", "serve.admit", "serve.step"} <= names


def test_tick_segments_same_without_profiler(tmp_path):
    """The profiler is the only switch: a tick records the same segments
    with one running and without."""
    with jax.profiler.trace(str(tmp_path)):
        traced = _one_tick(4)
    plain = _one_tick(5)
    assert [n for n, _, _ in traced.segments] == [n for n, _, _ in plain.segments] \
        == ["admit", "step"]
    assert set(plain.segment_s()) == set(traced.segment_s())


# ---------------------------------------------------------------------------
# Timeline satellites: preemption gaps, expiry, occupancy, savings
# ---------------------------------------------------------------------------


def test_tpot_excludes_preemption_gap():
    """Satellite (a): TPOT is decode cadence, not victimhood — the
    preempt->resume gap is subtracted from the token interval."""
    m = ServeMetrics()
    m.on_arrival("u", 0)
    m.on_admit("u", 2, total_steps=8, full_steps=4)
    m.on_token("u", 2)
    m.on_token("u", 3)
    m.on_token("u", 4)
    m.on_preempt("u", 5)
    m.on_resume("u", 9)                  # 4 dead ticks
    m.on_token("u", 9)
    m.on_token("u", 10)
    m.on_complete("u", 11, passes=12)
    t = m.timelines["u"]
    assert t.n_preempts == 1 and t.gap_ticks == 4
    assert t.queue_wait == 2
    assert t.tpot == pytest.approx((11 - 2 - 4) / 4)   # not (11-2)/4
    assert m.resumes == m.preemptions == 1


def test_expire_is_terminal_on_timeline():
    m = ServeMetrics()
    m.on_arrival("u", 0)
    m.on_admit("u", 1, total_steps=4, full_steps=2)
    m.on_expire("u", 6)
    t = m.timelines["u"]
    assert t.terminal and t.expired_at == 6 and t.completed is None
    assert m.expired == 1
    assert t.passes_saved == t.full_cfg_passes - t.passes
    assert m.passes_saved() == 0          # only completed requests count


def test_occupancy_peaks_deduped():
    """Satellite (c): one high-water path — occupancy events fire only
    on strict new page peaks, not on every sample."""
    m = ServeMetrics()
    m.page_bytes = 100
    for pages, tick in [(4, 0), (3, 1), (4, 2), (7, 3), (7, 4), (2, 5)]:
        m.note_pages(pages, tick)
    occ = [ev for ev in m.trace if ev.kind == "occupancy"]
    assert [(ev.tick, ev.get("pages")) for ev in occ] == [(0, 4), (3, 7)]
    assert m.peak_pages_in_use == 7
    assert m.peak_bytes_in_use == 700


def test_passes_saved_accounting_matches_plan():
    """Tentpole accounting: per-request passes_saved is exactly the COND
    steps of the plan (full CFG would run 2 passes for them too), and
    uncond_ticks_elided counts the COND-mode tokens."""
    total, frac = 10, 0.4
    plan = GuidancePlan.suffix(total, frac, 4.0)
    cond = 2 * total - plan.denoiser_passes()
    n = 5
    m = simulate([SimRequest(f"r{i}", i, plan) for i in range(n)],
                 num_slots=3, pass_budget=6).metrics
    assert m.completed == n
    assert m.passes_saved() == n * cond
    assert m.full_cfg_passes() == n * 2 * total
    assert m.savings_fraction() == pytest.approx(cond / (2 * total))
    # the counter samples COND-mode *token commits*; the completing step
    # emits `complete` instead of `token`, and a suffix plan always ends
    # COND, so each request shows cond-1 elided ticks while in flight —
    # the full cond-step saving is what passes_saved reports.
    assert m.uncond_ticks_elided == n * (cond - 1)
    assert m.uncond_ticks_elided == m.passes_saved() - n
    for row in m.request_rows():
        assert row["state"] == "done"
        assert row["passes_saved"] == cond
        assert row["full_cfg_passes"] == 2 * total
    s = m.summary()
    assert s["passes_saved"] == n * cond
    assert s["events"]["dropped"] == 0
    assert set(s["ttft"]) == {"count", "p50", "p95", "p99"}


def test_autotuner_headroom_signs():
    """Satellite: headroom_s is the envelope slack; negative exactly
    when the min-budget clamp knowingly violates the target."""
    tuner = BudgetAutotuner(target_tick_s=1.0)
    assert tuner.headroom_s() is None
    tuner.per_pass_s[(1, 0)] = 0.1        # budget 10, predicted 1.0
    assert tuner.headroom_s() == pytest.approx(0.0)
    assert not tuner.envelope_violated()
    tuner.per_pass_s[(1, 0)] = 0.9        # clamp to min_budget=2 -> 1.8s
    assert tuner.headroom_s() == pytest.approx(1.0 - 1.8)
    assert tuner.envelope_violated()
    assert "headroom_s" in tuner.report()


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------


def _contended_sim():
    plan = GuidancePlan.suffix(6, 0.5, 4.0)
    lens = [5, 6, 8, 5, 6, 8]
    prios = [0, 1, 0, 2, 1, 0]
    arrivals = [0, 0, 1, 2, 2, 3]
    trace = [SimRequest(f"r{i}", arrivals[i], plan, prompt_len=lens[i],
                        priority=prios[i]) for i in range(6)]
    return simulate(trace, num_slots=6, pass_budget=6, kv="paged",
                    page_size=4, num_pages=10, reservation="lazy",
                    prefills_per_tick=2).metrics


def test_chrome_trace_schema_valid(tmp_path):
    m = _contended_sim()
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(m, path)
    loaded = json.loads(path.read_text())
    assert loaded == doc
    assert doc["otherData"]["request_spans"] > 0
    assert doc["otherData"]["ticks"] == m.ticks
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert ev["pid"] in (1, 2)
            assert isinstance(ev["name"], str) and ev["cat"]


def test_chrome_request_spans_inside_tick_horizon():
    m = _contended_sim()
    doc = to_chrome_trace(m, synthetic_tick_s=1e-3)
    ticks = [ev for ev in doc["traceEvents"]
             if ev["ph"] == "X" and ev["cat"] == "tick"]
    horizon = max(ev["ts"] + ev["dur"] for ev in ticks)
    reqs = [ev for ev in doc["traceEvents"]
            if ev["ph"] == "X" and ev["cat"] == "request"]
    assert len(reqs) == doc["otherData"]["request_spans"]
    for ev in reqs:
        assert 0 <= ev["ts"] and ev["ts"] + ev["dur"] <= horizon + 1e-6
    # every admitted request decodes: it has a FULL or COND span
    decoded = {ev["tid"] for ev in reqs if ev["name"] in ("FULL", "COND")}
    assert len(decoded) == 6


def test_chrome_preemption_gap_becomes_span():
    m = _contended_sim()
    assert m.preemptions > 0               # the trace is contended
    doc = to_chrome_trace(m)
    names = [ev["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "X" and ev["cat"] == "request"]
    assert names.count("preempted") == m.preemptions


def test_chrome_tick_spans_sum_to_wall_s():
    """Acceptance: with real TickTimings the engine tick spans sum to
    ``wall_s`` exactly (same intervals, same clock)."""
    m = ServeMetrics()
    t = 100.0
    for i in range(5):
        dur = 0.008 + 0.001 * i
        seg = (("admit", t, t + 0.001), ("step", t + 0.001, t + dur))
        m.record_tick(i, n_full=1, n_cond=1, budget=4, active=2,
                      queue_depth=0)
        m.on_tick_timing(TickTiming(i, t, t + dur, seg))
        t += dur + 0.002                   # inter-tick gap: not wall time
    doc = to_chrome_trace(m)
    ticks = [ev for ev in doc["traceEvents"]
             if ev["ph"] == "X" and ev["cat"] == "tick"]
    assert len(ticks) == 5
    total_us = sum(ev["dur"] for ev in ticks)
    assert total_us == pytest.approx(m.wall_s * 1e6, rel=1e-6)
    assert doc["otherData"]["wall_s"] == pytest.approx(m.wall_s, abs=1e-4)
    phases = [ev for ev in doc["traceEvents"]
              if ev["ph"] == "X" and ev["cat"] == "tick_phase"]
    assert len(phases) == 10               # 2 segments x 5 ticks
    # segments nest inside their tick span
    for ph, tk in zip(phases, [t for t in ticks for _ in range(2)]):
        assert tk["ts"] - 1e-6 <= ph["ts"]
        assert ph["ts"] + ph["dur"] <= tk["ts"] + tk["dur"] + 1e-6


# ---------------------------------------------------------------------------
# Engine == sim, event for event (real smoke model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    return cfg, params


def test_engine_slot_events_fold_and_match_sim(small_model):
    """Slot arena: the engine's own counters fold from its events, and
    the offline simulator reproduces the event stream key-for-key."""
    cfg, params = small_model
    plan = GuidancePlan.suffix(6, 0.5, 4.0)
    arrivals = [0, 0, 1, 2]
    eng = ContinuousEngine(params, cfg, num_slots=3, pass_budget=6,
                           prompt_len=8, max_new=6, stop_on_eos=False)
    eng.serve_trace([ServeRequest(uid=f"s{i}", prompt=f"slot req {i}",
                                  max_new_tokens=6, plan=plan)
                     for i in range(4)], arrivals)
    m = eng.metrics
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key
    assert m.passes_saved() > 0
    sim_m = simulate([SimRequest(f"s{i}", arrivals[i], plan)
                      for i in range(4)],
                     num_slots=3, pass_budget=6).metrics
    assert m.trace.keys() == sim_m.trace.keys()
    assert m.summary()["ttft"] == sim_m.summary()["ttft"]


def test_engine_paged_lazy_event_parity_contended(small_model):
    """Tentpole acceptance: on a contended mixed-priority paged/lazy
    trace (growth, sharing, CoW, preemption, reclaim all firing) the
    engine and the simulator emit *identical* event streams."""
    cfg, params = small_model
    plan = GuidancePlan.suffix(6, 0.5, 4.0)
    lens = [5, 6, 8, 5, 6, 8]
    prios = [0, 1, 0, 2, 1, 0]
    arrivals = [0, 0, 1, 2, 2, 3]
    eng = ContinuousEngine(params, cfg, num_slots=6, pass_budget=6,
                           prompt_len=8, max_new=6, stop_on_eos=False,
                           kv="paged", page_size=4, prefills_per_tick=2,
                           num_pages=10, reservation="lazy")
    eng.serve_trace([ServeRequest(uid=f"r{i}", prompt=f"req {i}",
                                  max_new_tokens=6, plan=plan,
                                  prompt_len=lens[i], priority=prios[i])
                     for i in range(6)], arrivals)
    sim_m = simulate([SimRequest(f"r{i}", arrivals[i], plan,
                                 prompt_len=lens[i], priority=prios[i])
                      for i in range(6)],
                     num_slots=6, pass_budget=6, kv="paged", page_size=4,
                     num_pages=10, reservation="lazy",
                     prefills_per_tick=2).metrics
    m = eng.metrics
    assert m.preemptions > 0               # the trace really contends
    assert m.trace.keys() == sim_m.trace.keys()
    fold = fold_counters(m.trace)
    for key in FOLDED_COUNTERS:
        assert fold[key] == getattr(m, key), key
    # the export works end-to-end on a real engine run too
    doc = to_chrome_trace(m)
    assert doc["otherData"]["request_spans"] > 0
    assert doc["otherData"]["passes_saved"] == m.passes_saved() > 0
