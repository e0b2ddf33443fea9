"""Beyond-paper benchmark: the technique as a serving feature.

Part 1 (the seed benchmark): guided AR decoding throughput (tokens/s) vs
selective fraction on a reduced llama3-family model — the serving-side
analogue of Table 1.

Part 2 (continuous vs static): the same requests under a Poisson-ish
arrival trace, served by the phase-aware continuous engine and by the
static facade at **equal pass budget**. The phase-aware packer converts
the paper's FULL/COND cost asymmetry into requests-in-flight: COND-phase
requests cost 1 pass slot instead of 2, so the engine co-schedules up to
2x as many late-phase requests per tick.

Part 3 (``--kv paged``): the same comparison through the paged KV arena
(block tables over a shared page pool) plus a mixed-``prompt_len`` trace —
reporting reserved vs peak-in-use HBM and the unconditional pages
reclaimed at FULL->COND transitions, at the same pass budget.

Part 4 (``--reservation lazy``, implies ``--kv paged``): worst-case page
reservation vs on-demand growth at **equal pool size** on a COND-heavy
burst — lazy admission sustains strictly more concurrent requests than
eager reservation (the ISSUE-4 acceptance number: admitted requests per
GB), and the offline simulator reproduces the engine's ``pages_grown`` /
``preemptions`` counts exactly.

Part 5 (``--kv-dtype int8``, implies ``--kv paged``): int8 KV pages vs
bf16 at **equal pool bytes** on the lazy burst trace (DESIGN.md §11).
Int8 pages pin ~half the HBM per page, so the same byte budget holds
~2x the pages and the engine admits strictly more concurrent requests;
reported as reserved-vs-peak HBM in *bytes* (page counts are not
comparable across dtypes) plus peak concurrent admits.

Part 6 (``--kv paged``, any dtype): the ragged flat-pass-list step vs
the per-signature compile cache on the same trace — token-identical
outputs, exactly one warm-up compile for the ragged step with **zero**
recompiles after warm-up (the per-signature cache pays one compile per
phase-mix bucket traffic discovers), and per-tick wall time reported
side by side. ``--step`` picks the mode the other parts run under.

Part 7 (always on): the observability report (DESIGN.md §13) — TTFT/TPOT
p50/p95/p99 from the engine's log2 histograms, per-request
``passes_saved`` vs classic CFG (the paper's Table 1 reduction measured
per request in a serving context), and ``--trace-out PATH`` to export the
continuous run's event trace as Chrome-trace JSON.

Part 8 (``--host-pool-bytes N``, implies ``--reservation lazy``): the
two-tier KV hierarchy (DESIGN.md §14) vs plain lazy at **equal device
pool bytes** on a contended staggered-priority trace. ``--trace
popular`` draws prompts Zipf-style from a small head set, so the
content-addressed prefix cache turns repeat prefills into
copy-on-write shares; preemption victims swap to the pinned-host tier
and resume by DMA restore. Tiered must finish the same tokens with
strictly fewer total denoiser passes, and the offline simulator must
reproduce the engine's swap/hit/evict counters exactly. ``--only-tier``
runs just this part (the CI kv-tier smoke).

Part 9 (``--policy divergence|interval``): dynamic guidance policies
(DESIGN.md §15) vs the all-FULL baseline on the same trace. The
``divergence`` policy drops the uncond stream mid-flight when the EMA'd
cond/uncond divergence falls below ``--divergence-threshold``, emitting
``policy_switch`` events and eliding uncond passes beyond the bound
plan; ``--combine`` picks the FULL-step combine stage (Eq. 1 or APG),
at the scale the plan gives each step. The recorded switch steps
replayed through the offline simulator must reproduce the engine's
event stream and the new ``policy_switches`` /
``uncond_passes_elided_dynamic`` counters exactly.

Part 10 (``--replicas N``, N > 1): the fleet tier (DESIGN.md §16) —
N engine replicas behind the prefix-affinity router vs the seeded
random-routing baseline at **equal total device pool bytes** on the
Zipf ``popular`` trace. Affinity routing sends repeat prompts to the
replica whose content cache holds them, so it must produce strictly
more prefix hits and strictly fewer total forward passes (random
routing re-prefills the head prompt once per replica it lands on);
token outputs are identical either way, and ``simulate_fleet`` must
reproduce every replica's counters and event stream exactly. With
``--trace-out`` the whole fleet renders as one Chrome-trace timeline
(per-replica pids); single-replica trace files are unchanged.

    PYTHONPATH=src python -m benchmarks.serve_throughput [--tiny] \
        [--kv paged] [--reservation lazy] [--kv-dtype int8] \
        [--step auto|ragged|signature] [--trace-out trace.json] \
        [--policy static|divergence|interval] [--combine cfg|apg] \
        [--replicas N]
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_smoke_config
from repro.core.guidance import COMBINE_MODES
from repro.core.policy import GUIDANCE_POLICIES
from repro.core.selective import GuidancePlan
from repro.data.prompts import PAPER_PROMPTS
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import (ContinuousEngine, ServeFleet, ServeMetrics,
                         ServeRequest, SimRequest, fleet_chrome_trace,
                         host_pages_for_bytes, kv_page_bytes, pages_for,
                         pages_for_pool_bytes, poisson_arrivals, simulate,
                         simulate_fleet, write_chrome_trace)
from repro.serving import Request, ServingEngine

FRACTIONS = [0.0, 0.2, 0.5]


def _static_sweep(params, cfg, *, n_req: int, prompt_len: int, max_new: int,
                  fractions) -> list[dict]:
    reqs = [Request(uid=f"r{i}", prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                    max_new_tokens=max_new) for i in range(n_req)]
    rows = []
    base_tps = None
    for f in fractions:
        eng = ServingEngine(params, cfg, max_batch=8, prompt_len=prompt_len,
                            max_new=max_new, selective_fraction=f)
        eng.generate(reqs)                       # compile
        eng.stats = type(eng.stats)()
        eng.generate(reqs)
        s = eng.stats
        if f == fractions[0]:
            base_tps = s.tokens_per_s
        speedup = s.tokens_per_s / base_tps if base_tps else 1.0
        rows.append(dict(fraction=f, tokens_per_s=s.tokens_per_s,
                         passes=s.denoiser_passes, speedup=speedup))
        emit(f"serve/frac{int(f*100):02d}",
             1e6 / max(s.tokens_per_s, 1e-9),
             f"tok_s={s.tokens_per_s:.1f};speedup={speedup:.3f};"
             f"passes={s.denoiser_passes}")
    return rows


def _continuous_vs_static(params, cfg, *, n_req: int, prompt_len: int,
                          max_new: int, fraction: float, batch: int,
                          rate: float, seed: int = 0,
                          kv: str = "slot", page_size: int = 4,
                          reservation: str = "eager",
                          kv_dtype: str = "bf16",
                          step: str = "auto",
                          trace_out: str | None = None,
                          combine: str = "cfg") -> dict:
    arrivals = poisson_arrivals(seed, n=n_req, rate=rate)
    budget = 2 * batch

    def make_reqs(tag):
        return [ServeRequest(uid=f"{tag}{i}",
                             prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                             max_new_tokens=max_new)
                for i in range(n_req)]

    eng = ContinuousEngine(params, cfg, num_slots=2 * batch, pass_budget=budget,
                           prompt_len=prompt_len, max_new=max_new,
                           selective_fraction=fraction, stop_on_eos=False,
                           kv=kv, page_size=page_size,
                           reservation=reservation, kv_dtype=kv_dtype,
                           step_mode=None if step == "auto" else step,
                           combine=combine)
    # arrivals are relative to the current tick, so the measured run
    # replays the same trace shape the warmup compiled for
    eng.serve_trace(make_reqs("w"), arrivals)     # warmup/compile
    eng.metrics = ServeMetrics()
    eng.serve_trace(make_reqs("c"), arrivals)
    cont = eng.metrics
    hbm = eng.kv_hbm_bytes()
    if trace_out:
        doc = write_chrome_trace(cont, trace_out)
        emit("serve/trace", len(doc["traceEvents"]),
             f"out={trace_out};spans={doc['otherData']['request_spans']};"
             f"ticks={doc['otherData']['ticks']}")

    static = ServingEngine(params, cfg, max_batch=batch, prompt_len=prompt_len,
                           max_new=max_new, selective_fraction=fraction)
    sreqs = [Request(uid=f"s{i}", prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                     max_new_tokens=max_new) for i in range(n_req)]
    static.generate(sreqs)                        # warmup/compile
    static._engine.metrics = ServeMetrics()
    static.stats = type(static.stats)()
    static.generate(sreqs)
    stat = static._engine.metrics

    for tag, m in [("continuous", cont), ("static", stat)]:
        emit(f"serve/{tag}",
             1e6 * m.wall_s / max(m.tokens_emitted, 1),
             f"in_flight={m.mean_in_flight():.2f};util={m.utilization():.3f};"
             f"ticks={m.ticks};passes={m.denoiser_passes};"
             f"budget={budget}")
    emit(f"serve/kv_{kv}_{kv_dtype}" if kv == "paged" else f"serve/kv_{kv}",
         hbm["peak_in_use_bytes"],
         f"reserved={hbm['reserved_bytes']};"
         f"reclaimed={cont.pages_reclaimed};"
         f"peak_pages={cont.peak_pages_in_use}")
    emit("serve/savings", cont.passes_saved(),
         f"full_cfg={cont.full_cfg_passes()};"
         f"fraction={cont.savings_fraction():.3f};"
         f"uncond_elided={cont.uncond_ticks_elided}")
    return {"continuous": cont.summary(), "static": stat.summary(),
            "pass_budget": budget, "kv": kv, "hbm": hbm,
            "requests": cont.request_rows(),
            "in_flight_gain": cont.mean_in_flight() / max(stat.mean_in_flight(), 1e-9)}


def _paged_mixed_lengths(params, cfg, *, prompt_len: int, max_new: int,
                         fraction: float, batch: int,
                         page_size: int = 4) -> dict:
    """Paged-arena headline: a mixed-``prompt_len`` trace (impossible under
    the slot arena) shares one pool, and the COND suffix reclaims every
    request's unconditional pages mid-flight."""
    lens = [max(1, prompt_len // 4), max(1, prompt_len // 2), prompt_len]
    eng = ContinuousEngine(params, cfg, num_slots=2 * batch,
                           pass_budget=2 * batch, prompt_len=prompt_len,
                           max_new=max_new, selective_fraction=fraction,
                           stop_on_eos=False, kv="paged", page_size=page_size)
    reqs = [ServeRequest(uid=f"m{i}",
                         prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                         max_new_tokens=max_new,
                         prompt_len=lens[i % len(lens)])
            for i in range(2 * batch)]
    out = eng.serve(reqs)
    m = eng.metrics
    hbm = eng.kv_hbm_bytes()
    emit("serve/paged_mixed", hbm["peak_in_use_bytes"],
         f"lens={'/'.join(map(str, lens))};completed={m.completed};"
         f"reclaimed={m.pages_reclaimed};peak_pages={m.peak_pages_in_use};"
         f"reserved={hbm['reserved_bytes']}")
    assert len(out) == len(reqs)
    return {"lens": lens, "summary": m.summary(), "hbm": hbm}


def _lazy_vs_eager(params, cfg, *, prompt_len: int, max_new: int,
                   batch: int, page_size: int = 4) -> dict:
    """ISSUE-4 acceptance: a COND-heavy burst at equal pool size. Eager
    admission reserves each request's worst-case span up front, so the
    pool caps concurrency; lazy admission grants prompt pages only and
    grows at tick boundaries (preempting by priority when it runs dry),
    sustaining strictly more concurrent requests — more admitted requests
    per GB of KV pool. The offline simulator must reproduce the lazy
    engine's growth/preemption counters exactly."""
    n_req = 2 * batch
    plan = GuidancePlan.suffix(max_new, 1.0, 4.0)   # COND-heavy: late phase
    num_pages = n_req * pages_for(prompt_len, page_size) + 2
    arrivals = [0] * n_req                          # burst: pool contended

    def engine(reservation):
        eng = ContinuousEngine(params, cfg, num_slots=n_req,
                               pass_budget=n_req, prompt_len=prompt_len,
                               max_new=max_new, stop_on_eos=False,
                               kv="paged", page_size=page_size,
                               num_pages=num_pages, reservation=reservation,
                               prefills_per_tick=n_req)
        reqs = [ServeRequest(uid=f"z{i}",
                             prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                             max_new_tokens=max_new, plan=plan,
                             priority=i % 2)
                for i in range(n_req)]
        out = eng.serve_trace(reqs, arrivals)
        assert len(out) == n_req
        return eng.metrics

    peak = {}
    for res in ("eager", "lazy"):
        m = engine(res)
        peak[res] = max(r.active for r in m.records)
        emit(f"serve/reservation_{res}", peak[res],
             f"pool={num_pages}pages;grown={m.pages_grown};"
             f"preempt={m.preemptions};ticks={m.ticks}")
        if res == "lazy":
            lazy_m = m
    assert peak["lazy"] > peak["eager"], \
        f"lazy must admit more concurrent requests: {peak}"

    trace = [SimRequest(f"z{i}", 0, plan, prompt_len=prompt_len,
                        priority=i % 2) for i in range(n_req)]
    rep = simulate(trace, num_slots=n_req, pass_budget=n_req, kv="paged",
                   page_size=page_size, num_pages=num_pages,
                   reservation="lazy", prefills_per_tick=n_req)
    sim_m = rep.metrics
    for key in ("pages_grown", "preemptions", "shared_page_hits",
                "cow_copies"):
        got, want = getattr(sim_m, key), getattr(lazy_m, key)
        assert got == want, f"sim {key}={got} != engine {want}"
    return {"peak_concurrent": peak, "num_pages": num_pages,
            "lazy": lazy_m.summary(), "sim_matches": True}


def _int8_vs_bf16(params, cfg, *, prompt_len: int, max_new: int,
                  batch: int, page_size: int = 4) -> dict:
    """ISSUE-5 acceptance: int8 KV pages vs bf16 at **equal pool bytes**
    on the lazy COND-heavy burst. One HBM budget, two pools: bf16 holds
    ``N`` pages, int8 holds ``~1.9N`` (per-page bytes drop from
    ``2*hd`` to ``hd + 4`` per position-head, k+v), so the int8 engine
    sustains strictly more concurrent requests per byte — the paper's
    guidance-side reduction compounding with quantization."""
    n_req = 2 * batch
    plan = GuidancePlan.suffix(max_new, 1.0, 4.0)   # COND-heavy: late phase
    pages_bf16 = n_req * pages_for(prompt_len, page_size) + 2
    pool_bytes = pages_bf16 * kv_page_bytes(cfg, page_size, "bf16")
    arrivals = [0] * n_req                          # burst: pool contended

    def engine(kv_dtype):
        num_pages = pages_for_pool_bytes(cfg, pool_bytes, page_size, kv_dtype)
        eng = ContinuousEngine(params, cfg, num_slots=n_req,
                               pass_budget=n_req, prompt_len=prompt_len,
                               max_new=max_new, stop_on_eos=False,
                               kv="paged", page_size=page_size,
                               num_pages=num_pages, reservation="lazy",
                               kv_dtype=kv_dtype, prefills_per_tick=n_req)
        reqs = [ServeRequest(uid=f"q{i}",
                             prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                             max_new_tokens=max_new, plan=plan,
                             priority=i % 2)
                for i in range(n_req)]
        out = eng.serve_trace(reqs, arrivals)
        assert len(out) == n_req
        return eng

    stats = {}
    for kv_dtype in ("bf16", "int8"):
        eng = engine(kv_dtype)
        m = eng.metrics
        hbm = eng.kv_hbm_bytes()
        stats[kv_dtype] = {
            "num_pages": eng.num_pages,
            "reserved_bytes": hbm["reserved_bytes"],
            "peak_in_use_bytes": hbm["peak_in_use_bytes"],
            "peak_concurrent": max(r.active for r in m.records),
            "grown": m.pages_grown, "preemptions": m.preemptions,
            "ticks": m.ticks,
        }
        emit(f"serve/kvdtype_{kv_dtype}", stats[kv_dtype]["peak_concurrent"],
             f"pool_bytes={hbm['reserved_bytes']};"
             f"pages={eng.num_pages};"
             f"peak_bytes={hbm['peak_in_use_bytes']};"
             f"preempt={m.preemptions}")
    assert stats["int8"]["reserved_bytes"] <= pool_bytes, stats
    assert stats["int8"]["num_pages"] > stats["bf16"]["num_pages"], stats
    assert stats["int8"]["peak_concurrent"] > stats["bf16"]["peak_concurrent"], \
        f"int8 must admit strictly more at equal pool bytes: {stats}"
    return {"pool_bytes": pool_bytes, **stats}


def _ragged_vs_signature(params, cfg, *, n_req: int, prompt_len: int,
                         max_new: int, fraction: float, batch: int,
                         rate: float, seed: int = 0,
                         page_size: int = 4) -> dict:
    """Tentpole acceptance: the fixed-shape ragged pass-list step vs the
    per-signature compile cache on the same paged trace. Outputs must be
    token-identical; the ragged step must compile exactly once at warm-up
    and never again (``step_compiles == 0`` on the measured run); per-tick
    wall time is reported side by side (the measured signature run replays
    the warm trace, so its cache is as favourable as it can be)."""
    arrivals = poisson_arrivals(seed, n=n_req, rate=rate)

    def make_reqs(tag):
        return [ServeRequest(uid=f"{tag}{i}",
                             prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                             max_new_tokens=max_new)
                for i in range(n_req)]

    tokens, stats = {}, {}
    for mode in ("signature", "ragged"):
        eng = ContinuousEngine(params, cfg, num_slots=2 * batch,
                               pass_budget=2 * batch, prompt_len=prompt_len,
                               max_new=max_new, selective_fraction=fraction,
                               stop_on_eos=False, kv="paged",
                               page_size=page_size, step_mode=mode)
        eng.serve_trace(make_reqs("w"), arrivals)     # warmup/compile
        warm_compiles = eng.metrics.step_compiles
        eng.metrics = ServeMetrics()
        tokens[mode] = eng.serve_trace(make_reqs("c"), arrivals)
        m = eng.metrics
        stats[mode] = {"warm_compiles": warm_compiles,
                       "recompiles": m.step_compiles,
                       "launches": m.step_launches, "ticks": m.ticks,
                       "tick_us": 1e6 * m.wall_s / max(m.ticks, 1)}
        emit(f"serve/step_{mode}", stats[mode]["tick_us"],
             f"warm_compiles={warm_compiles};recompiles={m.step_compiles};"
             f"launches={m.step_launches};ticks={m.ticks}")
    assert {u: t for u, t in tokens["ragged"].items()} == \
        {u: t for u, t in tokens["signature"].items()}, \
        "ragged step must be token-identical to the per-signature path"
    assert stats["ragged"]["warm_compiles"] == 1, stats
    assert stats["ragged"]["recompiles"] == 0, \
        f"ragged step recompiled after warm-up: {stats['ragged']}"
    return stats


def _popular_prompts(seed: int, n: int, n_prompts: int = 3) -> list[int]:
    """Zipf-weighted prompt indices (p proportional to 1/rank^1.5): a
    'popular prompts' trace where the head prompt recurs — the workload
    the content-addressed prefix cache exists for."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_prompts + 1) ** 1.5
    return [int(k) for k in rng.choice(n_prompts, size=n, p=p / p.sum())]


def _tiered_vs_lazy(params, cfg, *, batch: int,
                    host_pool_bytes: int, trace: str = "popular",
                    page_size: int = 4, seed: int = 0) -> dict:
    """§14 acceptance: two-tier KV (host swap + content prefix cache) vs
    plain lazy at **equal device pool bytes**.

    The trace staggers arrivals two ticks apart with strictly rising
    priority, so each newcomer preempts its predecessor when the tight
    pool runs dry — under the tier, victims park their pages on the host
    and resume by DMA restore (zero denoiser passes) instead of the
    batched recompute forward. ``trace="popular"`` draws prompts
    Zipf-style from a 3-prompt head set so repeat prompts hit the
    content cache (2 prefill passes avoided each, CoW on divergence);
    ``"burst"`` uses distinct prompts (misses only — swap savings
    alone). Both engines see identical requests and device pool bytes;
    outputs must be token-identical and the tiered run must do strictly
    fewer total denoiser passes. The offline simulator replays the same
    trace and must reproduce the tier counters exactly."""
    n_req = 2 * batch
    prompt_len, max_new = 8, 6      # fixed micro geometry: the pool below
    plan = GuidancePlan.suffix(max_new, 0.5, 4.0)    # FULL prefix: uncond
    num_pages = n_req + 4           # is tuned to it (~1.5 requests' peak)
    arrivals = [2 * i for i in range(n_req)]
    picks = _popular_prompts(seed, n_req) if trace == "popular" \
        else [i % len(PAPER_PROMPTS) for i in range(n_req)]
    host_pages = host_pages_for_bytes(host_pool_bytes,
                                      kv_page_bytes(cfg, page_size, "bf16"))

    def engine(tiered):
        eng = ContinuousEngine(params, cfg, num_slots=n_req,
                               pass_budget=2 * n_req, prompt_len=prompt_len,
                               max_new=max_new, stop_on_eos=False,
                               kv="paged", page_size=page_size,
                               num_pages=num_pages, reservation="lazy",
                               prefills_per_tick=1,
                               host_pool_bytes=host_pool_bytes if tiered
                               else 0,
                               prefix_cache="content" if tiered else "length")
        reqs = [ServeRequest(uid=f"t{i}", prompt=PAPER_PROMPTS[picks[i]],
                             max_new_tokens=max_new, plan=plan,
                             prompt_len=prompt_len, priority=i)
                for i in range(n_req)]
        out = eng.serve_trace(reqs, arrivals)
        assert len(out) == n_req
        return out, eng.metrics

    tok_lazy, m_lazy = engine(False)
    tok_tier, m_tier = engine(True)
    assert tok_tier == tok_lazy, \
        "host restore / prefix-hit replay must be token-identical"
    total = {}
    for tag, m in [("lazy", m_lazy), ("tiered", m_tier)]:
        s = m.summary()
        total[tag] = s["prefill_passes"] + s["denoiser_passes"]
        emit(f"serve/tier_{tag}", total[tag],
             f"prefill={s['prefill_passes']};decode={s['denoiser_passes']};"
             f"preempt={s['preemptions']};resumes={s['resumes']};"
             f"ticks={s['ticks']};"
             f"tick_us={1e6 * m.wall_s / max(m.ticks, 1):.0f}")
    st = m_tier.summary()
    emit("serve/tier_savings", st["recompute_passes_avoided"],
         f"swap_outs={st['swap_outs']};swap_ins={st['swap_ins']};"
         f"host_evictions={st['host_evictions']};"
         f"prefix_hits={st['prefix_hits']};"
         f"hit_rate={st['prefix_hit_rate']:.2f}")
    assert st["swap_ins"] > 0, st
    assert st["recompute_passes_avoided"] > 0, st
    if trace == "popular":
        assert st["prefix_hits"] > 0 and st["prefix_hit_rate"] > 0, st
    assert total["tiered"] < total["lazy"], \
        f"tier must do strictly less denoiser work: {total}"

    sim_trace = [SimRequest(f"t{i}", 2 * i, plan, prompt_len=prompt_len,
                            priority=i, content=f"p{picks[i]}")
                 for i in range(n_req)]
    rep = simulate(sim_trace, num_slots=n_req, pass_budget=2 * n_req,
                   kv="paged", page_size=page_size, num_pages=num_pages,
                   reservation="lazy", prefills_per_tick=1,
                   host_pages=host_pages, prefix_cache="content")
    ss = rep.metrics.summary()
    for key in ("preemptions", "swap_outs", "swap_ins", "host_evictions",
                "prefix_hits", "prefix_misses", "recompute_passes_avoided"):
        assert ss[key] == st[key], f"sim {key}={ss[key]} != engine {st[key]}"
    return {"total_passes": total, "num_pages": num_pages,
            "host_pages": host_pages, "trace": trace,
            "tiered": st, "lazy": m_lazy.summary(), "sim_matches": True}


def _dynamic_vs_full(params, cfg, *, n_req: int, prompt_len: int,
                     max_new: int, batch: int, policy: str, combine: str,
                     divergence_threshold: float,
                     interval: tuple[float, float] = (0.0, 0.5),
                     page_size: int = 4) -> dict:
    """§15 acceptance: a dynamic guidance policy vs the FULL baseline on
    the same trace.  The baseline runs every request all-FULL (fraction
    0); the dynamic engine runs the same requests under ``--policy`` /
    ``--combine``.  ``divergence`` must fire ``policy_switch`` events and
    elide uncond passes (``uncond_passes_elided_dynamic > 0``, total
    denoiser passes strictly below the baseline by exactly that amount);
    ``interval`` realizes its bound plan structurally (fewer passes, no
    switch events).  The recorded switch steps replayed through the
    offline simulator must reproduce the dynamic engine's event stream —
    ``policy_switch`` and both new counters included."""
    arrivals = [i // 2 for i in range(n_req)]       # staggered, sorted
    num_pages = n_req * pages_for(prompt_len + max_new, page_size) + 2

    def engine(**kw):
        eng = ContinuousEngine(params, cfg, num_slots=n_req,
                               pass_budget=2 * batch, prompt_len=prompt_len,
                               max_new=max_new, stop_on_eos=False,
                               kv="paged", page_size=page_size,
                               num_pages=num_pages, reservation="lazy",
                               **kw)
        reqs = [ServeRequest(uid=f"y{i}",
                             prompt=PAPER_PROMPTS[i % len(PAPER_PROMPTS)],
                             max_new_tokens=max_new, selective_fraction=0.0)
                for i in range(n_req)]
        out = eng.serve_trace(reqs, arrivals)
        assert len(out) == n_req
        return eng.metrics

    m_full = engine()
    m_dyn = engine(guidance_policy=policy, combine=combine,
                   divergence_threshold=divergence_threshold,
                   interval=interval)
    s = m_dyn.summary()
    emit("serve/dyn_policy", s["denoiser_passes"],
         f"policy={policy};combine={combine};"
         f"full_baseline={m_full.denoiser_passes};"
         f"switches={s['policy_switches']};"
         f"elided={s['uncond_passes_elided_dynamic']}")
    assert m_dyn.denoiser_passes < m_full.denoiser_passes, \
        f"dynamic must beat FULL: {m_dyn.denoiser_passes} vs " \
        f"{m_full.denoiser_passes}"
    if policy == "divergence":
        assert s["policy_switches"] > 0, s
        assert s["uncond_passes_elided_dynamic"] > 0, s
        assert m_full.denoiser_passes - m_dyn.denoiser_passes \
            == s["uncond_passes_elided_dynamic"], s

    # replay the recorded switches through the model-free simulator
    switches = {ev.uid: ev.get("step") for ev in m_dyn.trace
                if ev.kind == "policy_switch"}
    if policy == "interval":
        plan = GuidancePlan.interval(max_new, interval[0], interval[1], 4.0)
    else:
        plan = GuidancePlan.suffix(max_new, 0.0, 4.0)
    sim_m = simulate([SimRequest(f"y{i}", arrivals[i], plan,
                                 prompt_len=prompt_len,
                                 switch_step=switches.get(f"y{i}"))
                      for i in range(n_req)],
                     num_slots=n_req, pass_budget=2 * batch, kv="paged",
                     page_size=page_size, num_pages=num_pages,
                     reservation="lazy").metrics
    assert m_dyn.trace.keys() == sim_m.trace.keys(), \
        "sim must reproduce the dynamic engine's event stream"
    for key in ("policy_switches", "uncond_passes_elided_dynamic",
                "denoiser_passes", "pages_reclaimed"):
        got, want = getattr(sim_m, key), getattr(m_dyn, key)
        assert got == want, f"sim {key}={got} != engine {want}"
    return {"policy": policy, "combine": combine,
            "full_passes": m_full.denoiser_passes,
            "dynamic_passes": m_dyn.denoiser_passes,
            "policy_switches": s["policy_switches"],
            "uncond_passes_elided_dynamic":
                s["uncond_passes_elided_dynamic"],
            "sim_matches": True}


def _fleet_routing(params, cfg, *, n_replicas: int, seed: int = 0,
                   page_size: int = 4,
                   trace_out: str | None = None) -> dict:
    """§16 acceptance: prefix-affinity routing vs the seeded random
    baseline across ``n_replicas`` identical engines at **equal total
    device pool bytes** (every replica gets the same pool either way).

    The Zipf ``popular`` trace (arrivals one tick apart, dense enough
    that the per-replica uncond prefix registry entries stay live
    between repeats) is routed through both policies. Token outputs are
    identical — placement changes the work, never the result — but
    affinity keeps every repeat of the head prompt on its founding
    replica's content cache, so it must win on prefix hits and total
    forward passes strictly. ``simulate_fleet`` routes the same trace
    with the same (pure) router and must reproduce each replica's
    counters and event stream exactly."""
    n_req, prompt_len, max_new = 16, 8, 8
    plan = GuidancePlan.suffix(max_new, 0.5, 4.0)
    arrivals = list(range(n_req))
    picks = _popular_prompts(seed, n_req)
    eng_kw = dict(num_slots=6, pass_budget=12, prompt_len=prompt_len,
                  max_new=max_new, stop_on_eos=False, kv="paged",
                  page_size=page_size, num_pages=64, reservation="lazy",
                  prefix_cache="content", prefills_per_tick=2)

    tokens, summ, fleets = {}, {}, {}
    for pol in ("affinity", "random"):
        fleet = ServeFleet([ContinuousEngine(params, cfg, **eng_kw)
                            for _ in range(n_replicas)],
                           policy=pol, seed=7)
        reqs = [ServeRequest(uid=f"f{i:02d}", prompt=PAPER_PROMPTS[picks[i]],
                             max_new_tokens=max_new, plan=plan,
                             prompt_len=prompt_len) for i in range(n_req)]
        tokens[pol] = fleet.serve_trace(reqs, arrivals)
        assert len(tokens[pol]) == n_req
        s = fleet.summary()
        summ[pol], fleets[pol] = s, fleet
        emit(f"serve/fleet_{pol}",
             s["prefill_passes"] + s["denoiser_passes"],
             f"replicas={n_replicas};hits={s['prefix_hits']};"
             f"hit_rate={s['prefix_hit_rate']:.2f};"
             f"prefill={s['prefill_passes']};"
             f"decode={s['denoiser_passes']};"
             f"spread={'/'.join(map(str, fleet.router.assigned_count))}")
    assert tokens["affinity"] == tokens["random"], \
        "routing must change the work, never the tokens"
    total = {p: summ[p]["prefill_passes"] + summ[p]["denoiser_passes"]
             for p in summ}
    assert summ["affinity"]["prefix_hits"] > summ["random"]["prefix_hits"], \
        f"affinity must win prefix hits: {summ}"
    assert total["affinity"] < total["random"], \
        f"affinity must do strictly fewer total passes: {total}"

    # router sim == per-replica engine runs (the §16 parity acceptance)
    sim = simulate_fleet(
        [SimRequest(f"f{i:02d}", arrivals[i], plan, prompt_len=prompt_len,
                    content=f"p{picks[i]}") for i in range(n_req)],
        n_replicas, policy="affinity", seed=7, page_size=page_size,
        **{k: eng_kw[k] for k in ("num_slots", "pass_budget", "kv",
                                  "num_pages", "reservation",
                                  "prefix_cache", "prefills_per_tick")})
    fleet = fleets["affinity"]
    assert sim.assignments == fleet.assignments, "router placement diverged"
    for rid, (em, sm) in enumerate(zip(fleet.metrics, sim.metrics)):
        assert em.trace.keys() == sm.trace.keys(), \
            f"replica {rid}: sim event stream diverged"
        for key in ("completed", "denoiser_passes", "prefill_passes",
                    "prefix_hits", "prefix_misses", "tokens_emitted"):
            got, want = getattr(sm, key), getattr(em, key)
            assert got == want, f"replica {rid} sim {key}={got} != {want}"

    if trace_out:
        doc = fleet_chrome_trace(fleet.metrics)
        with open(trace_out, "w") as f:
            json.dump(doc, f)
        emit("serve/fleet_trace", len(doc["traceEvents"]),
             f"out={trace_out};replicas={doc['otherData']['replicas']};"
             f"spans={doc['otherData']['request_spans']}")
    return {"replicas": n_replicas, "total_passes": total,
            "affinity": summ["affinity"], "random": summ["random"],
            "sim_matches": True}


def run(tiny: bool = False, kv: str = "slot",
        reservation: str = "eager", kv_dtype: str = "bf16",
        step: str = "auto", trace_out: str | None = None,
        host_pool_bytes: int = 0, trace: str = "popular",
        only_tier: bool = False, policy: str = "static",
        combine: str = "cfg", divergence_threshold: float = 1e9,
        replicas: int = 1) -> dict:
    # with a fleet, --trace-out means the merged fleet timeline; the
    # single-replica export path below stays exactly as it was
    fleet_trace_out = None
    if replicas > 1 and trace_out:
        fleet_trace_out, trace_out = trace_out, None
    if host_pool_bytes:
        reservation = "lazy"                        # only lazy preempts
    if step == "ragged":
        kv = "paged"                                # ragged implies paged
    if kv_dtype == "int8":
        kv = "paged"                                # int8 implies paged
        reservation = "lazy"                        # the burst acceptance
    if reservation == "lazy":
        kv = "paged"                                # lazy implies paged
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_model(cfg, L.ArrayMaker(jax.random.PRNGKey(0)))
    if tiny:
        n_req, prompt_len, max_new, batch = 4, 8, 6, 2
        fractions = [0.0, 0.5]
    else:
        n_req, prompt_len, max_new, batch = 8, 24, 24, 4
        fractions = FRACTIONS
    if only_tier:
        if not host_pool_bytes:
            raise SystemExit("--only-tier needs --host-pool-bytes > 0")
        return {"tiered_vs_lazy": _tiered_vs_lazy(
            params, cfg, batch=batch,
            host_pool_bytes=host_pool_bytes, trace=trace)}
    rows = _static_sweep(params, cfg, n_req=n_req, prompt_len=prompt_len,
                         max_new=max_new, fractions=fractions)
    # arrival rate well above the service rate so a queue builds and the
    # packing policy (not arrival sparsity) decides requests-in-flight
    compare = _continuous_vs_static(params, cfg, n_req=n_req,
                                    prompt_len=prompt_len, max_new=max_new,
                                    fraction=fractions[-1], batch=batch,
                                    rate=4.0 if tiny else 1.5, kv=kv,
                                    reservation=reservation,
                                    kv_dtype=kv_dtype, step=step,
                                    trace_out=trace_out, combine=combine)
    out = {"rows": rows, "compare": compare}
    if kv == "paged":
        out["paged_mixed"] = _paged_mixed_lengths(
            params, cfg, prompt_len=prompt_len, max_new=max_new,
            fraction=fractions[-1], batch=batch)
        out["ragged_vs_signature"] = _ragged_vs_signature(
            params, cfg, n_req=n_req, prompt_len=prompt_len,
            max_new=max_new, fraction=fractions[-1], batch=batch,
            rate=4.0 if tiny else 1.5)
    if reservation == "lazy" and kv_dtype == "bf16":
        out["lazy_vs_eager"] = _lazy_vs_eager(
            params, cfg, prompt_len=prompt_len, max_new=max_new,
            batch=batch)
    if kv_dtype == "int8":
        out["int8_vs_bf16"] = _int8_vs_bf16(
            params, cfg, prompt_len=prompt_len, max_new=max_new,
            batch=batch)
    if host_pool_bytes > 0:
        out["tiered_vs_lazy"] = _tiered_vs_lazy(
            params, cfg, batch=batch, host_pool_bytes=host_pool_bytes,
            trace=trace)
    if policy != "static":
        out["dynamic_vs_full"] = _dynamic_vs_full(
            params, cfg, n_req=n_req, prompt_len=prompt_len,
            max_new=max_new, batch=batch, policy=policy, combine=combine,
            divergence_threshold=divergence_threshold)
    if replicas > 1:
        out["fleet_routing"] = _fleet_routing(
            params, cfg, n_replicas=replicas, trace_out=fleet_trace_out)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: tiny shapes, two fractions")
    ap.add_argument("--kv", choices=["slot", "paged"], default="slot",
                    help="KV arena for the continuous engine")
    ap.add_argument("--reservation", choices=["eager", "lazy"],
                    default="eager",
                    help="paged arena page policy (lazy = on-demand growth "
                         "+ uncond prefix sharing + priority preemption; "
                         "implies --kv paged)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8"], default="bf16",
                    help="paged pool dtype (int8 = quantized pages with "
                         "fp32 per-row scales; implies --kv paged "
                         "--reservation lazy and runs the equal-pool-bytes "
                         "admission comparison)")
    ap.add_argument("--step", choices=["auto", "ragged", "signature"],
                    default="auto",
                    help="decode step mode for the continuous engine "
                         "(ragged = one fixed-shape flat-pass-list step, "
                         "one compile per model; implies --kv paged; auto "
                         "= engine default: ragged when paged)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the continuous run's event trace as "
                         "Chrome-trace JSON (chrome://tracing / Perfetto)")
    ap.add_argument("--host-pool-bytes", type=int, default=0,
                    help="pinned-host swap tier byte budget; >0 runs the "
                         "tiered-vs-lazy comparison (implies --reservation "
                         "lazy, DESIGN.md §14)")
    ap.add_argument("--trace", choices=["popular", "burst"],
                    default="popular",
                    help="tiered-part prompt mix: popular = Zipf head-set "
                         "(content-cache hits), burst = distinct prompts "
                         "(swap savings only)")
    ap.add_argument("--only-tier", action="store_true",
                    help="run just the tiered-vs-lazy part (the CI kv-tier "
                         "smoke; needs --host-pool-bytes)")
    ap.add_argument("--policy", choices=GUIDANCE_POLICIES,
                    default="static",
                    help="runtime guidance policy (DESIGN.md §15); non-"
                         "static runs the dynamic-vs-FULL comparison with "
                         "engine==sim replay of the recorded switches")
    ap.add_argument("--combine", choices=COMBINE_MODES,
                    default="cfg",
                    help="FULL-step combine stage: Eq. 1, or APG normalized "
                         "guidance (arxiv 2410.02416)")
    ap.add_argument("--divergence-threshold", type=float, default=1e9,
                    help="EMA cond/uncond divergence level below which the "
                         "divergence policy drops the uncond stream (the "
                         "huge default fires at the first observation — "
                         "the aggressive CI smoke)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas; >1 runs the fleet routing "
                         "comparison (prefix-affinity vs random at equal "
                         "total pool bytes, DESIGN.md §16) and makes "
                         "--trace-out export the merged fleet timeline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = run(tiny=args.tiny, kv=args.kv, reservation=args.reservation,
              kv_dtype=args.kv_dtype, step=args.step,
              trace_out=args.trace_out,
              host_pool_bytes=args.host_pool_bytes, trace=args.trace,
              only_tier=args.only_tier, policy=args.policy,
              combine=args.combine,
              divergence_threshold=args.divergence_threshold,
              replicas=args.replicas)
    if "tiered_vs_lazy" in out:
        tv = out["tiered_vs_lazy"]
        st = tv["tiered"]
        print(f"tiered @ {tv['num_pages']} device pages + "
              f"{tv['host_pages']} host pages ({tv['trace']} trace): "
              f"total passes tiered={tv['total_passes']['tiered']} "
              f"lazy={tv['total_passes']['lazy']}; "
              f"swap_outs={st['swap_outs']} swap_ins={st['swap_ins']} "
              f"prefix_hits={st['prefix_hits']} "
              f"hit_rate={st['prefix_hit_rate']:.2f} "
              f"recompute_passes_avoided={st['recompute_passes_avoided']} "
              f"(sim reproduces: {tv['sim_matches']})")
    if args.only_tier:
        raise SystemExit(0)
    print("continuous-vs-static:", out["compare"]["continuous"])
    print("                     ", out["compare"]["static"])
    cont = out["compare"]["continuous"]
    for name in ("ttft", "tpot"):
        h = cont[name]
        print(f"{name} ticks: p50={h['p50']} p95={h['p95']} p99={h['p99']} "
              f"(n={h['count']})")
    print(f"guidance savings: passes_saved={cont['passes_saved']} "
          f"({cont['savings_fraction']:.1%} of full CFG), "
          f"uncond_ticks_elided={cont['uncond_ticks_elided']}")
    for row in out["compare"]["requests"]:
        print(f"  {row['uid']}: {row['state']} ttft={row['ttft']} "
              f"tpot={row['tpot']} preempts={row['preempts']} "
              f"passes={row['passes']}/{row['full_cfg_passes']} "
              f"saved={row['passes_saved']}")
    if args.trace_out:
        print(f"chrome trace written to {args.trace_out}")
    print(f"in-flight gain at equal pass budget: "
          f"{out['compare']['in_flight_gain']:.2f}x")
    hbm = out["compare"]["hbm"]
    print(f"kv={out['compare']['kv']}: "
          f"reserved={hbm['reserved_bytes']/2**20:.2f}MiB "
          f"peak_in_use={hbm['peak_in_use_bytes']/2**20:.2f}MiB")
    if "paged_mixed" in out:
        pm = out["paged_mixed"]
        print(f"paged mixed lens={pm['lens']}: "
              f"reclaimed={pm['summary']['pages_reclaimed']} pages, "
              f"peak={pm['summary']['peak_pages_in_use']}")
    if "ragged_vs_signature" in out:
        rs = out["ragged_vs_signature"]
        print(f"step modes: ragged {rs['ragged']['tick_us']:.0f}us/tick "
              f"({rs['ragged']['warm_compiles']} compile, "
              f"{rs['ragged']['recompiles']} recompiles) vs signature "
              f"{rs['signature']['tick_us']:.0f}us/tick "
              f"({rs['signature']['warm_compiles']} compiles, "
              f"{rs['signature']['recompiles']} recompiles)")
    if "lazy_vs_eager" in out:
        lv = out["lazy_vs_eager"]
        print(f"reservation @ {lv['num_pages']} pages: "
              f"peak concurrent lazy={lv['peak_concurrent']['lazy']} "
              f"eager={lv['peak_concurrent']['eager']}; "
              f"lazy grown={lv['lazy']['pages_grown']} "
              f"preemptions={lv['lazy']['preemptions']} "
              f"(sim reproduces: {lv['sim_matches']})")
    if "dynamic_vs_full" in out:
        dv = out["dynamic_vs_full"]
        print(f"dynamic policy={dv['policy']} combine={dv['combine']}: "
              f"passes {dv['dynamic_passes']} vs FULL {dv['full_passes']}; "
              f"switches={dv['policy_switches']} "
              f"uncond_passes_elided_dynamic="
              f"{dv['uncond_passes_elided_dynamic']} "
              f"(sim reproduces: {dv['sim_matches']})")
    if "fleet_routing" in out:
        fr = out["fleet_routing"]
        aff, rnd = fr["affinity"], fr["random"]
        print(f"fleet @ {fr['replicas']} replicas (popular trace): "
              f"affinity hits={aff['prefix_hits']} "
              f"total passes={fr['total_passes']['affinity']} vs random "
              f"hits={rnd['prefix_hits']} "
              f"total passes={fr['total_passes']['random']} "
              f"(sim reproduces: {fr['sim_matches']})")
    if "int8_vs_bf16" in out:
        q = out["int8_vs_bf16"]
        print(f"kv-dtype @ {q['pool_bytes']/2**20:.2f}MiB pool: "
              f"int8 {q['int8']['num_pages']} pages / peak concurrent "
              f"{q['int8']['peak_concurrent']} vs bf16 "
              f"{q['bf16']['num_pages']} pages / "
              f"{q['bf16']['peak_concurrent']} "
              f"(peak bytes int8={q['int8']['peak_in_use_bytes']} "
              f"bf16={q['bf16']['peak_in_use_bytes']})")
