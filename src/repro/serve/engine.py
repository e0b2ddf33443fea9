"""Continuous-batching engine: the tick loop over mixed-phase jitted steps.

Requests join and leave mid-flight. Each engine tick:

1. expires queued requests past their deadline,
2. admits new requests (prefill) into the KV arena,
3. compacts the arena when needed (slot arena only; page frees are O(1)),
4. asks the :class:`Scheduler` to pack active requests against the tick's
   denoiser-pass budget (FULL=2, COND=1),
5. executes one jitted **mixed-phase step** — the FULL group runs both
   streams + Eq. 1, the COND group runs the conditional stream only — and
6. advances cursors, emits tokens, retires completed requests, and (paged
   arena) reclaims a request's unconditional pages the moment its plan
   crosses into the COND suffix.

Two KV arenas (``kv=`` toggle, DESIGN.md §8–§9):

* ``"slot"`` — whole-capacity rows per request-stream; every request uses
  the engine-wide ``prompt_len``; per-group steps are ``vmap`` of a
  batch-of-one decode against gathered rows.
* ``"paged"`` — one physical page pool shared by both streams of every
  request, addressed through per-request-stream block tables
  (:class:`PageAllocator`). Requests with *different* ``prompt_len``
  share the pool; under ``reservation="eager"`` admission reserves
  exactly the pages each stream can ever touch (the unconditional stream
  only spans its FULL prefix), and k>1 same-bucket admissions prefill
  through one batched compile.

``reservation="lazy"`` (paged only, DESIGN.md §10) admits with prompt
pages alone and grows the decode span on demand at tick boundaries; the
unconditional prompt prefix is shared across same-length requests via
the canonical :class:`PrefixShareRegistry` (copy-on-write when a shared
partial page diverges), and when the pool runs dry the engine preempts
the lowest-priority/latest-deadline in-flight request — pages freed,
cursor + generated tokens + RNG key checkpointed, re-admitted through
the front of the queue with its KV rebuilt by one batched forward, token
stream bit-identical to an uninterrupted run.

``kv_dtype="int8"`` (paged only, DESIGN.md §11) stores the page pool as
int8 values paired with per-(position, kv-head) fp32 scales: prefill
scatter and decode append quantize on write, the block-table kernel
dequantizes in-loop, and admission/occupancy metrics price pages in
HBM bytes at the pool dtype — an int8 page pins ~half the bytes of a
bf16 page, which is exactly the admission headroom the equal-bytes
benchmark measures. The bf16 default path is bit-identical to the
unquantized engine; int8 is lossy under the §11 bounded-exactness
contract (pinned roundtrip bound, kernel-vs-oracle parity, greedy
token identity on short golden traces).

Step modes (``step_mode=`` toggle, DESIGN.md §12):

* ``"ragged"`` (paged default) — the whole tick runs as **one
  fixed-shape step** over a flat pass list: each of ``ragged_rows``
  rows is one denoiser pass with its own block table, position and
  phase flag; FULL entries contribute a cond and an uncond row, COND
  entries one, the rest is phase-0 padding the kernel skips. The step
  compiles **exactly once per model** — there is no occupancy in the
  jit key — which is the point: the per-signature cache below paid a
  fresh XLA compile every time traffic found a new phase mix.
* ``"signature"`` (slot arenas; opt-in for paged) — step functions are
  keyed on the tick's **occupancy signature** ``(n_full, n_cond)``,
  rounded up to power-of-two buckets so a B-slot engine compiles
  O(log²B) variants, not O(B²).

``metrics.step_compiles`` / ``metrics.step_launches`` count both modes
(a compile is counted at jit-cache-miss time, so post-warm-up ragged
traffic reads 0 recompiles). Prefills are keyed on **pow2-padded length
buckets** ``(S_bucket, k_bucket)`` in either mode so mixed-length
admission does not recompile per distinct prompt length. Padded rows use
out-of-range indices — reads clamp (garbage compute on dead data), writes
drop — so padding can never corrupt live state.

``pass_budget="auto"`` derives the budget from the roofline step-latency
model (``repro.serve.autotune``) instead of a constant: the engine lowers
its step shapes (the two pure signatures, or the single ragged step),
prices a denoiser pass at the pool's KV dtype, and packs as many passes
as fit ``target_tick_s``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ar_decode as AR
from repro.core.guidance import COMBINE_MODES, combine_guidance
from repro.core.policy import (GUIDANCE_POLICIES, DivergenceGuidancePolicy,
                               DynamicPlanCursor, GuidancePolicy, make_policy)
from repro.core.selective import GuidancePlan, Mode, PlanCursor
from repro.data.tokenizer import EOS, PAD, encode
from repro.models import transformer as T
from repro.serve.autotune import BudgetAutotuner
from repro.serve.metrics import ServeMetrics
from repro.serve.obs import TickTimer
from repro.serve.queue import ArrivalQueue, ServeRequest
from repro.serve.scheduler import (Scheduler, TickPlan, admission_cutoff,
                                   bucket_pow2, provision_growth)
from repro.serve.state import (ContentPrefixRegistry, HostPagePool,
                               PageAllocator, PrefixShareRegistry, StatePool,
                               content_key, fresh_lazy_needs,
                               host_pages_for_bytes, kv_page_bytes,
                               paged_pool_shardings, pages_for,
                               pages_shard_count, plan_swap_out,
                               pool_partition_specs, resume_lazy_needs,
                               stream_page_needs)

KV_MODES = ("slot", "paged")
KV_DTYPES = ("bf16", "int8")
RESERVATION_MODES = ("eager", "lazy")
STEP_MODES = ("signature", "ragged")
PREFIX_CACHE_MODES = ("length", "content")
TICK_MODES = ("sync", "async")


def _sample(logits, key, temperature):
    """Traced-safe sampling: argmax at temperature 0, categorical above.
    ``temperature`` may be a per-row traced scalar."""
    greedy = jnp.argmax(logits, axis=-1)
    safe = jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(key, logits / safe, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


# pow2 bucket padding for the per-signature compile cache — shared with
# the scheduler/simulator so recompile accounting agrees across the stack
_bucket = bucket_pow2


class _SlotArrays:
    """Host-side per-slot scalars (token, position, temperature, ...)."""

    def __init__(self, n: int):
        self.tok = np.zeros(n, np.int32)
        self.pos = np.zeros(n, np.int32)
        self.temp = np.zeros(n, np.float32)
        self.lstep = np.zeros(n, np.int32)
        self.key = np.zeros((n, 2), np.uint32)

    def permute(self, src: np.ndarray) -> None:
        for name in ("tok", "pos", "temp", "lstep", "key"):
            arr = getattr(self, name)
            setattr(self, name, arr[src].copy())


class _RequestState:
    def __init__(self, req: ServeRequest, cursor: PlanCursor, slot: int):
        self.req = req
        self.cursor = cursor
        self.slot = slot
        self.generated: list[int] = []
        # checkpoint state driving the reclaim trigger (DESIGN.md §15):
        # True once the uncond stream is dead — reclaimed at a transition,
        # or never allocated (all-COND plan). Restored across preemption
        # so a resumed request neither double-reclaims nor strands pages.
        self.uncond_dead = not any(s.mode is Mode.FULL
                                   for s in cursor.plan.segments)


class _ResumeState:
    """Checkpoint of a preempted request: everything exact resume needs.

    The KV pages themselves are *not* checkpointed — they are freed for
    the preemptor and rebuilt at re-admission by one forward over
    ``prompt + generated[:-1]`` (the positions the evicted run had already
    written), scattered through fresh block tables. The per-request RNG
    key and the plan cursor make the continuation bit-compatible with an
    uninterrupted run. Dynamic-policy state (realized switch step, EMA
    divergence, uncond-dead flag) is part of the checkpoint: a resumed
    request must not rebuild a dead uncond stream or re-fire its
    transition (DESIGN.md §15).
    """

    def __init__(self, *, step: int, passes: int, generated: list[int],
                 key: np.ndarray, switch_step: int | None = None,
                 ema: float = 0.0, uncond_dead: bool = False):
        self.step = step                  # plan steps executed (== lstep)
        self.passes = passes
        self.generated = generated        # prefill token + one per step
        self.key = key
        self.switch_step = switch_step    # dynamic FULL->COND switch, if any
        self.ema = ema                    # divergence running average
        self.uncond_dead = uncond_dead    # reclaim already fired


class _PrefillItem:
    """One admission normalized for the batched bucketed prefill: fresh
    eager/lazy admissions, prefix-sharing admissions (uncond scatter
    masked), and resumes (longer token row, no token emitted)."""

    def __init__(self, req: ServeRequest, slot: int, tokens: np.ndarray,
                 true_len: int, u_mask_below: int | None, key: np.ndarray,
                 emit: bool, u_tokens: np.ndarray | None = None,
                 shared_pages: int = 0, restore: int = 0,
                 cached: tuple | None = None, hit_pages: int = 0,
                 miss: bool = False, publish_key: str | None = None):
        self.req = req
        self.slot = slot
        self.tokens = tokens              # (true_len,) int32
        self.true_len = true_len
        self.u_mask_below = u_mask_below  # mask uncond scatter below this
                                          # table column (None = mask all)
        self.key = key
        self.emit = emit
        self.u_tokens = u_tokens          # uncond-stream row; None = all-null
                                          # (resume: null prompt + generated)
        self.shared_pages = shared_pages  # uncond prefix pages acquired from
                                          # the canonical copy (event deferred
                                          # to the queue-order bookkeeping
                                          # pass so engine==sim stream order
                                          # holds across length buckets)
        self.restore = restore            # pages restored from the host tier
                                          # (resume-by-copy: skips the prefill
                                          # forward entirely)
        self.cached = cached              # content-cache hit: the founder's
                                          # (l_u, l_c) last-position logits —
                                          # token 0 replays from these, no
                                          # forward runs for this item
        self.hit_pages = hit_pages        # cond prompt pages shared on a hit
        self.miss = miss                  # content lookup ran and missed
        self.publish_key = publish_key    # install this prefill's logits as
                                          # the content entry's payload


class _DeferredMetrics:
    """Captures metric calls made during the async overlap window.

    The pipelined admission for tick t+1 is decided while tick t's step
    runs on device, but its events (expire, cache-evict) belong to tick
    t+1's stream position — *after* tick t's token events. The overlap
    code runs against this recorder instead of the live ``ServeMetrics``;
    ``replay`` re-issues the calls in decision order at the start of tick
    t+1's admit phase, so the event stream is ordered exactly as a
    synchronous engine (and the simulator) would emit it.
    """

    def __init__(self):
        self.calls: list[tuple[str, tuple, dict]] = []

    def __getattr__(self, name: str):
        if not name.startswith("on_"):
            raise AttributeError(name)

        def record(*args, **kwargs):
            self.calls.append((name, args, kwargs))

        return record

    def replay(self, metrics) -> None:
        for name, args, kwargs in self.calls:
            getattr(metrics, name)(*args, **kwargs)


class _AdmitStash:
    """One tick's admission decisions, staged for deferred bookkeeping.

    ``_admit_collect`` produces this in both tick modes: sync consumes it
    immediately, async carries it across the overlap boundary (decided
    during tick t, bookkept at tick t+1).
    """

    def __init__(self, batch: list[_PrefillItem], groups: list[tuple]):
        self.batch = batch
        # (items, tok0, l_c, l_u) per length bucket — device handles,
        # unforced until _admit_bookkeep harvests them
        self.groups = groups


class ContinuousEngine:
    """Phase-aware continuous batching over a slot or paged KV arena.

    ``pass_budget`` defaults to ``num_slots``: an all-FULL tick then carries
    ``num_slots/2`` requests while an all-COND tick carries ``num_slots`` —
    the 2x late-phase admission the paper's cost asymmetry buys. Pass
    ``pass_budget="auto"`` to derive it from the roofline latency model
    against ``target_tick_s`` instead.
    """

    def __init__(self, params, cfg, *, num_slots: int = 8,
                 pass_budget=None, prompt_len: int = 32,
                 max_new: int = 32, selective_fraction: float = 0.2,
                 rules=None, seed: int = 0, stop_on_eos: bool = True,
                 policy: str = "phase", starvation_limit: int = 4,
                 defrag_threshold: float = 0.5, prefills_per_tick: int = 2,
                 queue_depth: int = 256, bucket: bool = True,
                 kv: str = "slot", page_size: int = 8,
                 num_pages: int | None = None,
                 reservation: str = "eager",
                 kv_dtype: str = "bf16",
                 target_tick_s: float = 50e-3,
                 step_mode: str | None = None,
                 host_pool_bytes: int = 0,
                 swap_min_pages: int | str = 0,
                 prefix_cache: str = "length",
                 guidance_policy: str = "static",
                 divergence_threshold: float = 0.0,
                 divergence_momentum: float = 0.0,
                 combine: str = "cfg",
                 apg_eta: float = 0.0,
                 apg_threshold: float = 0.0,
                 interval: tuple[float, float] = (0.0, 1.0),
                 mesh=None,
                 tick_mode: str = "sync"):
        if kv not in KV_MODES:
            raise ValueError(f"kv {kv!r} not in {KV_MODES}")
        if step_mode is None:
            step_mode = "ragged" if kv == "paged" else "signature"
        if step_mode not in STEP_MODES:
            raise ValueError(f"step_mode {step_mode!r} not in {STEP_MODES}")
        if tick_mode not in TICK_MODES:
            raise ValueError(f"tick_mode {tick_mode!r} not in {TICK_MODES}")
        if tick_mode == "async":
            if kv != "paged" or step_mode != "ragged":
                raise ValueError('tick_mode="async" requires kv="paged" '
                                 'and step_mode="ragged" (the pipeline '
                                 "overlaps the one-compile ragged step)")
            if stop_on_eos:
                raise ValueError('tick_mode="async" requires '
                                 "stop_on_eos=False: completion must be "
                                 "cursor-driven so tick t+1's admission "
                                 "can be decided before tick t's tokens "
                                 "are harvested")
            if guidance_policy != "static":
                raise ValueError('tick_mode="async" requires '
                                 'guidance_policy="static": a dynamic '
                                 "switch reads tick t's divergence "
                                 "signal, which the pipeline has not "
                                 "harvested when t+1 is decided")
        if step_mode == "ragged" and kv != "paged":
            raise ValueError('step_mode="ragged" requires kv="paged" (the '
                             "flat pass list addresses KV through block "
                             "tables)")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
        if kv_dtype == "int8" and kv != "paged":
            raise ValueError('kv_dtype="int8" requires kv="paged" (the '
                             "slot arena quantizes via REPRO_KV_QUANT)")
        if reservation not in RESERVATION_MODES:
            raise ValueError(f"reservation {reservation!r} not in "
                             f"{RESERVATION_MODES}")
        if reservation == "lazy" and kv != "paged":
            raise ValueError('reservation="lazy" requires kv="paged" '
                             "(the slot arena reserves whole rows)")
        if prefix_cache not in PREFIX_CACHE_MODES:
            raise ValueError(f"prefix_cache {prefix_cache!r} not in "
                             f"{PREFIX_CACHE_MODES}")
        if prefix_cache == "content" and reservation != "lazy":
            raise ValueError('prefix_cache="content" requires '
                             'reservation="lazy" (the cache shares prompt '
                             "pages, which eager reservation pre-grants)")
        if host_pool_bytes < 0:
            raise ValueError(host_pool_bytes)
        if host_pool_bytes and reservation != "lazy":
            raise ValueError("host_pool_bytes requires reservation=\"lazy\" "
                             "(swap-out rides the preemption path)")
        if swap_min_pages != "auto" and (not isinstance(swap_min_pages, int)
                                         or swap_min_pages < 0):
            raise ValueError(f"swap_min_pages {swap_min_pages!r}")
        if swap_min_pages == "auto" and pass_budget != "auto":
            raise ValueError('swap_min_pages="auto" needs the roofline '
                             'latency model: set pass_budget="auto"')
        if guidance_policy not in GUIDANCE_POLICIES:
            raise ValueError(f"guidance_policy {guidance_policy!r} not in "
                             f"{GUIDANCE_POLICIES}")
        if guidance_policy == "divergence" and divergence_threshold <= 0.0:
            raise ValueError('guidance_policy="divergence" needs '
                             "divergence_threshold > 0 (the EMA divergence "
                             "level below which the uncond stream drops)")
        if combine not in COMBINE_MODES:
            raise ValueError(f"combine {combine!r} not in {COMBINE_MODES}")
        if not 0.0 <= interval[0] < interval[1] <= 1.0:
            raise ValueError(f"interval {interval!r} must satisfy "
                             "0 <= start < stop <= 1")
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.prompt_len = prompt_len           # engine-wide maximum
        self.max_new = max_new
        self.capacity = prompt_len + max_new
        self.selective_fraction = selective_fraction
        if mesh is not None and rules is None:
            # sharded arena without an explicit rule table: the serve
            # rules already name the pages/page logical axes
            from repro.dist.sharding import RULES_SERVE
            rules = RULES_SERVE
        self.rules = rules
        self.mesh = mesh
        # paged attention form: the platform decides (compiled Pallas on a
        # TPU), except on a mesh-placed arena, whose step the partitioner
        # splits over the mesh — Mosaic kernels cannot be partitioned
        # automatically, so that step takes the gather form
        self._attn_kernel = False if mesh is not None else None
        self.tick_mode = tick_mode
        self.stop_on_eos = stop_on_eos
        self.guidance_policy = guidance_policy
        self.divergence_threshold = divergence_threshold
        self.divergence_momentum = divergence_momentum
        self.combine = combine
        self.apg_eta = apg_eta
        self.apg_threshold = apg_threshold
        self.interval = (float(interval[0]), float(interval[1]))
        self.defrag_threshold = defrag_threshold
        self.prefills_per_tick = prefills_per_tick
        self.bucket = bucket
        self.kv = kv
        self.kv_dtype = kv_dtype
        self.page_size = page_size
        self.nb_max = pages_for(self.capacity, page_size)

        self._budget_auto = pass_budget == "auto"
        if self._budget_auto:
            self.pass_budget = max(2, num_slots)    # provisional until tuned
            self._autotuner = BudgetAutotuner(target_tick_s, min_budget=2,
                                              max_budget=2 * num_slots)
        else:
            self.pass_budget = pass_budget if pass_budget is not None \
                else num_slots
            self._autotuner = None

        self.step_mode = step_mode
        # the ragged step's fixed row count: every tick fits (a plan packs
        # at most min(budget, 2*num_slots) passes), so the step compiles
        # exactly once per model — there is no other shape to miss on
        self.ragged_rows = 2 * num_slots if self._budget_auto \
            else min(self.pass_budget, 2 * num_slots)

        self.reservation = reservation
        self.queue = ArrivalQueue(max_depth=queue_depth)
        self.pool = StatePool(num_slots)       # slot rows / host row ids
        self.pages: PageAllocator | None = None
        self._prefix: PrefixShareRegistry | None = None
        self._resume: dict[str, _ResumeState] = {}
        self._pool_shards = pages_shard_count(self.rules, mesh) \
            if (kv == "paged" and mesh is not None and rules is not None) \
            else 1
        if kv == "paged":
            # fail fast on unpageable stacks (recurrent state, MLA latents)
            from repro.models import layers as L
            T.paged_cache_specs(cfg, L.AxesMaker(), 1, page_size,
                                kv_dtype=kv_dtype)
            if num_pages is not None:
                # explicit count is honored as-is: an indivisible pool
                # falls down the logical_to_spec chain (partial subset or
                # replicated) instead of silently resizing
                self.num_pages = num_pages
            else:
                self.num_pages = 2 * num_slots * self.nb_max
                if self._pool_shards > 1:
                    # uniform shard shapes: the default pool rounds up to
                    # one whole page multiple per mesh shard
                    s = self._pool_shards
                    self.num_pages = -(-self.num_pages // s) * s
            self.pages = PageAllocator(self.num_pages, page_size,
                                       kv_dtype=kv_dtype)
            if reservation == "lazy":
                self._prefix = PrefixShareRegistry(self.pages)
        self.prefix_cache = prefix_cache
        self._content: ContentPrefixRegistry | None = \
            ContentPrefixRegistry(self.pages) if prefix_cache == "content" \
            else None
        self.scheduler = Scheduler(self.pass_budget, policy=policy,
                                   starvation_limit=starvation_limit)
        self.metrics = ServeMetrics()
        self.page_bytes = kv_page_bytes(cfg, page_size, kv_dtype) \
            if kv == "paged" else 0
        # host tier: byte budget -> whole pages at this pool's page price
        self.host_pool_bytes = host_pool_bytes
        host_pages = host_pages_for_bytes(host_pool_bytes, self.page_bytes)
        if host_pool_bytes and not host_pages:
            raise ValueError(f"host_pool_bytes={host_pool_bytes} affords no "
                             f"whole page (page_bytes={self.page_bytes})")
        self._host: HostPagePool | None = \
            HostPagePool(host_pages, page_bytes=self.page_bytes) \
            if host_pages else None
        self._swap_min_auto = swap_min_pages == "auto"
        self._swap_min = 0 if self._swap_min_auto else int(swap_min_pages)
        # price pages in HBM bytes at the pool's dtype so occupancy
        # metrics compare across bf16/int8 (abstract specs only)
        self.metrics.page_bytes = self.page_bytes
        self.results: dict[str, list[int]] = {}
        self.tick_count = 0

        self._base_key = jax.random.PRNGKey(seed)
        self._req_seq = 0
        self._states: dict[str, _RequestState] = {}
        self._slots = _SlotArrays(num_slots)
        self._jit: dict = {}
        self._pool_c = None                    # slot: cond arena
        self._pool_u = None                    # slot: uncond arena
        self._pool_p = None                    # paged: the shared page pool
        # async pipeline state: (tick, deferred metric calls, admissions)
        # decided during the previous tick's overlap window
        self._stash: tuple | None = None
        self._staging = None                   # double-buffered ragged args

    # -- public API --------------------------------------------------------

    def submit(self, req: ServeRequest) -> bool:
        """Queue a request at the current tick; False = rejected (queue
        full, or the request's plan/length is invalid for this engine)."""
        self.metrics.on_arrival(req.uid, self.tick_count)
        try:
            plan = self._plan_for(req)
            plan.validate_for_ar()
            S = self._prompt_len_for(req)
            if self.kv == "paged":
                # a request that can never fit the pool must not wedge the
                # FCFS head of the queue forever
                if sum(stream_page_needs(plan, S, self.page_size)) > \
                        self.num_pages:
                    raise ValueError("page need exceeds pool")
        except ValueError:
            self.metrics.on_reject(req.uid, self.tick_count)
            return False
        ok = self.queue.push(req, self.tick_count)
        if not ok:
            self.metrics.on_reject(req.uid, self.tick_count)
        return ok

    @property
    def _has_pending(self) -> bool:
        """Async: the previous tick's overlap window left work that must
        replay next tick — deferred events (e.g. an expiry decided during
        overlap) or staged admissions. Stashed admissions also hold
        scheduler slots, but a pure-event stash would otherwise strand."""
        if self._stash is None:
            return False
        _, rec, stash = self._stash
        return bool(rec.calls) or stash is not None

    def drain(self, max_ticks: int = 100_000) -> None:
        """Tick until queue and slots are empty."""
        while len(self.queue) or self.scheduler.n_active or self._has_pending:
            if self.tick_count >= max_ticks:
                raise RuntimeError(f"engine did not drain in {max_ticks} ticks")
            self.tick()

    def serve(self, requests: list[ServeRequest]) -> dict[str, list[int]]:
        """Submit everything now, drain, return uid -> generated tokens."""
        return self.serve_trace(requests, [0] * len(requests))

    def serve_trace(self, requests: list[ServeRequest], arrivals,
                    max_ticks: int = 100_000) -> dict[str, list[int]]:
        """Drive an arrival trace: ``requests[i]`` is submitted once
        ``arrivals[i]`` ticks (relative to now, non-decreasing) have
        elapsed; drains and returns uid -> generated tokens. The single
        trace driver shared by the launcher and the benchmarks."""
        start = self.tick_count
        i = 0
        while i < len(requests) or self.scheduler.n_active \
                or len(self.queue) or self._has_pending:
            if self.tick_count - start >= max_ticks:
                raise RuntimeError(f"trace did not drain in {max_ticks} ticks")
            while i < len(requests) and \
                    start + int(arrivals[i]) <= self.tick_count:
                self.submit(requests[i])
                i += 1
            self.tick()
        return {r.uid: self.results[r.uid] for r in requests
                if r.uid in self.results}

    def tick(self) -> TickPlan:
        if self.tick_mode == "async":
            return self._tick_async()
        timer = TickTimer(self.tick_count)
        now = self.tick_count
        # metrics objects are replaceable (benchmarks reset them between
        # warmup and measurement): keep the byte pricing installed
        self.metrics.page_bytes = self.page_bytes
        with timer.phase("admit"):
            self._expire_queue(now)
            if self._autotuner is not None and not self._autotuner.per_pass_s:
                self.autotune_budget()
            if self.kv == "paged":
                self._admit_paged(now)
                self.metrics.note_pages(self.pages.n_in_use, now)
            else:
                self._admit(now)
                self._maybe_defrag()
        with timer.phase("schedule"):
            plan = self.scheduler.plan_tick()
            if self.reservation == "lazy" and plan.in_flight:
                # on-demand page growth / CoW detach / priority preemption —
                # the same decision procedure the simulator replays offline
                plan = provision_growth(
                    plan, self.scheduler, self.pages,
                    page_size=self.page_size,
                    pos_of=lambda uid: int(
                        self._slots.pos[self._states[uid].slot]),
                    metrics=self.metrics,
                    preempt=lambda uid: self._preempt(uid, now),
                    copy_page=self._copy_page,
                    reclaim_cache=self._reclaim_cache,
                    now=now)
                self.metrics.note_pages(self.pages.n_in_use, now)
        with timer.phase("step"):
            sampled, divs = self._execute(plan) if plan.in_flight \
                else ([], [])
        with timer.phase("finalize"):
            events = self.scheduler.commit(plan)
            for ev, nxt, dv in zip(events, sampled, divs):
                state = self._states[ev.uid]
                if ev.done:
                    self._finalize(ev.uid, now)       # last sample discarded
                    continue
                if self.stop_on_eos and nxt == EOS:
                    self._finalize(ev.uid, now)
                    continue
                state.generated.append(int(nxt))
                slot = state.slot
                self._slots.tok[slot] = nxt
                self._slots.pos[slot] += 1
                self._slots.lstep[slot] += 1
                self.metrics.on_token(ev.uid, now, cond=ev.mode is Mode.COND)
                cursor = state.cursor
                if ev.mode is Mode.FULL \
                        and isinstance(cursor, DynamicPlanCursor) \
                        and cursor.observe(dv):
                    # the EMA'd cond/uncond divergence crossed the policy's
                    # threshold: every remaining plan-FULL step runs COND
                    self.metrics.on_policy_switch(
                        ev.uid, now, step=cursor.switch_step,
                        elided=cursor.elided_uncond_passes())
                if not state.uncond_dead and not cursor.done \
                        and cursor.mode is Mode.COND:
                    # the schedule (static plan or dynamic switch) just
                    # crossed into COND: the uncond stream is dead — in the
                    # paged arena, return its pages to the shared pool now.
                    # uncond_dead is checkpoint state, not an event-mode
                    # inference, so a request preempted exactly at the
                    # boundary reclaims exactly once (DESIGN.md §15)
                    state.uncond_dead = True
                    self.metrics.on_phase_transition(ev.uid, now)
                    if self.kv == "paged":
                        self.metrics.on_reclaim(ev.uid, now,
                                                self._release_uncond(ev.uid))
            self.metrics.record_tick(
                now, n_full=plan.n_full, n_cond=plan.n_cond,
                budget=plan.budget, active=self.scheduler.n_active,
                queue_depth=len(self.queue),
                pages_in_use=self.pages.n_in_use if self.pages else 0)
        self.metrics.on_tick_timing(timer.finish())
        self.tick_count += 1
        return plan

    def _expire_queue(self, now: int) -> None:
        for dead in self.queue.expire(now):
            had_ckpt = self._resume.pop(dead.uid, None) is not None
            self.metrics.on_expire(dead.uid, now)      # ttl keeps running
            if had_ckpt and self._host is not None:    # queued; drop the
                freed = self._host.drop(dead.uid)      # host checkpoint
                if freed:                              # with it — no leak
                    self.metrics.on_host_evict(dead.uid, now, freed)

    def _tick_async(self) -> TickPlan:
        """One pipelined tick (DESIGN.md §16).

        Tick ``now``'s admissions were *decided* during tick ``now-1``'s
        overlap window (the stash); this tick replays their deferred
        events and bookkeeping, schedules and dispatches the ragged step
        without blocking, then — while the device works — decides tick
        ``now+1``'s expiries and admissions. Only the final harvest
        blocks on the step's outputs. The decision procedures are the
        exact functions the synchronous tick runs (``_admit_collect``,
        ``provision_growth``, ``Scheduler.commit``), and every metric
        emission is sequenced to the synchronous order, so counters,
        event streams and token values are identical to ``tick_mode=
        "sync"`` on admission-order-preserving traces.
        """
        timer = TickTimer(self.tick_count)
        now = self.tick_count
        self.metrics.page_bytes = self.page_bytes
        with timer.phase("admit"):
            if self._autotuner is not None and not self._autotuner.per_pass_s:
                self.autotune_budget()
            if self._stash is not None:
                stamp, rec, stash = self._stash
                self._stash = None
                assert stamp == now, (stamp, now)
                rec.replay(self.metrics)
                if stash is not None:
                    self._admit_bookkeep(stash, now)
            elif admission_cutoff(now, pipelined=True) == now:
                # tick 0: no prior overlap window, and the shared cutoff
                # says arrivals at `now` are still admissible — the
                # pipeline fills inline
                self._expire_queue(now)
                stash = self._admit_collect(now)
                if stash is not None:
                    self._admit_bookkeep(stash, now)
            self.metrics.note_pages(self.pages.n_in_use, now)
        with timer.phase("schedule"):
            plan = self.scheduler.plan_tick()
            if self.reservation == "lazy" and plan.in_flight:
                plan = provision_growth(
                    plan, self.scheduler, self.pages,
                    page_size=self.page_size,
                    pos_of=lambda uid: int(
                        self._slots.pos[self._states[uid].slot]),
                    metrics=self.metrics,
                    preempt=lambda uid: self._preempt(uid, now),
                    copy_page=self._copy_page,
                    reclaim_cache=self._reclaim_cache,
                    now=now)
                self.metrics.note_pages(self.pages.n_in_use, now)
        with timer.phase("step"):
            handles = None
            if plan.in_flight:
                self.metrics.on_step_launch(self.tick_count)
                handles = self._dispatch_ragged(plan)
        with timer.phase("finalize"):
            # structural finalize runs *before* the overlap window so
            # tick now+1's admission decisions see completed requests'
            # pages (and COND-transition uncond pages) back in the pool —
            # exactly the state a synchronous tick would leave. Token
            # values are not needed for any of it (async mode pins
            # stop_on_eos=False and the static policy), so nothing here
            # blocks on the device.
            events = self.scheduler.commit(plan)
            pending = []
            for ev in events:
                state = self._states[ev.uid]
                if ev.done:
                    passes = state.cursor.passes_executed
                    self._finalize_state(ev.uid)
                    pending.append(("done", ev.uid, passes))
                    continue
                freed = None
                cursor = state.cursor
                if not state.uncond_dead and not cursor.done \
                        and cursor.mode is Mode.COND:
                    state.uncond_dead = True
                    freed = self._release_uncond(ev.uid)
                pending.append(("tok", ev.uid, state.slot, ev.mode, freed))
            # record_tick inputs snapshot the synchronous end-of-tick
            # state, before the overlap mutates queue/scheduler/pool
            snap = (self.scheduler.n_active, len(self.queue),
                    self.pages.n_in_use)
        with timer.phase("overlap"):
            # host-side scheduling for tick now+1 overlaps the in-flight
            # device step; its metric calls are captured for replay so
            # the event stream keeps the synchronous order
            rec = _DeferredMetrics()
            real, self.metrics = self.metrics, rec
            try:
                self._expire_queue(now + 1)
                stash = self._admit_collect(now + 1)
            finally:
                self.metrics = real
            self._stash = (now + 1, rec, stash)
        with timer.phase("finalize"):
            sampled = self._harvest_ragged(*handles)[0] \
                if handles is not None else []
            for info, nxt in zip(pending, sampled):
                if info[0] == "done":
                    _, uid, passes = info
                    self.metrics.on_complete(uid, now, passes)
                    continue
                _, uid, slot, mode, freed = info
                self._states[uid].generated.append(int(nxt))
                self._slots.tok[slot] = nxt
                self._slots.pos[slot] += 1
                self._slots.lstep[slot] += 1
                self.metrics.on_token(uid, now, cond=mode is Mode.COND)
                if freed is not None:
                    self.metrics.on_phase_transition(uid, now)
                    self.metrics.on_reclaim(uid, now, freed)
            self.metrics.record_tick(
                now, n_full=plan.n_full, n_cond=plan.n_cond,
                budget=plan.budget, active=snap[0], queue_depth=snap[1],
                pages_in_use=snap[2])
        self.metrics.on_tick_timing(timer.finish())
        self.tick_count += 1
        return plan

    # -- admission ---------------------------------------------------------

    def _plan_for(self, req: ServeRequest) -> GuidancePlan:
        if req.plan is not None:
            if req.plan.total_steps > self.max_new:
                raise ValueError(f"plan of {req.plan.total_steps} steps "
                                 f"exceeds engine max_new={self.max_new}")
            base = req.plan
        else:
            total = max(1, min(req.max_new_tokens, self.max_new))
            frac = (self.selective_fraction if req.selective_fraction is None
                    else req.selective_fraction)
            base = GuidancePlan.suffix(total, frac, req.guidance_scale)
        # the *bound* plan (DESIGN.md §15): what admission, reservation and
        # the pass budget price — a guaranteed upper bound on FULL steps.
        # Static/divergence bind the base plan unchanged; interval binds
        # GuidancePlan.interval, whose segments also carry the scales.
        return self._policy_for(base).bound_plan()

    def _policy_for(self, plan: GuidancePlan) -> GuidancePolicy:
        return make_policy(self.guidance_policy, plan,
                           threshold=self.divergence_threshold,
                           momentum=self.divergence_momentum,
                           interval=self.interval)

    def _cursor_for(self, plan: GuidancePlan, *, step: int = 0,
                    passes: int = 0, switch_step: int | None = None,
                    ema: float = 0.0) -> PlanCursor:
        """Per-request cursor through the configured policy. The static
        policy returns a plain :class:`PlanCursor` — bit-compatible with
        the pre-policy engine. ``switch_step``/``ema`` restore a
        preemption checkpoint's dynamic state."""
        policy = self._policy_for(plan)
        if isinstance(policy, DivergenceGuidancePolicy):
            return policy.cursor(step=step, passes_executed=passes,
                                 switch_step=switch_step, ema=ema)
        return policy.cursor(step=step, passes_executed=passes)

    def _eff_scale(self, uid: str, lstep: int | None = None) -> np.float32:
        """Combine-stage guidance scale for ``uid``'s step ``lstep`` (default:
        its next): the bound plan's segment scale where it sets one (the
        interval policy's 1.0 before its start), else the request's."""
        state = self._states[uid]
        if lstep is None:
            lstep = int(self._slots.lstep[state.slot])
        scale = state.cursor.plan.segment_at(lstep).scale
        return np.float32(state.req.guidance_scale if scale is None
                          else scale)

    def _combine(self, l_u, l_c, scale):
        """The configured combine stage (Eq. 1 or APG, DESIGN.md §15)."""
        return combine_guidance(l_u, l_c, scale, self.combine,
                                apg_eta=self.apg_eta,
                                apg_threshold=self.apg_threshold)

    def _prompt_len_for(self, req: ServeRequest) -> int:
        S = self.prompt_len if req.prompt_len is None else req.prompt_len
        if self.kv == "slot":
            if S != self.prompt_len:
                raise ValueError(f"slot arena serves fixed prompt_len="
                                 f"{self.prompt_len}, got {S}")
        elif not 1 <= S <= self.prompt_len:
            raise ValueError(f"prompt_len {S} outside [1, {self.prompt_len}]")
        return S

    def _tokenize(self, prompt, length: int) -> np.ndarray:
        if isinstance(prompt, str):
            ids = encode(prompt, self.cfg.vocab_size, length)
        else:
            ids = list(prompt)[:length]
            ids = ids + [PAD] * (length - len(ids))
        return np.asarray(ids, np.int32)[None]        # (1, length)

    def _admit(self, now: int) -> None:
        quota = min(self.scheduler.admission_quota(self.pool.n_free),
                    self.prefills_per_tick)
        for _ in range(quota):
            req = self.queue.pop()
            if req is None:
                return
            # plan construction before alloc: a raise here must not leak a
            # slot (plans are also pre-validated at submit)
            plan = self._plan_for(req)
            plan.validate_for_ar()
            cursor = self._cursor_for(plan)
            slot = self.pool.alloc(req.uid)
            assert slot is not None
            state = _RequestState(req, cursor, slot)
            self._states[req.uid] = state
            self.scheduler.admit(req.uid, slot, cursor, arrival=req.arrival,
                                 deadline=req.deadline, priority=req.priority)

            key = np.asarray(jax.random.fold_in(self._base_key, self._req_seq))
            self._req_seq += 1
            self._slots.pos[slot] = self.prompt_len
            self._slots.temp[slot] = req.temperature
            self._slots.lstep[slot] = 0
            self._slots.key[slot] = key

            if self._pool_c is None:
                self._init_pools()
            fn = self._prefill_fn()
            self._pool_c, self._pool_u, tok0 = fn(
                self.params, self._pool_c, self._pool_u,
                jnp.asarray(self._tokenize(req.prompt, self.prompt_len)),
                slot, jnp.asarray(key), self._eff_scale(req.uid, 0),
                np.float32(req.temperature))
            tok0 = int(tok0)
            self.metrics.on_admit(
                req.uid, now, total_steps=plan.total_steps,
                full_steps=plan.denoiser_passes() - plan.total_steps)
            if self.stop_on_eos and tok0 == EOS:
                self._finalize(req.uid, now)
                continue
            self._slots.tok[slot] = tok0
            state.generated.append(tok0)
            self.metrics.on_token(req.uid, now)       # TTFT: prefill emits

    def _admit_paged(self, now: int) -> None:
        """Synchronous admission: decide + prefill, then bookkeep, in one
        tick. The async tick runs the same two halves one tick apart."""
        stash = self._admit_collect(now)
        if stash is not None:
            self._admit_bookkeep(stash, now)

    def _admit_collect(self, now: int) -> _AdmitStash | None:
        """Pop admissible requests, then prefill them in per-length-bucket
        batches — one compile serves k>1 simultaneous admissions of a
        bucket. Under ``reservation="eager"`` admission requires the full
        worst-case page span; under ``"lazy"`` only the prompt pages
        (decode pages grow on demand), the uncond prompt prefix is shared
        through the canonical registry, and preempted requests re-admit
        through the same batched prefill (their KV rebuilt from
        prompt + generated tokens, no token emitted).

        This is the *decision* half (PR 4 discipline: one procedure for
        sync, async and the simulator): it claims slots/pages, dispatches
        the prefill forwards and returns the stash; the queue-order
        metric bookkeeping lives in ``_admit_bookkeep``."""
        quota = min(self.scheduler.admission_quota(self.pool.n_free),
                    self.prefills_per_tick)
        batch: list[_PrefillItem] = []
        lazy = self.reservation == "lazy"
        while len(batch) < quota:
            req = self.queue.peek()
            if req is None:
                break
            plan = self._plan_for(req)
            S = self._prompt_len_for(req)
            if lazy and req.uid in self._resume:
                item = self._try_admit_resume(req, plan, S, now)
            elif lazy:
                item = self._try_admit_lazy(req, plan, S, now)
            else:
                item = self._try_admit_eager(req, plan, S, now)
            if item is None:
                break                         # head-of-line waits for pages
            batch.append(item)
        if not batch:
            return None
        if self._pool_p is None:
            self._init_paged_pool()
        groups: dict[int, list] = {}
        for item in batch:
            if item.restore or item.cached is not None:
                continue               # no forward: host restore / replay
            groups.setdefault(_bucket(item.true_len), []).append(item)
        prefills = []
        for Sb in sorted(groups):
            its = groups[Sb]
            prefills.append((its,) + self._prefill_paged_group(Sb, its))
        return _AdmitStash(batch, prefills)

    def _admit_bookkeep(self, stash: _AdmitStash, now: int) -> None:
        """Harvest the stashed prefill results (this is where the host
        first blocks on the device) and emit the admission events. Split
        from ``_admit_collect`` so the async tick can run the decision
        half inside the overlap window and replay this half — with the
        captured event stream — at the next tick's admit phase."""
        tok0_of: dict[str, int] = {}
        for items, tok0, l_c, l_u in stash.groups:
            tok0 = np.asarray(tok0)
            if self._content is not None and \
                    any(it.publish_key for it in items):
                # install the founders' pre-combine last-position logits
                # as the content entries' payloads: a later hit replays
                # token 0 from these with its own scale/key/temp, zero
                # passes (`ready()` gates hits to ticks strictly after
                # the publish tick, so deferring the install here never
                # races a lookup)
                l_c_h, l_u_h = np.asarray(l_c), np.asarray(l_u)
                for i, it in enumerate(items):
                    if it.publish_key:
                        self._content.set_payload(
                            it.publish_key,
                            (l_u_h[i].copy(), l_c_h[i].copy()))
            for i, it in enumerate(items):
                tok0_of[it.req.uid] = int(tok0[i])
        for it in stash.batch:
            if it.cached is None:
                continue
            # content-cache hit: token 0 replays from the founder's cached
            # pre-combine logits with this request's own scale/key/temp —
            # bit-exact vs the prefill's vmapped sample (elementwise
            # cfg_combine + per-element vmap semantics)
            l_u, l_c = it.cached
            t0 = self._hit_sample_fn()(
                jnp.asarray(l_u), jnp.asarray(l_c),
                self._eff_scale(it.req.uid, 0), jnp.asarray(it.key),
                np.float32(it.req.temperature))
            tok0_of[it.req.uid] = int(t0)
        # bookkeeping in *queue order* (not bucket order): the simulator
        # admits one request at a time, so the event stream must read
        # share -> hit/miss -> admit -> first-token (or share -> swap_in
        # -> resume) per request in pop order for the engine==sim event
        # contract to hold
        for it in stash.batch:
            uid = it.req.uid
            if it.shared_pages:
                self.metrics.on_share(uid, now, it.shared_pages)
            if it.hit_pages:
                self.metrics.on_prefix_hit(uid, now, it.hit_pages)
            elif it.miss:
                self.metrics.on_prefix_miss(uid, now)
            if not it.emit:                # resume: KV rebuilt, no emit
                if it.restore:
                    self.metrics.on_swap_in(uid, now, it.restore)
                cursor = self._states[uid].cursor
                self.metrics.on_resume(uid, now,
                                       full=int(cursor.mode is Mode.FULL),
                                       from_host=bool(it.restore))
                continue
            state = self._states[uid]
            plan = state.cursor.plan
            self.metrics.on_admit(
                uid, now, total_steps=plan.total_steps,
                full_steps=plan.denoiser_passes() - plan.total_steps,
                cached=it.cached is not None)
            t0 = tok0_of[uid]
            if self.stop_on_eos and t0 == EOS:
                self._finalize(uid, now)
                continue
            self._slots.tok[it.slot] = t0
            state.generated.append(t0)
            self.metrics.on_token(uid, now)           # TTFT: prefill emits

    def _admit_common(self, req: ServeRequest, cursor: PlanCursor,
                      pos: int) -> int:
        """Slot-row claim + scheduler admission + per-slot scalars shared
        by the eager / lazy / resume paged admission paths."""
        slot = self.pool.alloc(req.uid)
        assert slot is not None
        state = _RequestState(req, cursor, slot)
        self._states[req.uid] = state
        self.scheduler.admit(req.uid, slot, cursor, arrival=req.arrival,
                             deadline=req.deadline, priority=req.priority)
        self._slots.pos[slot] = pos
        self._slots.temp[slot] = req.temperature
        return slot

    def _fresh_key(self) -> np.ndarray:
        key = np.asarray(jax.random.fold_in(self._base_key, self._req_seq))
        self._req_seq += 1
        return key

    def _free_for_admission(self, n: int, uid: str, now: int) -> bool:
        """Make ``n`` device pages free for a blocked admission by
        draining the content cache. The §14 content entries are
        *persistent*, so an idle pool can be all cache with nothing
        active to trigger ``provision_growth``'s reclaim path — without
        this the queue head would wedge on pure cache. The length-keyed
        uncond registry is left alone: its entries die with their users,
        so it can never pin an idle pool (and evicting live shares here
        would change pre-§14 scheduling)."""
        while self.pages.n_free < n:
            if self._content is None or \
                    not self._content.evict_under_pressure():
                return False
            self.metrics.on_cache_evict(uid, now)
        return True

    def _try_admit_eager(self, req: ServeRequest, plan: GuidancePlan,
                         S: int, now: int) -> _PrefillItem | None:
        need_c, need_u = stream_page_needs(plan, S, self.page_size)
        if self.pages.n_free < need_c + need_u:
            return None
        self.queue.pop()
        self.pages.alloc(req.uid, "c", need_c)
        if need_u:
            self.pages.alloc(req.uid, "u", need_u)
        slot = self._admit_common(req, self._cursor_for(plan), S)
        key = self._fresh_key()
        self._slots.lstep[slot] = 0
        self._slots.key[slot] = key
        return _PrefillItem(req, slot, self._tokenize(req.prompt, S)[0],
                            S, 0, key, emit=True)

    def _try_admit_lazy(self, req: ServeRequest, plan: GuidancePlan,
                        S: int, now: int) -> _PrefillItem | None:
        shared = self._prefix.lookup(S) is not None
        need_c, need_u, wants_u = fresh_lazy_needs(plan, S, self.page_size,
                                                   shared=shared)
        tokens = self._tokenize(req.prompt, S)[0]
        ckey = content_key(tokens) if self._content is not None else None
        if ckey is not None and self._content.ready(ckey, now) \
                and self._content.matches(ckey, tokens) \
                and (not wants_u or shared):
            # identical prompt, founder's prefill already ran, and the
            # uncond side (if any) is servable from the length registry:
            # admit with zero forward passes
            return self._admit_prefix_hit(req, plan, S, now, tokens, ckey,
                                          wants_u)
        if not self._free_for_admission(need_c + need_u, req.uid, now):
            return None
        self.queue.pop()
        self.pages.alloc(req.uid, "c", need_c)
        u_mask: int | None = 0                 # founder scatters everything
        n_share = 0
        if wants_u and shared:
            n_share = len(self._prefix.acquire(S, req.uid))
            u_mask = None                      # canonical content: no writes
        elif wants_u:
            self.pages.alloc(req.uid, "u", need_u)
            self._prefix.publish(S, req.uid)   # this prefill is canonical
        slot = self._admit_common(req, self._cursor_for(plan), S)
        key = self._fresh_key()
        self._slots.lstep[slot] = 0
        self._slots.key[slot] = key
        miss = ckey is not None
        publish_key = None
        if miss and self._content.lookup(ckey) is None:
            # found the content cache cold: this prefill's cond prompt
            # pages become the canonical entry (hittable next tick)
            self._content.publish(ckey, req.uid, ids=tokens, tick=now)
            publish_key = ckey
        return _PrefillItem(req, slot, tokens, S, u_mask, key, emit=True,
                            shared_pages=n_share, miss=miss,
                            publish_key=publish_key)

    def _admit_prefix_hit(self, req: ServeRequest, plan: GuidancePlan,
                          S: int, now: int, tokens: np.ndarray, ckey: str,
                          wants_u: bool) -> _PrefillItem:
        """Content-cache hit: share the canonical cond prompt pages (and
        the length-keyed uncond prefix, when the plan has a FULL phase)
        and replay token 0 from the founder's cached last-position logits
        — the whole admission costs zero denoiser passes."""
        self.queue.pop()
        got = self._content.acquire(ckey, req.uid)
        n_share = len(self._prefix.acquire(S, req.uid)) if wants_u else 0
        slot = self._admit_common(req, self._cursor_for(plan), S)
        key = self._fresh_key()
        self._slots.lstep[slot] = 0
        self._slots.key[slot] = key
        payload = self._content.payload(ckey)
        assert payload is not None     # ready() gates on the founder tick
        return _PrefillItem(req, slot, tokens, S, None, key, emit=True,
                            shared_pages=n_share, hit_pages=len(got),
                            cached=payload)

    def _try_admit_resume(self, req: ServeRequest, plan: GuidancePlan,
                          S: int, now: int) -> _PrefillItem | None:
        rs = self._resume[req.uid]
        if self._host is not None and self._host.holds(req.uid):
            # restore by copy: the preemption swap kept this checkpoint's
            # exact KV pages, so re-admission is a host->device DMA and
            # zero denoiser passes (the recompute path below stays the
            # fallback once LRU pressure drops the checkpoint)
            held = self._host.pages_of(req.uid)
            total = sum(len(v) for v in held.values())
            if not self._free_for_admission(total, req.uid, now):
                return None
            self.queue.pop()
            del self._resume[req.uid]
            if self._pool_p is None:
                self._init_paged_pool()
            for stream in sorted(held):
                dst = self.pages.alloc(req.uid, stream, len(held[stream]))
                self._restore_pages(held[stream], dst)
            self._host.drop(req.uid)
            L = S + rs.step
            cursor = self._cursor_for(plan, step=rs.step, passes=rs.passes,
                                      switch_step=rs.switch_step, ema=rs.ema)
            slot = self._admit_common(req, cursor, L)
            state = self._states[req.uid]
            state.uncond_dead = rs.uncond_dead
            state.generated = list(rs.generated)
            self._slots.tok[slot] = rs.generated[-1]
            self._slots.lstep[slot] = rs.step
            self._slots.key[slot] = rs.key
            return _PrefillItem(req, slot, np.zeros(0, np.int32), L, None,
                                rs.key, emit=False, restore=total)
        shared = self._prefix.lookup(S) is not None
        need_c, need_u, wants_u, n_share = resume_lazy_needs(
            plan, rs.step, S, self.page_size, shared=shared,
            switch_step=rs.switch_step)
        if not self._free_for_admission(need_c + need_u, req.uid, now):
            return None
        self.queue.pop()
        del self._resume[req.uid]
        self.pages.alloc(req.uid, "c", need_c)
        u_mask: int | None = None
        if wants_u:
            if n_share:
                self._prefix.acquire(S, req.uid, count=n_share)
                if need_u:
                    self.pages.grow(req.uid, "u", need_u)
                u_mask = n_share               # write only the private tail
            else:
                self.pages.alloc(req.uid, "u", need_u)
                u_mask = 0
        L = S + rs.step
        cursor = self._cursor_for(plan, step=rs.step, passes=rs.passes,
                                  switch_step=rs.switch_step, ema=rs.ema)
        slot = self._admit_common(req, cursor, L)
        state = self._states[req.uid]
        state.uncond_dead = rs.uncond_dead
        state.generated = list(rs.generated)
        self._slots.tok[slot] = rs.generated[-1]
        self._slots.lstep[slot] = rs.step
        self._slots.key[slot] = rs.key
        row = np.concatenate([self._tokenize(req.prompt, S)[0],
                              np.asarray(rs.generated[:-1], np.int32)])
        # the uncond stream consumed the *sampled* tokens during decode:
        # null the prompt only, replay the generated suffix verbatim
        u_row = row.copy()
        u_row[:S] = PAD
        return _PrefillItem(req, slot, row, L, u_mask, rs.key, emit=False,
                            u_tokens=u_row,
                            shared_pages=n_share if wants_u else 0)

    def _prefill_paged_group(self, Sb: int,
                             items: list[_PrefillItem]) -> tuple:
        kb = _bucket(len(items))
        nb_pre = pages_for(Sb, self.page_size)
        tokens = np.full((kb, Sb), PAD, np.int32)
        tokens_u = np.full((kb, Sb), PAD, np.int32)   # PAD == null token
        true_len = np.ones(kb, np.int32)
        btc = np.full((kb, nb_pre), self.num_pages, np.int32)
        btu = np.full((kb, nb_pre), self.num_pages, np.int32)
        keys = np.zeros((kb, 2), np.uint32)
        scales = np.zeros(kb, np.float32)
        temps = np.zeros(kb, np.float32)
        for i, it in enumerate(items):
            tokens[i, :it.true_len] = it.tokens
            if it.u_tokens is not None:
                tokens_u[i, :it.true_len] = it.u_tokens
            true_len[i] = it.true_len
            btc[i] = self.pages.table(it.req.uid, "c", nb_pre)
            tu = self.pages.table(it.req.uid, "u", nb_pre)
            if it.u_mask_below is None:
                tu[:] = self.num_pages         # shared/absent: writes drop
            else:
                tu[:it.u_mask_below] = self.num_pages
            btu[i] = tu
            keys[i] = it.key
            scales[i] = self._eff_scale(it.req.uid, 0)
            temps[i] = it.req.temperature
        fn = self._paged_prefill_fn(Sb, kb)
        self._pool_p, tok0, l_c, l_u = fn(
            self.params, self._pool_p,
            jnp.asarray(tokens), jnp.asarray(tokens_u),
            jnp.asarray(true_len),
            jnp.asarray(btc), jnp.asarray(btu),
            jnp.asarray(keys), jnp.asarray(scales),
            jnp.asarray(temps))
        # hand back unforced device handles: converting tok0 here would
        # stall the async overlap window on the in-flight decode step —
        # _admit_bookkeep harvests them (and installs founder payloads)
        return tok0, l_c, l_u

    def _release_uncond(self, uid: str) -> int:
        """Free a request's unconditional pages at the COND transition,
        dropping its prefix-registry membership with them. Canonical
        pages the registry frees here (the departing request was the
        entry's last user) count toward the reclaim too — they return to
        the pool mid-flight just the same."""
        freed = self.pages.free(uid, "u")
        if self._prefix is not None:
            freed += self._prefix.release(uid)
        return freed

    def _reclaim_cache(self) -> bool:
        """Pool-pressure cache reclaim, content tier first: persistent
        content entries are pure cache (recomputable from the prompt) so
        they yield before the uncond length-prefix registry, whose
        canonical copies live requests may still be acquiring."""
        if self._content is not None and \
                self._content.evict_under_pressure():
            return True
        return self._prefix.evict_under_pressure()

    def _preempt(self, uid: str, now: int) -> None:
        """RUNNING -> PREEMPTED: evict ``uid`` back to the queue. Its
        pages are freed for the preemptor; the plan cursor, generated
        tokens and RNG key are checkpointed so the eventual resume is
        token-identical to an uninterrupted run. With a host tier, the
        victim's pages are copied out first (preempt -> host_evict* ->
        swap_out event order, the contract the sim replays) so resume
        restores by DMA copy instead of recompute."""
        state = self._states.pop(uid)
        self._resume[uid] = _ResumeState(
            step=state.cursor.step, passes=state.cursor.passes_executed,
            generated=list(state.generated),
            key=self._slots.key[state.slot].copy(),
            switch_step=getattr(state.cursor, "switch_step", None),
            ema=getattr(state.cursor, "ema", 0.0),
            uncond_dead=state.uncond_dead)
        self.pool.free(state.slot)
        self.metrics.on_preempt(uid, now)
        swap = plan_swap_out(self.pages, self._host, uid,
                             min_pages=self._swap_min)
        if swap is not None:
            put = self._host.put(uid, swap)
            assert put is not None       # plan_swap_out checked capacity
            placed, evicted = put
            for euid, n_freed in evicted:
                self.metrics.on_host_evict(euid, now, n_freed)
            self._swap_out(uid, swap, placed)
            self.metrics.on_swap_out(uid, now, sum(swap.values()))
        self.pages.free_all(uid)
        self._prefix.release(uid)
        if self._content is not None:
            self._content.release(uid)
        self.scheduler.release(uid)
        self.queue.requeue(state.req)

    def _copy_page(self, src: int, dst: int) -> None:
        """Device copy backing a CoW detach (page payload, all layers)."""
        fn = self._copy_page_fn()
        self._pool_p = fn(self._pool_p, np.int32(src), np.int32(dst))

    def _swap_out(self, uid: str, swap: dict[str, int],
                  placed: dict[str, list[int]]) -> None:
        """Copy a preemption victim's device pages into its reserved host
        slots, stream by stream: one pow2-bucketed gather per stream
        reads the pages (values and int8 scales through the same
        indices, so the §11 pair invariant holds across tiers), then a
        host-side scatter into the arena."""
        if self._host.arena is None:
            self._host.attach(self._pool_p)
        for stream in sorted(swap):
            pages_dev = self.pages.owned(uid, stream)
            n = len(pages_dev)
            nb = _bucket(n)
            idx = np.zeros(nb, np.int32)       # pad in-range: store slices
            idx[:n] = pages_dev
            rows = jax.device_get(
                self._gather_pages_fn(nb)(self._pool_p, jnp.asarray(idx)))
            self._host.store(placed[stream], rows)

    def _restore_pages(self, host_slots: list[int],
                       dev_pages: list[int]) -> None:
        """Scatter host-tier page rows into freshly granted device pages
        (the resume-from-host path): one pow2-bucketed scatter, padding
        addressed at the out-of-range page index so it drops."""
        rows = self._host.load(host_slots)
        n = len(dev_pages)
        nb = _bucket(n)
        idx = np.full(nb, self.num_pages, np.int32)
        idx[:n] = dev_pages

        def pad(leaf):
            axis = 1 if leaf.ndim == 5 else 0
            if leaf.shape[axis] == nb:
                return jnp.asarray(leaf)
            widths = [(0, 0)] * leaf.ndim
            widths[axis] = (0, nb - leaf.shape[axis])
            return jnp.asarray(np.pad(leaf, widths))

        self._pool_p = self._scatter_pages_fn(nb)(
            self._pool_p, jnp.asarray(idx), jax.tree.map(pad, rows))

    def _finalize_state(self, uid: str) -> "_RequestState":
        """The structural half of completion: free the slot, pages and
        registry memberships and publish the result. The async tick runs
        this before its overlap window (so tick t+1's admission sees the
        freed pages) and defers only the ``complete`` event to the
        harvest, where it lands in the synchronous stream order."""
        state = self._states.pop(uid)
        self.pool.free(state.slot)
        if self.pages is not None:
            self.pages.free_all(uid)
            if self._prefix is not None:
                self._prefix.release(uid)
            if self._content is not None:
                self._content.release(uid)
        self.scheduler.release(uid)
        self.results[uid] = state.generated
        return state

    def _finalize(self, uid: str, now: int) -> None:
        state = self._finalize_state(uid)
        self.metrics.on_complete(uid, now, state.cursor.passes_executed)

    # -- defragmentation (slot arena only) ---------------------------------

    def _maybe_defrag(self) -> None:
        if self.pool.fragmentation() <= self.defrag_threshold:
            return
        src = self.pool.defrag_plan()
        if src is None or self._pool_c is None:
            return
        fn = self._defrag_fn()
        self._pool_c, self._pool_u = fn(self._pool_c, self._pool_u,
                                        jnp.asarray(src))
        self._slots.permute(src)
        for slot, uid in self.pool.active():
            self._states[uid].slot = slot
            self.scheduler.reslot(uid, slot)

    # -- jitted device functions ------------------------------------------

    def _init_pools(self) -> None:
        S, cap, cfg = self.prompt_len, self.capacity, self.cfg

        def one_stream(params, prompt):
            _, caches = AR.prefill(params, cfg, prompt, rules=self.rules)
            return T.prepare_decode_caches(cfg, caches, seq_len=S,
                                           capacity=cap)

        row = jax.eval_shape(one_stream, self.params,
                             jax.ShapeDtypeStruct((1, S), jnp.int32))
        zeros = lambda s: jnp.zeros((self.num_slots,) + tuple(s.shape), s.dtype)
        self._pool_c = jax.tree.map(zeros, row)
        self._pool_u = jax.tree.map(zeros, row)
        if self.mesh is not None and self.rules is not None:
            from jax.sharding import NamedSharding
            specs = pool_partition_specs(
                self.cfg, self.num_slots, cap, rules=self.rules,
                mesh=self.mesh)
            # the spec tree mirrors T.cache_specs; decode-prepared caches
            # can grow extra leaves (e.g. REPRO_KV_QUANT scale pairs) the
            # spec builder does not model — those configs keep the
            # replicated layout rather than guessing at specs
            if jax.tree.structure(specs) == jax.tree.structure(self._pool_c):
                put = lambda x, sp: jax.device_put(
                    x, NamedSharding(self.mesh, sp))
                self._pool_c = jax.tree.map(put, self._pool_c, specs)
                self._pool_u = jax.tree.map(put, self._pool_u, specs)

    def _init_paged_pool(self) -> None:
        from repro.models import layers as L
        specs = T.paged_cache_specs(self.cfg, L.SpecMaker(jnp.bfloat16),
                                    self.num_pages, self.page_size,
                                    kv_dtype=self.kv_dtype)
        zeros = lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                     specs)
        if self.mesh is not None and self.rules is not None:
            # land the arena on the mesh at construction: values, int8
            # fp32 scale leaves and block-table-indexed rows all shard
            # along `pages` (per-shard counts uniform by the ctor's
            # divisibility rounding; indivisible explicit pools fall down
            # the logical_to_spec fallback chain to replication). The
            # zeros are made in place, shard by shard: the whole pool never
            # lands on one device first.
            shardings = paged_pool_shardings(
                self.cfg, self.num_pages, self.page_size,
                rules=self.rules, mesh=self.mesh, kv_dtype=self.kv_dtype)
            self._pool_p = jax.jit(zeros, out_shardings=shardings)()
            return
        self._pool_p = zeros()

    def _prefill_fn(self):
        # pow2-padded length bucket key: the slot engine serves one fixed
        # prompt_len, but the key shape is shared with the paged prefills
        # so mixed-length engines never compile per distinct length
        key = ("prefill", _bucket(self.prompt_len), 1)
        if key in self._jit:
            return self._jit[key]
        S, cap, cfg, rules = self.prompt_len, self.capacity, self.cfg, self.rules

        def fn(params, pool_c, pool_u, prompt, slot, rkey, scale, temp):
            logits_c, cc = AR.prefill(params, cfg, prompt, rules=rules)
            logits_u, cu = AR.prefill(params, cfg, AR.null_prompt(prompt),
                                      rules=rules)
            cc = T.prepare_decode_caches(cfg, cc, seq_len=S, capacity=cap)
            cu = T.prepare_decode_caches(cfg, cu, seq_len=S, capacity=cap)
            logits = self._combine(logits_u, logits_c, scale)
            tok0 = _sample(logits, jax.random.fold_in(rkey, 0), temp)
            pool_c = jax.tree.map(lambda p, r: p.at[slot].set(r), pool_c, cc)
            pool_u = jax.tree.map(lambda p, r: p.at[slot].set(r), pool_u, cu)
            return pool_c, pool_u, tok0[0]

        self._jit[key] = jax.jit(fn, donate_argnums=(1, 2))
        return self._jit[key]

    def _paged_prefill_fn(self, Sb: int, kb: int):
        """Batched dual-stream prefill for one (length-bucket, k-bucket):
        tokens (kb, Sb) at true lengths ``true_len``, KV scattered through
        per-row block tables into the shared page pool."""
        key = ("prefill", Sb, kb)
        if key in self._jit:
            return self._jit[key]
        cfg, rules = self.cfg, self.rules
        ps = self.page_size

        # per-layer scatter (models/attention.paged_scatter_prefill):
        # cache {k,v} (kb, Sb, K, hd) — or with a leading layers axis for
        # scan segments — lands in the matching pool layer through the
        # flattened (kb*Sb,) pages/offs; out-of-range pages (padding, or
        # positions a short prompt never covers) drop. An int8 pool
        # quantizes on write inside the same traversal, so prefill stays
        # one-pass (DESIGN.md §11).
        is_layer = lambda x: isinstance(x, dict)

        def scatter_all(pool, caches, pages, offs):
            from repro.models import attention as A
            return jax.tree.map(
                lambda p, c: A.paged_scatter_prefill(p, c, pages, offs),
                pool, caches, is_leaf=is_layer)

        def fn(params, pool, tokens, tokens_u, true_len, btc, btu, keys,
               scales, temps):
            h_c, caches_c, _ = T.forward(params, cfg, tokens,
                                         want_caches=True, rules=rules)
            # tokens_u is the explicit null stream: all-PAD for fresh
            # admissions (== AR.null_prompt), null prompt + replayed
            # generated suffix for preemption resumes
            h_u, caches_u, _ = T.forward(params, cfg, tokens_u,
                                         want_caches=True, rules=rules)
            last = (true_len - 1)[:, None, None]
            take = lambda h: jnp.take_along_axis(
                h, jnp.broadcast_to(last, (kb, 1, h.shape[-1])), axis=1)
            l_c = T.unembed(params, cfg, take(h_c))[:, 0, :].astype(jnp.float32)
            l_u = T.unembed(params, cfg, take(h_u))[:, 0, :].astype(jnp.float32)
            logits = self._combine(l_u, l_c, scales[:, None])

            def sample0(lg, k, t):
                return _sample(lg[None], jax.random.fold_in(k, 0), t)[0]

            tok0 = jax.vmap(sample0)(logits, keys, temps)

            posidx = jnp.arange(Sb)
            offs = jnp.tile(posidx % ps, kb)
            slot_of = posidx // ps                          # (Sb,) table col
            pages_c = btc[:, slot_of].reshape(kb * Sb)
            pages_u = btu[:, slot_of].reshape(kb * Sb)
            pool = scatter_all(pool, caches_c, pages_c, offs)
            pool = scatter_all(pool, caches_u, pages_u, offs)
            # the pre-combine logits ride out so content-cache founders
            # can deposit them as replayable payloads
            return pool, tok0, l_c, l_u

        self._jit[key] = jax.jit(fn, donate_argnums=(1,))
        return self._jit[key]

    def _step_fn(self, n_full: int, n_cond: int):
        """Mixed-phase decode step for one occupancy signature."""
        key = ("step", n_full, n_cond)
        if key in self._jit:
            return self._jit[key]
        self.metrics.on_step_compile(self.tick_count)
        cfg, rules = self.cfg, self.rules

        def fn(params, pool_c, pool_u, f_idx, f_tok, f_pos, f_scale, f_temp,
               f_key, f_lstep, c_idx, c_tok, c_pos, c_temp, c_key, c_lstep):

            def one_full(cc, cu, tok, pos, scale, temp, rkey, lstep):
                emb = T.embed_tokens(params, cfg, tok[None, None])
                h_c, cc = T.decode_step(params, cfg, emb, cc, pos, rules=rules)
                h_u, cu = T.decode_step(params, cfg, emb, cu, pos, rules=rules)
                l_c = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
                l_u = T.unembed(params, cfg, h_u)[:, 0, :].astype(jnp.float32)
                logits = self._combine(l_u, l_c, scale)
                nxt = _sample(logits, jax.random.fold_in(rkey, 1 + lstep), temp)
                # the dynamic-policy signal: ||l_c - l_u||_2 for this step
                div = jnp.sqrt(jnp.sum((l_c - l_u) ** 2))
                return nxt[0], cc, cu, div

            def one_cond(cc, tok, pos, temp, rkey, lstep):
                emb = T.embed_tokens(params, cfg, tok[None, None])
                h_c, cc = T.decode_step(params, cfg, emb, cc, pos, rules=rules)
                logits = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
                nxt = _sample(logits, jax.random.fold_in(rkey, 1 + lstep), temp)
                return nxt[0], cc

            f_next = jnp.zeros((n_full,), jnp.int32)
            c_next = jnp.zeros((n_cond,), jnp.int32)
            f_div = jnp.zeros((n_full,), jnp.float32)
            if n_full:
                rows_c = jax.tree.map(lambda a: a[f_idx], pool_c)
                rows_u = jax.tree.map(lambda a: a[f_idx], pool_u)
                f_next, rows_c, rows_u, f_div = jax.vmap(one_full)(
                    rows_c, rows_u, f_tok, f_pos, f_scale, f_temp, f_key,
                    f_lstep)
                pool_c = jax.tree.map(
                    lambda p, r: p.at[f_idx].set(r, mode="drop"), pool_c, rows_c)
                pool_u = jax.tree.map(
                    lambda p, r: p.at[f_idx].set(r, mode="drop"), pool_u, rows_u)
            if n_cond:
                rows_c = jax.tree.map(lambda a: a[c_idx], pool_c)
                c_next, rows_c = jax.vmap(one_cond)(
                    rows_c, c_tok, c_pos, c_temp, c_key, c_lstep)
                pool_c = jax.tree.map(
                    lambda p, r: p.at[c_idx].set(r, mode="drop"), pool_c, rows_c)
            # divergences ride at the END of the tuple so the autotuner's
            # out[0]/out[1] pool indices stay stable
            return pool_c, pool_u, f_next, c_next, f_div

        self._jit[key] = jax.jit(fn, donate_argnums=(1, 2))
        return self._jit[key]

    def _paged_step_fn(self, n_full: int, n_cond: int):
        """Mixed-phase decode step against the shared page pool: both
        streams of the FULL group and the cond stream of the COND group
        write/read through their block tables; per-row positions let
        mixed-length requests step together."""
        key = ("pstep", n_full, n_cond)
        if key in self._jit:
            return self._jit[key]
        self.metrics.on_step_compile(self.tick_count)
        cfg, rules, kernel = self.cfg, self.rules, self._attn_kernel

        def sample_rows(logits, keys, temps, lsteps):
            def one(lg, k, t, ls):
                return _sample(lg[None], jax.random.fold_in(k, 1 + ls), t)[0]
            return jax.vmap(one)(logits, keys, temps, lsteps)

        def fn(params, pool, f_btc, f_btu, f_tok, f_pos, f_scale, f_temp,
               f_key, f_lstep, c_btc, c_tok, c_pos, c_temp, c_key, c_lstep):
            f_next = jnp.zeros((n_full,), jnp.int32)
            c_next = jnp.zeros((n_cond,), jnp.int32)
            f_div = jnp.zeros((n_full,), jnp.float32)
            if n_full:
                emb = T.embed_tokens(params, cfg, f_tok[:, None])
                h_c, pool = T.decode_step_paged(params, cfg, emb, pool,
                                                f_btc, f_pos, rules=rules,
                                                kernel=kernel)
                h_u, pool = T.decode_step_paged(params, cfg, emb, pool,
                                                f_btu, f_pos, rules=rules,
                                                kernel=kernel)
                l_c = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
                l_u = T.unembed(params, cfg, h_u)[:, 0, :].astype(jnp.float32)
                logits = self._combine(l_u, l_c, f_scale[:, None])
                f_next = sample_rows(logits, f_key, f_temp, f_lstep)
                f_div = jnp.sqrt(jnp.sum((l_c - l_u) ** 2, axis=-1))
            if n_cond:
                emb = T.embed_tokens(params, cfg, c_tok[:, None])
                h_c, pool = T.decode_step_paged(params, cfg, emb, pool,
                                                c_btc, c_pos, rules=rules,
                                                kernel=kernel)
                logits = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
                c_next = sample_rows(logits, c_key, c_temp, c_lstep)
            # f_div rides at the END: the autotuner's out[0] stays the pool
            return pool, f_next, c_next, f_div

        self._jit[key] = jax.jit(fn, donate_argnums=(1,))
        return self._jit[key]

    def _ragged_step_fn(self):
        """One fixed-shape decode step for the whole tick's flat pass list
        (DESIGN.md §12) — the step that kills the occupancy compile cache.

        Every row is one denoiser pass addressed by its own block table,
        position and phase flag; ``ragged_rows`` is fixed at construction,
        so this compiles exactly once per model whatever phase mix the
        scheduler packs. ``u_idx[r]`` names the row carrying row ``r``'s
        unconditional logits for Eq. 1: the uncond pair row for FULL
        output rows, ``r`` itself everywhere else — the self-pairing makes
        ``cfg_combine`` the exact fp32 identity (``c - u == 0``) so COND,
        uncond and padding rows need no masking.
        """
        R = self.ragged_rows
        key = ("rstep", R)
        if key in self._jit:
            return self._jit[key]
        self.metrics.on_step_compile(self.tick_count)
        cfg, rules, kernel = self.cfg, self.rules, self._attn_kernel

        def fn(params, pool, bt, tok, pos, scale, temp, rkey, lstep, u_idx,
               phase):
            emb = T.embed_tokens(params, cfg, tok[:, None])
            h, pool = T.decode_step_paged(params, cfg, emb, pool, bt, pos,
                                          rules=rules, phase=phase,
                                          kernel=kernel)
            logits = T.unembed(params, cfg, h)[:, 0, :].astype(jnp.float32)
            combined = self._combine(logits[u_idx], logits, scale[:, None])

            def one(lg, k, t, ls):
                return _sample(lg[None], jax.random.fold_in(k, 1 + ls), t)[0]

            nxt = jax.vmap(one)(combined, rkey, temp, lstep)
            # per-output-row divergence signal; self-paired rows (COND,
            # uncond, padding) read exactly 0 — div rides at the END so
            # the autotuner's out[0] stays the pool
            div = jnp.sqrt(jnp.sum((logits - logits[u_idx]) ** 2, axis=-1))
            return pool, nxt, div

        self._jit[key] = jax.jit(fn, donate_argnums=(1,))
        return self._jit[key]

    def _defrag_fn(self):
        key = ("defrag",)
        if key not in self._jit:
            def fn(pool_c, pool_u, src):
                take = lambda a: a[src]
                return jax.tree.map(take, pool_c), jax.tree.map(take, pool_u)
            self._jit[key] = jax.jit(fn, donate_argnums=(0, 1))
        return self._jit[key]

    def _copy_page_fn(self):
        """CoW payload copy ``pool[dst] = pool[src]`` across every layer
        leaf (stacked segments carry a leading layers axis). ``src``/
        ``dst`` are traced scalars: one compile serves every detach."""
        key = ("copy_page",)
        if key not in self._jit:
            def fn(pool, src, dst):
                def one(leaf):
                    if leaf.ndim == 5:              # (layers, P, ps, K, hd)
                        return leaf.at[:, dst].set(leaf[:, src])
                    return leaf.at[dst].set(leaf[src])
                return jax.tree.map(one, pool)
            self._jit[key] = jax.jit(fn, donate_argnums=(0,))
        return self._jit[key]

    def _gather_pages_fn(self, nb: int):
        """Gather ``nb`` whole pages from every pool leaf (swap-out read).
        Padding indices are in-range (0): the host store slices them off,
        and a clamped read can never fault."""
        key = ("hgather", nb)
        if key not in self._jit:
            def fn(pool, idx):
                return jax.tree.map(
                    lambda leaf: leaf[:, idx] if leaf.ndim == 5
                    else leaf[idx], pool)
            self._jit[key] = jax.jit(fn)
        return self._jit[key]

    def _scatter_pages_fn(self, nb: int):
        """Scatter ``nb`` page rows into the pool (restore-from-host
        write); padding rows address ``num_pages`` and drop."""
        key = ("hscatter", nb)
        if key not in self._jit:
            def fn(pool, idx, rows):
                def one(leaf, r):
                    if leaf.ndim == 5:          # (layers, P, ps, K, hd)
                        return leaf.at[:, idx].set(r, mode="drop")
                    return leaf.at[idx].set(r, mode="drop")
                return jax.tree.map(one, pool, rows)
            self._jit[key] = jax.jit(fn, donate_argnums=(0,))
        return self._jit[key]

    def _hit_sample_fn(self):
        """Token-0 replay for a content-cache hit: Eq. 1 over the
        founder's cached pre-combine logits with the hit request's own
        scale/key/temperature. ``cfg_combine`` is elementwise and the
        prefill samples through a per-row ``vmap``, so this unbatched
        replay is bit-exact against what a fresh prefill would emit."""
        key = ("hit_sample",)
        if key not in self._jit:
            def fn(l_u, l_c, scale, rkey, temp):
                lg = self._combine(l_u, l_c, scale)
                return _sample(lg[None], jax.random.fold_in(rkey, 0),
                               temp)[0]
            self._jit[key] = jax.jit(fn)
        return self._jit[key]

    # -- pass-budget autotuning (roofline hook) ----------------------------

    def autotune_budget(self) -> dict:
        """Derive ``pass_budget`` from the roofline step-latency model.

        Signature mode lowers + compiles the two pure occupancy signatures
        ((1,0) and (0,1)) and prices a denoiser pass from each; ragged
        mode lowers its single fixed-width step — the only executable it
        will ever run — and prices a pass at full packing
        (``repro.serve.autotune``). Either way the engine installs the
        largest budget whose predicted tick latency fits ``target_tick_s``
        priced at the pool's KV dtype. Idempotent; also runs automatically
        on the first tick when ``pass_budget="auto"``.
        """
        if self._autotuner is None:
            raise ValueError('autotuning requires pass_budget="auto"')
        if self.kv == "paged":
            if self._pool_p is None:
                self._init_paged_pool()
        elif self._pool_c is None:
            self._init_pools()
        i32 = lambda *s: np.zeros(s, np.int32)
        f32 = lambda *s: np.zeros(s, np.float32)
        u32 = lambda *s: np.zeros(s, np.uint32)
        # dummy rows address out-of-range slots/pages (reads clamp, writes
        # drop), so the warm-up execution below cannot corrupt live state
        oob_slot = lambda n: np.full(n, self.num_slots, np.int32)
        oob_bt = lambda n: np.full((n, self.nb_max), self.num_pages, np.int32)
        if self.step_mode == "ragged":
            R = self.ragged_rows
            fn = self._ragged_step_fn()
            args = (self.params, self._pool_p, oob_bt(R), i32(R), i32(R),
                    f32(R), f32(R), u32(R, 2), i32(R),
                    np.arange(R, dtype=np.int32), i32(R))
            self._autotuner.observe_ragged(R, fn.lower(*args).compile(),
                                           kv_dtype=self.kv_dtype)
            # warm the jit dispatch cache too: the AOT compile above does
            # not populate it, and this is the only step shape the engine
            # ever dispatches — pay the one compile here, not on traffic
            self._pool_p = fn(*args)[0]
        else:
            for sig in ((1, 0), (0, 1)):
                nf, nc = sig
                if self.kv == "paged":
                    fn = self._paged_step_fn(nf, nc)
                    args = (self.params, self._pool_p,
                            oob_bt(nf), oob_bt(nf),
                            i32(nf), i32(nf), f32(nf), f32(nf), u32(nf, 2),
                            i32(nf), oob_bt(nc), i32(nc), i32(nc),
                            f32(nc), u32(nc, 2), i32(nc))
                else:
                    fn = self._step_fn(nf, nc)
                    args = (self.params, self._pool_c, self._pool_u,
                            oob_slot(nf), i32(nf), i32(nf), f32(nf), f32(nf),
                            u32(nf, 2), i32(nf), oob_slot(nc), i32(nc),
                            i32(nc), f32(nc), u32(nc, 2), i32(nc))
                self._autotuner.observe(sig, fn.lower(*args).compile(),
                                        kv_dtype=self.kv_dtype)
                # warm the jit dispatch cache too: the AOT compile above
                # does not populate it, and (1,0)/(0,1) are the most common
                # real signatures — pay both compiles here, not on traffic
                out = fn(*args)
                if self.kv == "paged":
                    self._pool_p = out[0]
                else:
                    self._pool_c, self._pool_u = out[0], out[1]
        budget = self._autotuner.budget(self.kv_dtype)
        if self.step_mode == "ragged":
            budget = min(budget, self.ragged_rows)
        self.pass_budget = budget
        self.scheduler.pass_budget = budget
        self.metrics.on_autotune(self.tick_count, budget)
        if self._swap_min_auto and self._host is not None:
            # restore-bytes vs recompute-passes break-even: checkpoints
            # cheaper to recompute than to DMA back skip the host tier
            self._swap_min = self._autotuner.swap_break_even_pages(
                self.page_bytes, kv_dtype=self.kv_dtype)
        return self._autotuner.report(self.kv_dtype)

    # -- HBM accounting ----------------------------------------------------

    def kv_hbm_bytes(self) -> dict:
        """Reserved vs peak-in-use KV arena bytes — the number the
        ``--kv paged|slot`` benchmark toggle compares at equal budget.
        Computed from abstract specs / ``eval_shape`` only: asking for the
        accounting never allocates the arena."""
        import math as _math
        from repro.models import layers as L
        leaf_bytes = lambda s: _math.prod(s.shape) * np.dtype(s.dtype).itemsize
        if self.kv == "paged":
            # every pool leaf scales linearly in num_pages, so the spec-
            # derived per-page price from __init__ is the whole accounting
            return {"kv": "paged", "kv_dtype": self.kv_dtype,
                    "reserved_bytes": self.num_pages * self.page_bytes,
                    "page_bytes": self.page_bytes,
                    # the byte-true counter, NOT peak_pages * page_bytes:
                    # the page peak and the byte peak can come from
                    # different instants once page_bytes varies, and an
                    # int8 pool priced off the page count overstated its
                    # high-water mark
                    "peak_in_use_bytes": self.metrics.peak_bytes_in_use,
                    "num_pages": self.num_pages,
                    "page_size": self.page_size}
        S, cap, cfg = self.prompt_len, self.capacity, self.cfg

        def one_stream(params, prompt):
            _, caches = AR.prefill(params, cfg, prompt, rules=self.rules)
            return T.prepare_decode_caches(cfg, caches, seq_len=S,
                                           capacity=cap)

        row = jax.eval_shape(one_stream, self.params,
                             jax.ShapeDtypeStruct((1, S), jnp.int32))
        row_bytes = sum(leaf_bytes(l) for l in jax.tree.leaves(row))
        reserved = 2 * self.num_slots * row_bytes    # both streams, all rows
        peak_active = max((r.active for r in self.metrics.records), default=0)
        return {"kv": "slot", "reserved_bytes": reserved,
                "row_bytes": 2 * row_bytes,
                "peak_in_use_bytes": int(peak_active * 2 * row_bytes),
                "num_slots": self.num_slots}

    # -- execution ---------------------------------------------------------

    def _group_arrays(self, entries, bucket_n: int):
        """Gathered per-slot scalars for one group, padded to ``bucket_n``
        with the out-of-bounds slot index (clamped reads, dropped writes)."""
        slots = [e.slot for e in entries]
        pad = bucket_n - len(slots)
        idx = np.asarray(slots + [self.num_slots] * pad, np.int32)
        real = np.asarray(slots, np.int32)
        gather = lambda a: np.concatenate(
            [a[real], np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad \
            else a[real].copy()
        return (jnp.asarray(idx), jnp.asarray(gather(self._slots.tok)),
                jnp.asarray(gather(self._slots.pos)),
                jnp.asarray(gather(self._slots.temp)),
                jnp.asarray(gather(self._slots.key)),
                jnp.asarray(gather(self._slots.lstep)))

    def _group_tables(self, entries, bucket_n: int, stream: str):
        """Block tables for one group, padded rows all out-of-range."""
        out = np.full((bucket_n, self.nb_max), self.num_pages, np.int32)
        for i, e in enumerate(entries):
            out[i] = self.pages.table(e.uid, stream, self.nb_max)
        return jnp.asarray(out)

    def _execute(self, plan: TickPlan) -> tuple[list[int], list[float]]:
        """Run one mixed-phase step; returns sampled next-tokens and the
        per-entry cond/uncond divergence norms (0.0 for COND entries),
        both aligned with ``plan.full + plan.cond``."""
        self.metrics.on_step_launch(self.tick_count)
        if self.step_mode == "ragged":
            return self._execute_ragged(plan)
        nf_b = _bucket(plan.n_full) if self.bucket else plan.n_full
        nc_b = _bucket(plan.n_cond) if self.bucket else plan.n_cond
        f_idx, f_tok, f_pos, f_temp, f_key, f_lstep = \
            self._group_arrays(plan.full, nf_b)
        c_idx, c_tok, c_pos, c_temp, c_key, c_lstep = \
            self._group_arrays(plan.cond, nc_b)
        f_scale = np.zeros(nf_b, np.float32)
        f_scale[:plan.n_full] = [self._eff_scale(e.uid) for e in plan.full]
        f_scale = jnp.asarray(f_scale)
        if self.kv == "paged":
            fn = self._paged_step_fn(nf_b, nc_b)
            self._pool_p, f_next, c_next, f_div = fn(
                self.params, self._pool_p,
                self._group_tables(plan.full, nf_b, "c"),
                self._group_tables(plan.full, nf_b, "u"),
                f_tok, f_pos, f_scale, f_temp, f_key, f_lstep,
                self._group_tables(plan.cond, nc_b, "c"),
                c_tok, c_pos, c_temp, c_key, c_lstep)
        else:
            fn = self._step_fn(nf_b, nc_b)
            self._pool_c, self._pool_u, f_next, c_next, f_div = fn(
                self.params, self._pool_c, self._pool_u,
                f_idx, f_tok, f_pos, f_scale, f_temp, f_key, f_lstep,
                c_idx, c_tok, c_pos, c_temp, c_key, c_lstep)
        f_next = np.asarray(f_next)[: plan.n_full]
        c_next = np.asarray(c_next)[: plan.n_cond]
        f_div = np.asarray(f_div)[: plan.n_full]
        toks = [int(t) for t in f_next] + [int(t) for t in c_next]
        divs = [float(d) for d in f_div] + [0.0] * plan.n_cond
        return toks, divs

    def _execute_ragged(self, plan: TickPlan) -> list[int]:
        """Run the whole tick as one fixed-shape ragged step. Row layout
        (the DESIGN.md §12 contract, emitted by ``plan.pass_rows()``):
        rows ``[0, in_flight)`` are the output rows — every entry's cond
        pass in ``plan.full + plan.cond`` order — rows
        ``[in_flight, in_flight + n_full)`` are the FULL entries' uncond
        passes, and the rest is padding (phase 0, out-of-range tables:
        reads clamp, writes drop, attention output is exactly zero).
        Returns sampled next-tokens and per-entry divergence norms (0.0
        for COND entries) aligned with ``plan.full + plan.cond``.
        """
        return self._harvest_ragged(*self._dispatch_ragged(plan))

    def _ragged_staging(self) -> dict:
        """Double-buffered host staging, selected by tick parity.
        ``jnp.asarray`` may alias host numpy memory zero-copy, so the
        buffers a dispatched-but-unfinished step reads must not be
        refilled by the next dispatch. The async pipeline is exactly one
        tick deep (tick t's step is harvested before tick t+1 dispatches),
        so two buffers suffice."""
        if self._staging is None:
            R = self.ragged_rows

            def bufs():
                return dict(
                    bt=np.full((R, self.nb_max), self.num_pages, np.int32),
                    tok=np.zeros(R, np.int32),
                    pos=np.zeros(R, np.int32),
                    scale=np.zeros(R, np.float32),
                    temp=np.zeros(R, np.float32),
                    rkey=np.zeros((R, 2), np.uint32),
                    lstep=np.zeros(R, np.int32),
                    u_idx=np.arange(R, dtype=np.int32),
                    phase=np.zeros(R, np.int32))

            self._staging = (bufs(), bufs())
        return self._staging[self.tick_count & 1]

    def _dispatch_ragged(self, plan: TickPlan) -> tuple:
        """Stage the tick's rows and launch the ragged step; returns
        unforced device handles ``(nxt, div, n_out)`` for
        ``_harvest_ragged``. The async tick calls this before its overlap
        window and harvests after, so host scheduling for tick t+1 runs
        while the device executes tick t."""
        R = self.ragged_rows
        rows = plan.pass_rows()
        assert len(rows) <= R, (len(rows), R)
        n_out = plan.in_flight
        st = self._ragged_staging()
        bt, tok, pos = st["bt"], st["tok"], st["pos"]
        scale, temp, rkey = st["scale"], st["temp"], st["rkey"]
        lstep, u_idx, phase = st["lstep"], st["u_idx"], st["phase"]
        bt.fill(self.num_pages)
        tok.fill(0); pos.fill(0); scale.fill(0.0); temp.fill(0.0)
        rkey.fill(0); lstep.fill(0); phase.fill(0)
        u_idx[:] = np.arange(R, dtype=np.int32)   # self-pair: Eq.1 identity
        for r, pr in enumerate(rows):
            slot = pr.entry.slot
            bt[r] = self.pages.table(pr.entry.uid, pr.stream, self.nb_max)
            tok[r] = self._slots.tok[slot]
            pos[r] = self._slots.pos[slot]
            scale[r] = self._eff_scale(pr.entry.uid)
            temp[r] = self._slots.temp[slot]
            rkey[r] = self._slots.key[slot]
            lstep[r] = self._slots.lstep[slot]
            phase[r] = 1
        u_idx[: plan.n_full] = n_out + np.arange(plan.n_full)
        fn = self._ragged_step_fn()
        self._pool_p, nxt, div = fn(
            self.params, self._pool_p, jnp.asarray(bt), jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(scale), jnp.asarray(temp),
            jnp.asarray(rkey), jnp.asarray(lstep), jnp.asarray(u_idx),
            jnp.asarray(phase))
        return nxt, div, n_out

    def _harvest_ragged(self, nxt, div, n_out: int) -> tuple:
        """Force the step's outputs — the only point where the host
        blocks on the device in ragged mode."""
        return ([int(t) for t in np.asarray(nxt)[:n_out]],
                [float(d) for d in np.asarray(div)[:n_out]])
