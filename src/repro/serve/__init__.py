"""Phase-aware continuous-batching serving subsystem (DESIGN.md §8–§9).

The unit of scheduling is the denoiser-pass slot: a FULL-phase request
costs 2 passes per tick, a COND-phase request costs 1 — the paper's cost
asymmetry as a packing problem. The same asymmetry governs memory under
the paged KV arena (``kv="paged"``): a request's unconditional pages are
reclaimed the moment its plan enters the COND suffix, so selective
guidance saves HBM as well as FLOPs. ``repro.serving.ServingEngine``
remains as a static-batching compatibility facade over
:class:`ContinuousEngine`.

Observability (``repro.serve.obs``, DESIGN.md §13): every engine/sim
state change is a typed event in ``metrics.trace``; counters fold from
the stream, latency percentiles come from log2 histograms, and a run
exports to Chrome-trace JSON via :func:`to_chrome_trace`.

Fleet tier (``repro.serve.fleet``, DESIGN.md §16): N replicas behind a
:class:`FleetRouter` with prefix-affinity placement (repeats of a
``content_key`` land on the replica whose cache holds them) and
byte-load fallback; :func:`fleet_summary` merges per-replica counters
and histograms into fleet-wide percentiles, and :func:`simulate_fleet`
replays the identical routing offline. The engine's pipelined tick mode
(``tick_mode="async"``) shares its admission cutoff with the simulator
via :func:`admission_cutoff`.

Tiered KV memory (DESIGN.md §14): preemption victims park their pages
in a byte-budgeted pinned-host :class:`HostPagePool` and resume by DMA
restore instead of recompute (``plan_swap_out`` is the shared
engine/sim decision procedure), and a :class:`ContentPrefixRegistry`
keyed by :func:`content_key` lets identical prompts share
cond-stream prompt KV copy-on-write.
"""

from repro.serve.autotune import BudgetAutotuner
from repro.serve.engine import TICK_MODES, ContinuousEngine
from repro.serve.fleet import (FLEET_COUNTERS, ROUTE_POLICIES, FleetReport,
                               FleetRouter, ServeFleet, fleet_summary,
                               simulate_fleet)
from repro.serve.metrics import RequestTimeline, ServeMetrics, TickRecord
from repro.serve.obs import (Event, EventTrace, Log2Histogram, TickTimer,
                             TickTiming, fleet_chrome_trace, fold_counters,
                             to_chrome_trace, write_chrome_trace)
from repro.serve.queue import ArrivalQueue, ServeRequest
from repro.serve.scheduler import (PassRow, Scheduler, TickPlan,
                                   admission_cutoff, bucket_pow2,
                                   provision_growth, victim_key)
from repro.serve.sim import (SimRequest, compare_policies, poisson_arrivals,
                             poisson_trace, simulate)
from repro.serve.state import (ContentPrefixRegistry, HostPagePool,
                               PageAllocator, PrefixShareRegistry, StatePool,
                               content_key, fresh_lazy_needs,
                               host_pages_for_bytes, kv_page_bytes,
                               page_nbytes, paged_partition_specs,
                               paged_pool_shardings, pages_for,
                               pages_for_pool_bytes, pages_shard_count,
                               plan_swap_out, pool_partition_specs,
                               pooled_cache_axes, resume_lazy_needs,
                               stream_page_needs)

__all__ = [
    "ArrivalQueue", "BudgetAutotuner",
    "ContentPrefixRegistry",
    "ContinuousEngine", "Event", "EventTrace", "FLEET_COUNTERS",
    "FleetReport", "FleetRouter", "HostPagePool",
    "Log2Histogram", "PageAllocator",
    "PassRow", "PrefixShareRegistry", "ROUTE_POLICIES", "RequestTimeline",
    "Scheduler",
    "ServeFleet", "ServeMetrics", "ServeRequest", "SimRequest", "StatePool",
    "TICK_MODES", "TickPlan",
    "TickRecord", "TickTimer", "TickTiming", "admission_cutoff",
    "bucket_pow2", "compare_policies", "content_key", "fleet_chrome_trace",
    "fleet_summary", "fold_counters",
    "fresh_lazy_needs", "host_pages_for_bytes", "kv_page_bytes",
    "page_nbytes",
    "paged_partition_specs", "paged_pool_shardings", "pages_for",
    "pages_for_pool_bytes", "pages_shard_count",
    "plan_swap_out",
    "pool_partition_specs", "pooled_cache_axes", "poisson_arrivals",
    "poisson_trace", "provision_growth", "resume_lazy_needs", "simulate",
    "simulate_fleet",
    "stream_page_needs", "to_chrome_trace", "victim_key",
    "write_chrome_trace",
]
