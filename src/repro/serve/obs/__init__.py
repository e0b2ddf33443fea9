"""Structured serve-stack observability (DESIGN.md §13).

Four pieces, consumed by ``ServeMetrics`` and the engine/simulator pair:

* :mod:`.trace` — typed event stream in a bounded ring buffer; counters
  are a fold over it and engine==sim is asserted event-for-event.
* :mod:`.hist` — fixed-bucket log2 histograms (TTFT/TPOT/queue-wait/
  tick-duration) with p50/p95/p99, SLO attainment, and merge.
* :mod:`.timing` — per-tick admit/schedule/step/finalize wall-time
  segments, mirrored into the JAX profiler's trace while one runs.
* :mod:`.chrome` — Chrome-trace (Perfetto) JSON export of the run.
"""

from repro.serve.obs.chrome import (fleet_chrome_trace, to_chrome_trace,
                                    write_chrome_trace)
from repro.serve.obs.hist import Log2Histogram, default_histograms
from repro.serve.obs.timing import TICK_SEGMENTS, TickTimer, TickTiming
from repro.serve.obs.trace import (EVENT_KINDS, FOLDED_COUNTERS, Event,
                                   EventTrace, fold_counters)

__all__ = [
    "EVENT_KINDS", "FOLDED_COUNTERS", "Event", "EventTrace",
    "fold_counters", "Log2Histogram", "default_histograms",
    "TICK_SEGMENTS", "TickTimer", "TickTiming",
    "fleet_chrome_trace", "to_chrome_trace", "write_chrome_trace",
]
