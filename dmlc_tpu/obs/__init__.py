"""dmlc_tpu.obs — unified observability: tracing, metrics, watchdog.

One place to see where time went and why a pull wedged, across the
Python and native layers (docs/observability.md):

- :mod:`~dmlc_tpu.obs.trace` — thread-aware span/instant/counter ring
  buffer, near-zero cost when off, mirrored onto the ``jax.profiler``
  timeline while one collects; the repo's ONE span API;
- :mod:`~dmlc_tpu.obs.export` — Chrome/Perfetto trace-event JSON
  export + gang trace merging;
- :mod:`~dmlc_tpu.obs.metrics` — counters/gauges/histograms plus the
  registered ``stats()`` surfaces, one versioned ``snapshot()``;
- :mod:`~dmlc_tpu.obs.watchdog` — stall detection over every
  instrumented wait, with a single diagnosis report (blocked stage,
  queue state, metrics snapshot, all-thread stacks);
- :mod:`~dmlc_tpu.obs.log` — the rate-limited, gang-deduplicated
  warn channel;
- :mod:`~dmlc_tpu.obs.serve` — the LIVE plane: per-rank in-process
  HTTP status server (/metrics Prometheus exposition, /healthz,
  /stacks, on-demand /trace capture) + gang scraping;
- :mod:`~dmlc_tpu.obs.flight` — the always-on crash flight recorder
  (small trace ring + periodic metrics, post-mortem bundle on
  uncaught exception, fatal signal, or watchdog-confirmed stall);
- :mod:`~dmlc_tpu.obs.timeseries` — the ANALYSIS substrate: a
  bounded, downsampling ring of periodic metric samples shared by
  /history, stall reports, and crash bundles;
- :mod:`~dmlc_tpu.obs.aggregate` — rank-0 gang aggregation onto one
  wall-anchored timeline (per-rank series, rollups, explicit
  unreachable-rank gaps; served at /gang);
- :mod:`~dmlc_tpu.obs.analyze` — bottleneck attribution (the
  structured bound verdict /analyze serves) and
  band-aware BENCH-to-BENCH regression comparison;
- :mod:`~dmlc_tpu.obs.profile` — the continuous sampling profiler:
  merged Python+native flamegraphs (sys._current_frames + the
  engine's phase beacons) in a byte-budgeted coarsening trie, served
  at /profile, attached to stall reports and crash bundles, and the
  ``hot_frames`` evidence in the analyze verdict.
"""

from dmlc_tpu.obs.aggregate import GangAggregator
from dmlc_tpu.obs.analyze import attribute, compare, gauge_band
from dmlc_tpu.obs.export import (
    chrome_events, merge_chrome_files, write_chrome,
)
from dmlc_tpu.obs.flight import FlightRecorder
from dmlc_tpu.obs.profile import FrameTrie, StackProfiler
from dmlc_tpu.obs.timeseries import TimeSeriesRing
from dmlc_tpu.obs.log import warn_limited, warn_once
from dmlc_tpu.obs.metrics import (
    METRICS_SCHEMA, REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
    merge_snapshots,
)
from dmlc_tpu.obs.serve import (
    StatusServer, render_prometheus, scrape_gang, serve,
)
from dmlc_tpu.obs.trace import (
    TraceRecorder, counter, instant, span, start, stop, trace_to,
)
from dmlc_tpu.obs.watchdog import Watchdog

__all__ = [
    "TraceRecorder", "span", "instant", "counter", "start", "stop",
    "trace_to",
    "chrome_events", "write_chrome", "merge_chrome_files",
    "MetricsRegistry", "REGISTRY", "Counter", "Gauge", "Histogram",
    "merge_snapshots", "METRICS_SCHEMA",
    "Watchdog", "warn_once", "warn_limited",
    "StatusServer", "serve", "render_prometheus", "scrape_gang",
    "FlightRecorder",
    "TimeSeriesRing", "GangAggregator",
    "attribute", "compare", "gauge_band",
    "StackProfiler", "FrameTrie",
]
