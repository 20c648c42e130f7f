"""raft_tpu.obs — the shared observability spine (ISSUE 10 + 11 + 15).

Five pillars, one seam across frontend -> router -> engine -> pool ->
trainer (docs/observability.md). The fifth is *trace
propagation*: a ``trace_id`` born at the HTTP front door rides the
dispatch path and the IPC wire (:class:`~raft_tpu.obs.trace
.TraceContext`), every process's spans are stitched back into ONE
clock-aligned trace (:meth:`~raft_tpu.obs.trace.Trace.absorb`), and
``scripts/postmortem.py --fleet`` renders the result as per-process
lanes.

  * **Request tracing** (:mod:`raft_tpu.obs.trace`) — low-overhead
    monotonic-clock spans per sampled request (admit, queue_wait,
    dispatch, fetch, pool refine, trainer window phases), carried as a
    ``trace_id`` on :class:`~raft_tpu.serve.ServeResult` and sampled via
    ``ServeConfig.trace_sample_rate``.
  * **Unified metrics** (:mod:`raft_tpu.obs.metrics`) — typed counters /
    gauges / fixed-bucket histograms every layer registers into; one
    snapshot feeding the existing ``stats()`` dicts, Prometheus text
    exposition, and the JSONL ``MetricLogger``.
  * **Flight recorder** (:mod:`raft_tpu.obs.recorder`) — a bounded ring
    of structured fault-ladder events plus the last-N completed traces,
    dumped as a postmortem bundle when a ``Watchdog`` trips, a replica
    is evicted, ``DivergenceError`` raises, or a page-severity alert
    fires (``scripts/postmortem.py`` reads the bundle back).
  * **Burn-rate alerting** (:mod:`raft_tpu.obs.alerts`, ISSUE 11) —
    multi-window burn-rate rules over registry snapshots (SLO miss
    fraction, shed, quarantine, watchdog trips, tier evictions);
    fire/resolve are flight-recorder events and page-severity rules
    auto-dump a postmortem.

:mod:`raft_tpu.obs.profile` additionally toggles ``jax.profiler`` trace
annotations around the dispatch windows, and keeps the pool scheduler's
phase totals (:class:`~raft_tpu.obs.profile.PhaseTable`): every phase of
its loop — a wait on the device (``WAIT_PHASES`` in ``serve/engine.py``)
or host work, never both — timed and counted at full pace, sampled or
not, as ``engine.stats()["sched"]`` and the ``sched`` counter group in
Prometheus. Device time itself is the device trace's to give
(``jax.profiler``; the benchmark's ``device_trace`` metrics).
"""

from raft_tpu.obs import profile
from raft_tpu.obs.alerts import (
    AlertEngine,
    AlertRule,
    rate,
    ratio_rate,
)
from raft_tpu.obs.metrics import (
    LATENCY_BUCKETS_MS,
    RESIDUAL_BUCKETS,
    Counter,
    CounterGroup,
    Gauge,
    Histogram,
    MetricsRegistry,
    relabel_prometheus,
)
from raft_tpu.obs.recorder import (
    SCHEMA,
    FlightRecorder,
    file_sink,
    logger_sink,
    validate_bundle,
)
from raft_tpu.obs.trace import Trace, TraceContext, Tracer, dedupe_traces

__all__ = [
    "Trace",
    "TraceContext",
    "Tracer",
    "dedupe_traces",
    "relabel_prometheus",
    "Counter",
    "CounterGroup",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "LATENCY_BUCKETS_MS",
    "RESIDUAL_BUCKETS",
    "AlertEngine",
    "AlertRule",
    "rate",
    "ratio_rate",
    "FlightRecorder",
    "SCHEMA",
    "file_sink",
    "logger_sink",
    "validate_bundle",
    "profile",
]
