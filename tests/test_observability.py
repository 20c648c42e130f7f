"""Observability spine (ISSUE 10): tracing, metrics registry, flight
recorder, postmortem bundles.

Three layers of coverage:

* **Unit** — the obs primitives in isolation: deterministic trace
  sampling, bounded rings, histogram/Prometheus exposition, MetricLogger
  shutdown hardening, Watchdog dump-on-trip, stability-ladder events.
* **Schema pins** — the nested ``stats()`` / ``health()`` key sets for
  engine (pool AND fallback mode) and router are snapshotted as
  constants; silent drift (a renamed counter, a dropped block) fails
  here before it breaks dashboards or `serve_bench` report parsing.
* **Chaos** — the acceptance scenario: a replica killed mid-flood with
  tracing enabled must produce a postmortem bundle containing the
  eviction event, the re-routed requests' traces, and the drain phase
  events; plus the tracing-overhead A/B (off vs 1.0) bounded at < 5%.
"""

import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from raft_tpu.obs import (
    DEVICE_TIME_BUCKETS_MS,
    AlertEngine,
    AlertRule,
    DeviceTimeLedger,
    FlightRecorder,
    MetricsRegistry,
    Tracer,
    file_sink,
    logger_sink,
    rate,
    validate_bundle,
)
from raft_tpu.serve import (
    Overloaded,
    ReplicaState,
    RouterConfig,
    ServeConfig,
    ServeEngine,
    ServeError,
    ServeRouter,
)


def _tiny_model():
    from raft_tpu.models import RAFT_SMALL, build_raft, init_variables
    from raft_tpu.models.corr import CorrBlock

    cfg = RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,),
        motion_flow_widths=(16, 8),
        motion_out_channels=20,
        gru_hidden=24,
        flow_head_hidden=16,
        corr_levels=2,
    )
    model = build_raft(cfg, corr_block=CorrBlock(num_levels=2, radius=3))
    return model, init_variables(model)


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


# NOTE: no persistent-compile-cache fixture here, deliberately. This
# module sorts BEFORE tests/test_serve_aot.py, and wiring the
# process-global cache would change that module's save_artifact
# behavior (it bypasses executable reuse under a live cache dir by
# design). The shared warmup artifact below amortizes this module's
# compiles instead.


def _config(**kw):
    # the fallback whole-request engine keeps per-engine compiles small
    # (mirrors tests/test_serve_router._config)
    base = dict(
        buckets=((48, 64),),
        ladder=(2, 1),
        max_batch=2,
        pool_capacity=0,
        queue_capacity=8,
        max_wait_ms=4.0,
        default_deadline_ms=30000.0,
        cooldown_batches=1,
        recover_after=1,
        high_watermark=0.5,
        low_watermark=0.25,
        drain_retry_after_ms=50.0,
    )
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def shared_artifact(tiny_model, tmp_path_factory):
    """ONE warmup artifact shared by every engine in this module, so the
    chaos/overhead tests measure serving + observability, not compiles."""
    from raft_tpu.serve import aot

    model, variables = tiny_model
    path = str(tmp_path_factory.mktemp("obs_aot") / "shared.raftaot")
    builder = ServeEngine(model, variables, _config())
    aot.save_artifact(builder, path)
    return path


def _image(rng, hw=(45, 60)):
    return rng.integers(0, 255, (*hw, 3), dtype=np.uint8)


def _engine(tiny_model, artifact=None, **kw):
    model, variables = tiny_model
    if artifact is not None:
        kw.setdefault("warmup", True)
        kw.setdefault("warmup_artifact", artifact)
    return ServeEngine(model, variables, _config(**kw))


@pytest.fixture(scope="module")
def pool_engine(tiny_model):
    """ONE running pool-mode engine (ledger K=1, tracing on) shared by
    the convergence + ledger tests below — pool programs compile once
    for the module, not once per test."""
    model, variables = tiny_model
    eng = ServeEngine(
        model, variables,
        _config(
            pool_capacity=2, stream_cache_size=0,
            trace_sample_rate=1.0, ledger_sample_every=1,
        ),
    )
    eng.start()
    yield eng
    eng.stop()


def _router(tiny_model, n=2, router_kw=None, artifact=None, **cfg_kw):
    model, variables = tiny_model
    if artifact is not None:
        cfg_kw.setdefault("warmup", True)
        cfg_kw.setdefault("warmup_artifact", artifact)
    scfg = _config(**cfg_kw)

    def factory(**overrides):
        return ServeEngine(
            model, variables,
            dataclasses.replace(scfg, **overrides) if overrides else scfg,
        )

    rkw = dict(
        heartbeat_interval_s=0.05, heartbeat_timeout_s=1.0, cooldown_s=0.5,
    )
    rkw.update(router_kw or {})
    return ServeRouter.from_factory(factory, n, RouterConfig(**rkw))


# ---------------------------------------------------------------------------
# Tracing primitives
# ---------------------------------------------------------------------------


class TestTracer:
    def test_sampling_is_deterministic_and_proportional(self):
        for rate, expect in ((0.0, 0), (0.25, 25), (1.0, 100)):
            t = Tracer(rate)
            n = sum(1 for i in range(100) if t.start("pair", i) is not None)
            assert n == expect, (rate, n)

    def test_zero_rate_never_allocates(self):
        t = Tracer(0.0)
        assert t.start("pair", 1) is None
        assert t.started == 0 and t.finished == 0

    def test_ring_is_bounded(self):
        t = Tracer(1.0, capacity=4)
        for i in range(10):
            t.start("pair", i).finish()
        snap = t.snapshot()
        assert len(snap) == 4
        assert [r["rid"] for r in snap] == [6, 7, 8, 9]  # newest survive
        assert t.finished == 10

    def test_span_timeline_and_meta(self):
        t = Tracer(1.0)
        t0 = time.monotonic()
        tr = t.start("pair", 7, t_start=t0)
        tr.add_span("admit", t0, t0 + 0.001)
        tr.add_span("queue_wait", t0 + 0.001, t0 + 0.003)
        tr.annotate(bucket="48x64")
        rec = tr.finish(ok=True, level=1)
        assert rec["trace_id"].startswith("t-")
        assert rec["bucket"] == "48x64" and rec["level"] == 1
        names = [s["name"] for s in rec["spans"]]
        assert names == ["admit", "queue_wait"]
        # spans are relative to the trace start: a readable timeline
        assert rec["spans"][0]["t0_ms"] == pytest.approx(0.0, abs=1e-6)
        assert rec["spans"][1]["t0_ms"] == pytest.approx(1.0, rel=0.01)
        assert rec["spans"][1]["dur_ms"] == pytest.approx(2.0, rel=0.01)

    def test_finish_is_set_once(self):
        t = Tracer(1.0)
        tr = t.start("pair", 1)
        assert tr.finish(ok=True) is not None
        assert tr.finish(ok=False, error="late") is None
        assert t.snapshot()[-1]["ok"] is True
        tr.add_span("late", time.monotonic())  # no-op after finish
        assert t.snapshot()[-1]["spans"] == []

    def test_validation(self):
        with pytest.raises(ValueError):
            Tracer(1.5)
        with pytest.raises(ValueError):
            Tracer(0.5, capacity=0)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_group_is_a_dict_drop_in(self):
        reg = MetricsRegistry("serve")
        g = reg.counter_group("counters", ("a", "b"))
        g["a"] += 3
        g["b"] = 7
        assert dict(g) == {"a": 3, "b": 7}
        assert sorted(g.items()) == [("a", 3), ("b", 7)]
        snap = reg.snapshot()
        assert snap["serve/counters/a"] == 3
        assert snap["serve/counters/b"] == 7

    def test_gauge_callback_and_histogram(self):
        reg = MetricsRegistry()
        box = {"v": 2}
        reg.gauge("depth", lambda: box["v"])
        h = reg.histogram("latency_ms")
        for v in (3.0, 9.0, 40.0, 900.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["depth"] == 2
        assert snap["latency_ms_count"] == 4
        assert snap["latency_ms_sum"] == pytest.approx(952.0)
        assert snap["latency_ms_p50"] >= 9.0
        # a broken gauge probe must not break the snapshot
        reg.gauge("broken", lambda: 1 / 0)
        assert np.isnan(reg.snapshot()["broken"])

    def test_prometheus_exposition(self):
        reg = MetricsRegistry("serve")
        reg.counter("boots", help="engine boots").inc()
        g = reg.counter_group("counters", ("shed",))
        g["shed"] += 2
        reg.histogram("latency_ms", bounds=(10.0, 100.0)).observe(42.0)
        text = reg.prometheus_text()
        assert "# TYPE serve_boots counter" in text
        assert "serve_boots 1" in text
        assert 'serve_counters{key="shed"} 2' in text
        assert 'serve_latency_ms_bucket{le="100"} 1' in text
        assert 'serve_latency_ms_bucket{le="+Inf"} 1' in text
        assert "serve_latency_ms_count 1" in text

    def test_log_to_metric_logger(self, tmp_path):
        from raft_tpu.utils.logging import MetricLogger

        reg = MetricsRegistry("x")
        reg.counter("n").inc(5)
        with MetricLogger(str(tmp_path), tensorboard=False) as logger:
            reg.log_to(logger, step=3)
        rec = json.loads((tmp_path / "scalars.jsonl").read_text())
        assert rec["step"] == 3 and rec["x/n"] == 5.0

    def test_histogram_validation(self):
        with pytest.raises(ValueError):
            MetricsRegistry().histogram("h", bounds=(5.0, 1.0))


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_event_ring_bounds(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("shed", rid=i)
        evs = rec.events()
        assert len(evs) == 4
        assert [e["rid"] for e in evs] == [6, 7, 8, 9]
        assert rec.events_recorded == 10

    def test_trace_ring_bounds(self):
        rec = FlightRecorder(trace_capacity=2)
        for i in range(5):
            rec.add_trace({"trace_id": f"t{i}", "kind": "pair",
                           "spans": [], "dur_ms": 1.0})
        assert [t["trace_id"] for t in rec.traces()] == ["t3", "t4"]

    def test_dump_bundle_content_and_schema(self):
        rec = FlightRecorder()
        rec.record("evict", replica="r1", reason="test")
        rec.add_trace({"trace_id": "t0", "kind": "pair", "rid": 0,
                       "spans": [{"name": "admit", "t0_ms": 0.0,
                                  "dur_ms": 0.1}], "dur_ms": 5.0})
        b = rec.dump("evict:r1", extra={"note": "unit"})
        assert b["reason"] == "evict:r1"
        assert b["extra"]["note"] == "unit"
        assert [e["kind"] for e in b["events"]] == ["evict"]
        assert validate_bundle(b) == []
        assert rec.last_bundle is b and rec.dumps == 1
        # bundles are JSON-able end to end
        assert validate_bundle(json.loads(json.dumps(b, default=repr))) == []

    def test_broken_sink_never_raises(self):
        rec = FlightRecorder()
        rec.add_sink(lambda bundle: 1 / 0)
        got = []
        rec.add_sink(got.append)
        b = rec.dump("x")
        assert got == [b]  # later sinks still fire

    def test_file_sink_writes_and_bounds(self, tmp_path):
        rec = FlightRecorder()
        rec.add_sink(file_sink(str(tmp_path), keep=2))
        for i in range(3):
            rec.record("shed", rid=i)
            rec.dump(f"dump{i}")
        files = sorted(p.name for p in tmp_path.glob("postmortem_*.json"))
        assert len(files) == 2 and files[-1].startswith("postmortem_0002")
        loaded = json.loads((tmp_path / files[-1]).read_text())
        assert validate_bundle(loaded) == []

    def test_validate_bundle_rejects_malformed(self):
        assert validate_bundle([]) != []
        assert any("schema" in p for p in validate_bundle({"schema": "v0"}))
        good = FlightRecorder().dump("x")
        bad = dict(good)
        bad.pop("events")
        assert any("events" in p for p in validate_bundle(bad))
        bad2 = dict(good, traces=[{"kind": "pair"}])
        assert any("trace_id" in p for p in validate_bundle(bad2))

    def test_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# MetricLogger hardening (satellite)
# ---------------------------------------------------------------------------


class TestMetricLoggerHardening:
    def test_log_after_close_is_counted_noop(self, tmp_path):
        from raft_tpu.utils.logging import MetricLogger

        logger = MetricLogger(str(tmp_path), tensorboard=False)
        logger.log(1, {"a": 1.0})
        logger.close()
        # the shutdown race: the serve worker logs while the owner closes
        logger.log(2, {"a": 2.0})          # must not raise
        logger.log_event({"kind": "late"})  # must not raise
        assert logger.dropped_records == 2
        logger.close()  # idempotent
        lines = (tmp_path / "scalars.jsonl").read_text().splitlines()
        assert len(lines) == 1

    def test_log_event_structured_records(self, tmp_path):
        from raft_tpu.utils.logging import MetricLogger

        with MetricLogger(str(tmp_path), tensorboard=False) as logger:
            logger.log_event(
                {"kind": "postmortem", "bundle": {"events": [{"k": 1}]}}
            )
        rec = json.loads((tmp_path / "events.jsonl").read_text())
        assert rec["kind"] == "postmortem"
        assert rec["bundle"]["events"] == [{"k": 1}]
        assert "time" in rec

    def test_no_events_file_without_events(self, tmp_path):
        from raft_tpu.utils.logging import MetricLogger

        with MetricLogger(str(tmp_path), tensorboard=False) as logger:
            logger.log(1, {"a": 1.0})
        assert not (tmp_path / "events.jsonl").exists()

    def test_logger_sink_drops_after_close(self, tmp_path):
        from raft_tpu.utils.logging import MetricLogger

        logger = MetricLogger(str(tmp_path), tensorboard=False)
        rec = FlightRecorder()
        rec.add_sink(logger_sink(logger))
        rec.dump("before")
        logger.close()
        rec.dump("after")  # dropped, not raised
        assert logger.dropped_records == 1
        lines = (tmp_path / "events.jsonl").read_text().splitlines()
        assert len(lines) == 1


# ---------------------------------------------------------------------------
# Watchdog dump-on-trip (flight-recorder wiring in utils/faults.py)
# ---------------------------------------------------------------------------


class TestWatchdogDump:
    def test_trip_records_event_and_dumps_bundle(self, tmp_path):
        from raft_tpu.utils.faults import Watchdog

        rec = FlightRecorder()
        rec.record("shed", rid=1)  # pre-trip context must ride the bundle
        fired = []
        wd = Watchdog(
            0.25, dump_path=str(tmp_path / "stalls.log"),
            install_handler=False, recorder=rec,
        )
        try:
            with wd.section("serve/apply", on_timeout=fired.append):
                time.sleep(1.0)
        finally:
            wd.close()
        assert fired == ["serve/apply"]
        trips = rec.events("watchdog_trip")
        assert len(trips) == 1 and trips[0]["section"] == "serve/apply"
        b = rec.last_bundle
        assert b is not None and b["reason"] == "watchdog_trip:serve/apply"
        assert validate_bundle(b) == []
        kinds = [e["kind"] for e in b["events"]]
        assert kinds == ["shed", "watchdog_trip"]  # context + the trip


# ---------------------------------------------------------------------------
# Stability ladder events + divergence dump (train/stability.py wiring)
# ---------------------------------------------------------------------------


class TestStabilityRecorder:
    def test_skip_windows_and_rollbacks_become_events(self):
        from raft_tpu.train.stability import (
            StabilityMonitor, StabilityPolicy,
        )

        rec = FlightRecorder()
        mon = StabilityMonitor(
            StabilityPolicy(skip_budget=2, max_rollbacks=2), recorder=rec,
        )
        assert not mon.breached(1)
        assert mon.breached(5)
        kinds = [e["kind"] for e in rec.events()]
        assert kinds == ["nan_skip_window", "skip_budget_breach"]
        mon.record_rollback(100, 50, 5)
        ev = rec.events("rollback")[0]
        assert ev["at_step"] == 100 and ev["to_step"] == 50

    def test_divergence_death_dumps_postmortem(self):
        from raft_tpu.train.stability import (
            DivergenceError, StabilityMonitor, StabilityPolicy,
        )

        rec = FlightRecorder()
        mon = StabilityMonitor(
            StabilityPolicy(skip_budget=0, max_rollbacks=0), recorder=rec,
        )
        with pytest.raises(DivergenceError):
            mon.check_escalation(10, 3)
        b = rec.last_bundle
        assert b is not None and b["reason"] == "divergence"
        assert validate_bundle(b) == []
        assert rec.events("divergence_death")


# ---------------------------------------------------------------------------
# stats()/health() schema pins (satellite): silent drift fails here
# ---------------------------------------------------------------------------

ENGINE_STATS_KEYS = frozenset({
    "alerts", "batch_ladder", "batches", "boot", "completed",
    "convergence", "degradation",
    "dispatched_rows", "dispatched_slot_iters", "drained",
    "early_exit_iters_saved", "early_exit_iters_saved_converged",
    "early_exit_iters_saved_deadline", "early_exits_converged",
    "early_exits_deadline", "encode_cache_hits",
    "encode_cache_misses", "encoder_cache_hit_rate", "expired",
    # PR 30: host bytes the pool's retirements fetched (beside completed)
    "fetched_bytes",
    # PR 37: retirements read after a later tick's dispatch, and of those
    # the ones whose arrays were all on the host when read
    "retire_deferred", "retire_ready_at_settle",
    "idle_slot_iters", "inflight_peak", "invalid", "latency", "ledger",
    "mesh_devices", "nonfinite_batches", "obs", "padded_rows",
    "padding_waste", "pool", "pool_admitted", "pool_resets", "pool_ticks",
    "programs", "qos", "quarantined", "quarantined_rids", "queue_depth",
    "rejected", "retried_singles", "shed", "shed_slow_path", "slow_path",
    # ISSUE 18: shadow_* are the mirrored-traffic twin counters (shadow
    # submits land here INSTEAD of the live counters above, so QoS and
    # the autoscaler never see them); variables_hash is the serving
    # weights identity (the aot fingerprint field, now first-class)
    "shadow_completed", "shadow_expired", "shadow_shed", "shadow_submitted",
    "stream_evictions", "stream_invalidations", "stream_primes",
    "stream_warm_starts", "submitted", "variables_hash", "watchdog_trips",
    "worker_errors",
    # PR 36: the device-resident session cache — frames admitted through
    # it, sessions remembered, bytes of table rows they hold
    "stream_frames", "stream_sessions", "stream_cache_bytes",
    # ISSUE 20: the waste-aware tile fan-out block (envelope-level
    # tiled-request accounting; schema pinned by TILER_STATS_KEYS)
    "tiler",
})
# ISSUE 20: stats()['tiler'] — the degraded-but-served rung's ledger.
# admission_acquisitions counts put_many lock acquisitions attributable
# to tiled fan-outs: on a clean run it equals `requests` (the one-batch
# admission pin, asserted live in tests/test_serve_zzzzz_tiler.py).
TILER_STATS_KEYS = frozenset({
    "enabled", "overlap_px", "plans_built", "plan_cache_hits",
    "requests", "completed", "failures", "tiles_submitted",
    "tiles_retried", "admission_acquisitions", "waste_frac", "blend_ms",
})
ENGINE_LEDGER_KEYS = frozenset({
    "by_family", "est_total_device_ms", "families", "sample_every",
    "sampled_dispatches",
})
ENGINE_ALERTS_KEYS = frozenset({"active", "fired", "resolved", "rules"})
ENGINE_CONVERGENCE_KEYS = frozenset({
    "enabled", "final_residual_p50", "final_residual_p99", "n",
    "resid_by_iter", "streak", "threshold", "warm_start",
})
ENGINE_DEGRADATION_KEYS = frozenset({
    "ladder", "level", "num_flow_updates", "occupancy", "steps_down",
    "steps_up", "transitions",
})
ENGINE_BOOT_KEYS = frozenset({
    "artifact_error", "backend_compiles", "boot_to_ready_ms",
    "programs_compiled", "programs_loaded", "programs_total", "smoke_runs",
    "source",
})
ENGINE_POOL_KEYS = frozenset({
    # PR 30: "buckets" = per live bucket slot_bytes / state_bytes /
    # query_tile / coords_blocked; PR 34: level_rows / window_rows /
    # lookup_rows_read / lookup_rows_whole (pool.state_layout)
    "buckets", "capacity", "mesh_devices", "occupancy", "occupied",
    "per_device_occupancy", "tick_ms_ewma", "ticks", "ttfd_p50_ms",
})
ENGINE_OBS_KEYS = frozenset({
    "events_recorded", "postmortem_dumps", "trace_sample_rate",
    "traces_dropped", "traces_finished", "traces_started",
})
ENGINE_HEALTH_KEYS = frozenset({
    "draining", "healthy", "level", "num_flow_updates", "quarantined",
    "queue_capacity", "queue_depth", "ready", "watchdog_trips",
})
ROUTER_STATS_KEYS = frozenset({
    "aggregate", "alerts", "autoscaler", "engines", "obs", "qos",
    "replica_count", "replicas", "rollout", "router",
})
ROUTER_COUNTER_KEYS = frozenset({
    "canary_routed", "completed", "drains", "evictions",
    "heartbeat_misses", "mirror_shed", "mirrored",
    "no_healthy_replicas", "readmissions", "rerouted", "restarts",
    "routed", "shed_all_replicas", "stream_remaps", "streams_opened",
    # ISSUE 20: whole-plan affinity dispatches vs per-tile spills
    "tiled_fanout", "tiled_routed",
})
ROUTER_OBS_KEYS = frozenset({"events_recorded", "postmortem_dumps"})
REPLICA_SNAPSHOT_KEYS = frozenset({
    # backend/pid: the process-per-replica seam (ISSUE 13) — pid is None
    # for thread replicas, the worker's real OS pid for process replicas;
    # endpoint: the remote seam (ISSUE 16) — host:port for remote
    # replicas, None for anything in-machine
    "backend", "cooldown_remaining_s", "deadline_misses", "dispatched",
    "endpoint", "error_rate", "errors", "evictions", "generation",
    "heartbeat_age_s", "inflight", "last_evict_reason", "pid",
    "sheds_by_class", "state", "variables_hash",
})
ROUTER_HEALTH_KEYS = frozenset({
    "healthy", "healthy_count", "ready", "replica_count", "replicas",
})
# ISSUE 14: a ProcessEngineClient's stats() is the worker engine's tree
# (byte-identical keys to a thread engine) PLUS this one parent-side
# "transport" block — the cross-process tax ledger (negotiated codec,
# coalescer write stats, ring copy counters, health-cache hits/misses,
# pack/ring_wait/rpc/unpack span quantiles). Pinned here with the rest
# of the schema contract; asserted against a live worker in
# tests/test_serve_xport.py.
PROCESS_TRANSPORT_KEYS = frozenset({
    "transport", "health_ttl_s", "health_cache_hits",
    "health_cache_misses", "sender", "msgs_received", "frames_received",
    "bytes_received", "rings", "spans",
    # ISSUE 15: trace propagation negotiation + the handshake-estimated
    # cross-process clock offset (stitching error bound = rtt/2)
    "trace_propagation", "clock_offset_ms", "clock_rtt_ms",
    # ISSUE 17: QoS class/tenant propagation, negotiated the same way
    "qos_propagation",
})
PROCESS_TRANSPORT_SPAN_KEYS = frozenset({
    "pack", "ring_wait", "rpc", "unpack",
})
# ISSUE 15: the frontend stats block (/statz "frontend" key), the
# decision-grade autoscaler block (stats()['autoscaler'] when attached),
# and the stitched-trace record contract.
FRONTEND_STATS_KEYS = frozenset({
    "http_requests", "http_completed", "http_errors", "http_quota_refused",
    "http_shed", "http_slo_miss", "http_streams_opened", "max_inflight",
    "open_streams", "edge_latency", "alerts", "tracing",
    # ISSUE 19: the async-edge block and the (always-present, zeroed
    # when off) redundancy-layer block
    "edge", "edge_cache",
})
FRONTEND_EDGE_LATENCY_KEYS = frozenset({"n", "p50_ms", "p99_ms"})
FRONTEND_TRACING_KEYS = frozenset({"sample_rate", "started", "finished"})
AUTOSCALER_STATS_KEYS = frozenset({
    "attached", "actions", "min_replicas", "max_replicas", "evaluations",
    "scale_ups", "scale_downs", "up_streak", "down_streak",
    "cooldown_remaining_s", "last_decision",
})
# a finished trace record (stitched or not): the keys every consumer —
# postmortem --fleet, serve_phase_breakdown, dashboards — relies on
TRACE_RECORD_KEYS = frozenset({
    "trace_id", "kind", "rid", "t_start", "wall_start", "dur_ms", "ok",
    "error", "spans",
})
TRACE_SPAN_BASE_KEYS = frozenset({"name", "t0_ms", "dur_ms"})
# the pool scheduler's loop record (docs/observability.md section 1):
# what the loop did; PR 37 adds `deferred`, the retirements it read
# after its tick was dispatched
SCHED_RECORD_KEYS = TRACE_RECORD_KEYS | frozenset({
    "ticked", "occupied", "pending", "admitted", "retired",
    "admitted_rids", "retired_rids", "deferred", "cpu_ms",
})
# ISSUE 17: the QoS block every engine stats() carries (and the router
# aggregates): per-class counters + the policy's per-tenant view. The
# per-class value dict is pinned in tests/test_serve_zzz_qos.py next to
# the behavior it counts.
QOS_STATS_KEYS = frozenset({"enabled", "aging_ms", "classes", "tenants"})
ROUTER_QOS_KEYS = frozenset({
    "enabled", "shed_all_replicas", "classes", "tenants",
})
# ISSUE 18: the rollout block (router.stats()['rollout'], /statz). With
# no candidate ever added it is exactly {"active": False}; with one, the
# full ladder view below (asserted live in tests/test_serve_zzz_rollout
# .py next to the behavior it reports).
ROLLOUT_STATS_KEYS = frozenset({
    "active", "stage", "abort_reason", "stage_history", "candidate",
    "overrides", "mirrored", "mirror_shed", "mirror_errors",
    "canary_routed", "canary_errors", "promoted_replicas", "rollbacks",
    "gate",
})
ROLLOUT_GATE_KEYS = frozenset({"ready", "breach", "short", "long"})
ROLLOUT_GATE_METRIC_KEYS = frozenset({
    "samples", "flow_mean_px", "flow_p99_px", "latency_ratio",
    "iters_delta", "error_rate",
})


class TestStatsSchemaPin:
    """The dashboards-and-tooling contract: these exact key sets. A new
    key is a deliberate schema change — update the pin in the same PR
    that documents it; a missing key is a regression."""

    @pytest.mark.parametrize("pool_capacity", [0, 2],
                             ids=["fallback", "pool"])
    def test_engine_schema(self, tiny_model, pool_capacity):
        # unstarted engines have the full stats()/health() shape and
        # compile nothing, so the pin stays cheap
        eng = _engine(tiny_model, pool_capacity=pool_capacity)
        stats = eng.stats()
        assert frozenset(stats) == ENGINE_STATS_KEYS
        assert frozenset(stats["degradation"]) == ENGINE_DEGRADATION_KEYS
        assert frozenset(stats["boot"]) == ENGINE_BOOT_KEYS
        assert frozenset(stats["pool"]) == ENGINE_POOL_KEYS
        assert frozenset(stats["obs"]) == ENGINE_OBS_KEYS
        assert frozenset(stats["ledger"]) == ENGINE_LEDGER_KEYS
        assert frozenset(stats["alerts"]) == ENGINE_ALERTS_KEYS
        assert frozenset(stats["convergence"]) == ENGINE_CONVERGENCE_KEYS
        assert stats["convergence"]["enabled"] is (pool_capacity > 0)
        assert frozenset(stats["qos"]) == QOS_STATS_KEYS
        assert stats["qos"]["enabled"] is False  # default-off contract
        assert frozenset(stats["tiler"]) == TILER_STATS_KEYS
        assert stats["tiler"]["enabled"] is False  # default stays reject
        assert frozenset(eng.health()) == ENGINE_HEALTH_KEYS

    def test_router_schema(self, tiny_model):
        router = _router(tiny_model, n=2)
        for rep in router.replicas:
            rep.build()  # engines exist (unstarted): full stats shape
        stats = router.stats()
        assert frozenset(stats) == ROUTER_STATS_KEYS
        assert frozenset(stats["router"]) == ROUTER_COUNTER_KEYS
        assert frozenset(stats["obs"]) == ROUTER_OBS_KEYS
        assert frozenset(stats["alerts"]) == ENGINE_ALERTS_KEYS
        # the autoscaler block is ALWAYS present; unattached tiers
        # report exactly {"attached": False} (ISSUE 15)
        assert stats["autoscaler"] == {"attached": False}
        assert frozenset(stats["qos"]) == ROUTER_QOS_KEYS
        assert stats["qos"]["enabled"] is False  # default-off contract
        # the rollout block is ALWAYS present; with no candidate it is
        # exactly {"active": False} (ISSUE 18 default-off contract)
        assert stats["rollout"] == {"active": False}
        for snap in stats["replicas"].values():
            assert frozenset(snap) == REPLICA_SNAPSHOT_KEYS
        for eng_stats in stats["engines"].values():
            assert frozenset(eng_stats) == ENGINE_STATS_KEYS
        health = router.health()
        assert frozenset(health) == ROUTER_HEALTH_KEYS
        for snap in health["replicas"].values():
            assert frozenset(snap) == REPLICA_SNAPSHOT_KEYS | {"ring"}

    def test_frontend_schema(self, tiny_model):
        # the frontend block is pure bookkeeping: pinnable without
        # starting the HTTP server or the tier
        from raft_tpu.serve import ServeFrontend

        fe = ServeFrontend(_engine(tiny_model), trace_sample_rate=0.5)
        snap = fe.snapshot()
        assert frozenset(snap) == FRONTEND_STATS_KEYS
        # 'tiled' is its own edge class (ISSUE 20): the degraded-but-
        # served rung gets a separately tracked edge SLO
        assert frozenset(snap["edge_latency"]) == {"pair", "stream", "tiled"}
        for cls_q in snap["edge_latency"].values():
            assert frozenset(cls_q) == FRONTEND_EDGE_LATENCY_KEYS
        assert frozenset(snap["alerts"]) == ENGINE_ALERTS_KEYS
        assert frozenset(snap["tracing"]) == FRONTEND_TRACING_KEYS
        assert snap["alerts"]["rules"] == ["slo_burn"]
        assert snap["tracing"]["sample_rate"] == 0.5

    def test_autoscaler_block_schema(self):
        from raft_tpu.serve import AutoscaleConfig, Autoscaler

        class _StubRouter:
            replicas = []

            def attach_autoscaler(self, a):
                self._a = a

            def stats(self):
                return {"aggregate": {}}

            def health(self):
                return {"healthy_count": 1, "replica_count": 1}

        router = _StubRouter()
        scaler = Autoscaler(router, AutoscaleConfig(min_replicas=1,
                                                    max_replicas=2))
        decision = scaler.evaluate_once()
        assert {"action", "reason", "signals", "t",
                "up_streak", "down_streak"} <= frozenset(decision)
        snap = scaler.snapshot()
        assert frozenset(snap) == AUTOSCALER_STATS_KEYS
        assert snap["attached"] is True
        # explain(): EVERY evaluation in full, not just actions
        ex = scaler.explain()
        assert len(ex) == 1 and ex[0]["action"] in ("up", "down", "hold")
        assert "signals" in ex[0] and "up_streak" in ex[0]

    def test_trace_record_schema(self):
        tracer = Tracer(1.0)
        tr = tracer.start("http", rid=1)
        tr.add_span("http_read", time.monotonic())
        tr.absorb(
            {"trace_id": tr.trace_id, "t_start": time.monotonic(),
             "spans": [{"name": "admit", "t0_ms": 0.0, "dur_ms": 0.1}]},
            proc="worker-1",
        )
        rec = tr.finish(ok=True)
        assert frozenset(rec) == TRACE_RECORD_KEYS
        for sp in rec["spans"]:
            assert TRACE_SPAN_BASE_KEYS <= frozenset(sp)
        assert rec["spans"][1]["proc"] == "worker-1"

    def test_sched_record_schema(self, pool_engine):
        rng = np.random.default_rng(3)
        reqs = pool_engine.submit_many(
            [{"image1": _image(rng), "image2": _image(rng)} for _ in range(3)]
        )
        for r in reqs:
            assert r.wait(120.0) and r.error is None, r.error
        time.sleep(0.2)  # the last loop's record closes one poll later
        recs = [r for r in pool_engine.tracer.snapshot()
                if r["kind"] == "sched"]
        assert recs
        for rec in recs:
            assert frozenset(rec) == SCHED_RECORD_KEYS
        # a retirement settled after its loop's tick says so
        assert any(r["deferred"] for r in recs if r["retired"] and r["ticked"])


# ---------------------------------------------------------------------------
# Engine tracing end to end (chaos: real engines)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestEngineTracing:
    def test_fallback_trace_spans_and_trace_id(
        self, tiny_model, shared_artifact, rng
    ):
        with _engine(
            tiny_model, artifact=shared_artifact, trace_sample_rate=1.0
        ) as eng:
            res = eng.submit(_image(rng), _image(rng))
            assert res.trace_id is not None
            recs = eng.tracer.snapshot()
            rec = next(r for r in recs if r["trace_id"] == res.trace_id)
            names = [s["name"] for s in rec["spans"]]
            # the full request path, in order
            for phase in ("admit", "queue_wait", "batch_form", "dispatch",
                          "fetch"):
                assert phase in names, names
            assert names.index("admit") < names.index("queue_wait") < (
                names.index("dispatch")
            )
            assert rec["ok"] is True
            assert rec["bucket"] == "48x64"
            assert rec["dur_ms"] == pytest.approx(res.latency_ms, rel=0.5)
            # the flight recorder keeps the last-N completed traces
            assert any(
                t["trace_id"] == res.trace_id
                for t in eng.recorder.traces()
            )
            # live engine counters reach the Prometheus surface
            assert 'serve_counters{key="completed"} 1' in eng.prometheus()

    def test_pool_trace_has_refine_span(
        self, tiny_model, shared_artifact, rng
    ):
        # pool-mode programs are not in the fallback artifact: warm off
        with _engine(
            tiny_model, pool_capacity=2, trace_sample_rate=1.0
        ) as eng:
            res = eng.submit(_image(rng), _image(rng))
            rec = next(
                r for r in eng.tracer.snapshot()
                if r["trace_id"] == res.trace_id
            )
            names = [s["name"] for s in rec["spans"]]
            for phase in ("admit", "queue_wait", "dispatch", "refine",
                          "fetch"):
                assert phase in names, names
            refine = next(s for s in rec["spans"] if s["name"] == "refine")
            assert refine["iters"] == res.num_flow_updates

    def test_tracing_off_is_off(self, tiny_model, shared_artifact, rng):
        with _engine(tiny_model, artifact=shared_artifact) as eng:
            res = eng.submit(_image(rng), _image(rng))
            assert res.trace_id is None
            assert eng.tracer.snapshot() == []
            assert eng.stats()["obs"]["traces_started"] == 0

    def test_shed_is_recorded_and_finishes_trace(self, tiny_model, rng):
        # no worker: the queue fills, then sheds — tracing must seal the
        # shed request's trace and the recorder must see the event
        eng = _engine(tiny_model, queue_capacity=1, trace_sample_rate=1.0)
        eng._ready.set()  # admit without a worker thread
        im = _image(rng)
        t = threading.Thread(
            target=lambda: pytest.raises(Exception, eng.submit, im, im)
        )
        t.daemon = True
        t.start()
        deadline = time.monotonic() + 5.0
        while eng._queue.depth() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(Overloaded):
            eng.submit(im, im)
        assert eng.recorder.events("shed")
        shed_traces = [
            r for r in eng.tracer.snapshot() if r.get("error") == "Overloaded"
        ]
        assert len(shed_traces) == 1 and shed_traces[0]["ok"] is False
        eng._stop.set()
        for r in eng._queue.close():
            r.finish(error=ServeError("test teardown"))


# ---------------------------------------------------------------------------
# Router postmortems (chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestRouterPostmortem:
    def test_evict_dumps_bundle(self, tiny_model, shared_artifact, rng):
        router = _router(
            tiny_model, n=2, artifact=shared_artifact,
            router_kw=dict(cooldown_s=60.0),
        )
        with router:
            router.submit(_image(rng), _image(rng))
            router.replicas[0].engine.stop()  # crash one replica
            deadline = time.monotonic() + 10.0
            while (
                router.stats()["router"]["evictions"] == 0
                and time.monotonic() < deadline
            ):
                try:
                    router.submit(_image(rng), _image(rng))
                except ServeError:
                    pass
            b = router.recorder.last_bundle
            assert b is not None and b["reason"].startswith("evict:")
            assert validate_bundle(b) == []
            evict = next(e for e in b["events"] if e["kind"] == "evict")
            assert evict["replica"] == "r0"
            # the bundle carries per-replica context + recent traces
            assert "r0" in b["extra"]["replicas"]
            assert b["extra"]["replicas"]["r0"]["state"] in (
                ReplicaState.UNHEALTHY, ReplicaState.STOPPED,
            )

    def test_manual_dump_postmortem(self, tiny_model, shared_artifact, rng):
        router = _router(tiny_model, n=2, artifact=shared_artifact,
                         trace_sample_rate=1.0)
        with router:
            router.submit(_image(rng), _image(rng))
            b = router.dump_postmortem("operator_snapshot", extra={"x": 1})
            assert validate_bundle(b) == []
            assert b["extra"]["x"] == 1
            assert set(b["extra"]["replicas"]) == {"r0", "r1"}
            assert b["traces"], "replica traces must join the bundle"
            # each live engine contributes its own recent event lane
            assert any(
                info.get("events")
                for info in b["extra"]["engines"].values()
            )


# ---------------------------------------------------------------------------
# Acceptance (chaos): replica kill mid-flood with tracing on
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestAcceptancePostmortem:
    def test_replica_kill_mid_flood_produces_forensic_bundle(
        self, tiny_model, shared_artifact, rng
    ):
        """ISSUE 10 acceptance: the test_serve_router chaos scenario
        (replica kill mid-flood + draining restart) re-run with tracing
        enabled must leave a postmortem bundle containing the eviction
        event, the re-routed requests' traces, and the drain phase
        events — the incident is reconstructable after the fact."""
        router = _router(
            tiny_model, n=3, artifact=shared_artifact,
            trace_sample_rate=1.0, queue_capacity=8,
            router_kw=dict(cooldown_s=60.0),
        )
        results, lost = [], []
        stop = threading.Event()
        lock = threading.Lock()

        def client(i):
            r = np.random.default_rng(100 + i)
            while not stop.is_set():
                try:
                    res = router.submit(
                        _image(r), _image(r), deadline_ms=60000.0
                    )
                    with lock:
                        results.append(res)
                except Overloaded as e:
                    stop.wait(min(e.retry_after_ms, 100.0) / 1e3)
                except ServeError as e:
                    with lock:
                        lost.append(e)

        with router:
            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(12)
            ]
            for t in threads:
                t.start()
            time.sleep(0.5)
            router.replicas[0].engine.stop()          # death mid-flood
            time.sleep(0.5)
            victim = next(
                rep.replica_id for rep in router.replicas[1:]
                if rep.state == ReplicaState.HEALTHY
            )
            router.restart_replica(victim)            # rolling restart
            time.sleep(0.4)
            stop.set()
            for t in threads:
                t.join(timeout=90.0)
            stats = router.stats()
            assert not lost, [repr(e) for e in lost[:5]]
            assert stats["router"]["evictions"] >= 1
            assert stats["router"]["restarts"] == 1

            # --- the forensic record -----------------------------------
            b = router.recorder.last_bundle
            assert b is not None
            assert validate_bundle(b) == []
            kinds = [e["kind"] for e in router.recorder.events()]
            # 1) the eviction event (and its bundle was auto-dumped)
            assert "evict" in kinds
            assert any(
                bb["reason"].startswith("evict:")
                for bb in router.recorder.bundles()
            )
            # 2) the drain phases of the rolling restart
            assert "drain_begin" in kinds and "drain_done" in kinds
            assert "restart_done" in kinds
            # 3) the re-routed requests' traces: reroute events carry the
            # landing trace ids, and an operator dump contains traces
            reroutes = router.recorder.events("reroute")
            assert reroutes, "the kill must have re-routed requests"
            final = router.dump_postmortem("acceptance_final")
            assert final["traces"], "bundle must carry request traces"
            rerouted_ids = {
                e.get("trace_id") for e in reroutes if e.get("trace_id")
            }
            if rerouted_ids:  # sampled re-routes land in the trace ring
                all_ids = {
                    t["trace_id"] for bb in router.recorder.bundles()
                    for t in bb["traces"]
                }
                assert rerouted_ids & all_ids, (
                    "re-routed requests' traces must appear in a bundle"
                )
        # traced results carried ids end to end
        assert results and any(r.trace_id for r in results)


# ---------------------------------------------------------------------------
# Tracing hot-path overhead (satellite): < 5% on the tiny-CPU smoke
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestTracingOverhead:
    def _throughput(self, tiny_model, artifact, rate, seconds, clients=4):
        rng = np.random.default_rng(0)
        im1, im2 = _image(rng), _image(rng)
        done = [0] * clients
        stop = threading.Event()
        with _engine(
            tiny_model, artifact=artifact, trace_sample_rate=rate,
            queue_capacity=32,
        ) as eng:

            def worker(i):
                while not stop.is_set():
                    try:
                        eng.submit(im1, im2, deadline_ms=60000.0)
                        done[i] += 1
                    except ServeError:
                        pass

            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(clients)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            time.sleep(seconds)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            elapsed = time.monotonic() - t0
        return sum(done) / elapsed

    def test_trace_on_overhead_under_5_percent(
        self, tiny_model, shared_artifact
    ):
        """A/B: closed-loop throughput with tracing off vs
        trace_sample_rate=1.0. Interleaved rounds, best-per-arm across
        rounds (absorbs scheduler noise on shared CI — each round is a
        fresh engine, and the comparison stops as soon as the bound
        holds); the traced arm must stay within 5% of the untraced one."""
        seconds = 1.2
        best = {"off": 0.0, "on": 0.0}
        ratio = 0.0
        for _ in range(3):  # A B, A B, A B — early exit once in bound
            best["off"] = max(
                best["off"],
                self._throughput(tiny_model, shared_artifact, 0.0, seconds),
            )
            best["on"] = max(
                best["on"],
                self._throughput(tiny_model, shared_artifact, 1.0, seconds),
            )
            ratio = best["on"] / max(best["off"], 1e-9)
            if ratio >= 0.95:
                break
        assert best["off"] > 0 and best["on"] > 0
        assert ratio >= 0.95, (
            f"tracing-on throughput regressed {100 * (1 - ratio):.1f}% "
            f"(off={best['off']:.1f} rps, on={best['on']:.1f} rps)"
        )


# ---------------------------------------------------------------------------
# Trainer window traces (the spine's training side)
# ---------------------------------------------------------------------------


class TestTrainerObservability:
    def test_window_traces_and_phase_histograms(self, tmp_path, monkeypatch):
        from raft_tpu.models import zoo
        from raft_tpu.train.trainer import TrainConfig, Trainer
        from tests.test_train import tiny_cfg

        monkeypatch.setitem(zoo.CONFIGS, "raft_small", tiny_cfg(large=False))

        class DS:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                r = np.random.default_rng(i)
                hw = (140, 180)
                return {
                    "image1": r.integers(0, 255, (*hw, 3)).astype(np.uint8),
                    "image2": r.integers(0, 255, (*hw, 3)).astype(np.uint8),
                    "flow": r.uniform(-3, 3, (*hw, 2)).astype(np.float32),
                    "valid": np.ones(hw, bool),
                }

        config = TrainConfig(
            arch="raft_small", num_steps=2, global_batch_size=2,
            num_flow_updates=2, crop_size=(128, 128), log_every=1,
            log_dir=str(tmp_path / "logs"), data_mesh=False,
            ledger_sample_every=1,
        )
        tr = Trainer(config, DS())
        tr.run(log_fn=lambda *_: None)
        traces = tr.tracer.snapshot()
        assert len(traces) == 2  # one per window
        for rec in traces:
            assert rec["kind"] == "train_window" and rec["ok"]
            names = [s["name"] for s in rec["spans"]]
            assert "data_wait" in names and "dispatch" in names
            assert "metric_fetch" in names  # log_every=1: every window
        snap = tr.metrics.snapshot()
        assert snap["train/data_wait_ms_count"] == 2
        assert snap["train/dispatch_ms_count"] == 2
        assert snap["train/counters/windows"] == 2
        # device-time ledger (ISSUE 11): the trainer's window-step family
        # was timed (K=1: every window), and the same histogram reached
        # the trainer's Prometheus surface
        bd = tr.ledger.breakdown()
        fam = next(
            (f for n, f in bd["by_family"].items()
             if n.startswith("train_window_step")), None,
        )
        assert fam is not None and fam["sampled"] == 2
        assert fam["est_total_ms"] > 0
        assert "device_ms_train_window_step" in tr.metrics.prometheus_text()


# ---------------------------------------------------------------------------
# scripts/postmortem.py (satellite: CI tooling)
# ---------------------------------------------------------------------------


class TestPostmortemScript:
    def _bundle(self):
        rec = FlightRecorder()
        tracer = Tracer(1.0, on_finish=rec.add_trace)
        tr = tracer.start("pair", 3)
        tr.add_span("admit", time.monotonic() - 0.001)
        tr.finish(ok=True)
        rec.record("evict", replica="r1", reason="heartbeat stalled")
        rec.record("drain_begin", replica="r2", graceful=True)
        rec.record("drain_done", replica="r2")
        return rec.dump("evict:r1", extra={
            "replicas": {"r1": {"state": "unhealthy", "generation": 2,
                                "errors": 3, "evictions": 1,
                                "last_evict_reason": "hb"}},
        })

    def test_check_mode_gates_schema(self, tmp_path, capsys):
        import scripts.postmortem as pm

        path = tmp_path / "bundle.json"
        path.write_text(json.dumps(self._bundle(), default=repr))
        assert pm.main([str(path), "--check"]) == 0
        bad = json.loads(path.read_text())
        del bad["events"]
        bad_path = tmp_path / "bad.json"
        bad_path.write_text(json.dumps(bad))
        assert pm.main([str(bad_path), "--check"]) == 2
        err = capsys.readouterr().err
        assert "events" in err

    def test_timeline_render(self, tmp_path, capsys):
        import scripts.postmortem as pm

        path = tmp_path / "bundle.json"
        path.write_text(json.dumps(self._bundle(), default=repr))
        assert pm.main([str(path), "--traces"]) == 0
        out = capsys.readouterr().out
        assert "evict" in out and "[r1]" in out and "[r2]" in out
        assert "drain_begin" in out
        assert "admit" in out  # span detail under --traces

    def test_reads_events_jsonl(self, tmp_path, capsys):
        import scripts.postmortem as pm
        from raft_tpu.utils.logging import MetricLogger

        rec = FlightRecorder()
        with MetricLogger(str(tmp_path), tensorboard=False) as logger:
            rec.add_sink(logger_sink(logger))
            rec.record("evict", replica="r0", reason="x")
            rec.dump("evict:r0")
        events_file = tmp_path / "events.jsonl"
        assert pm.main([str(events_file), "--check"]) == 0
        out = capsys.readouterr().out
        assert "evict:r0" in out


# ---------------------------------------------------------------------------
# serve_bench phase breakdown (satellite; chaos: runs the bench)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestBenchPhaseBreakdown:
    def test_breakdown_line_from_traces(self, shared_artifact, capsys):
        import scripts.serve_bench as sb

        report = sb.main([
            "--tiny", "--duration", "1.2", "--clients", "3",
            "--max-batch", "2", "--ladder", "2,1", "--pool-capacity", "0",
            "--queue-capacity", "16", "--warmup-artifact", shared_artifact,
            "--trace-sample", "1.0",
        ])
        assert report["traces_collected"] > 0
        pb = report["phase_breakdown"]
        for phase in ("admit", "queue_wait", "dispatch", "fetch"):
            assert phase in pb, pb.keys()
            assert pb[phase]["n"] > 0
            assert pb[phase]["p99_ms"] >= pb[phase]["p50_ms"] >= 0.0
        out = capsys.readouterr().out
        line = next(
            json.loads(l) for l in out.splitlines()
            if '"serve_phase_breakdown"' in l
        )
        assert line["phases"]["queue_wait"]["n"] == pb["queue_wait"]["n"]


# ---------------------------------------------------------------------------
# Device-time ledger (ISSUE 11): unit
# ---------------------------------------------------------------------------


class TestDeviceTimeLedger:
    def test_off_records_nothing(self):
        led = DeviceTimeLedger(0)
        assert not led.active
        assert led.run("fam", lambda: 7) == 7
        bd = led.breakdown()
        assert bd["families"] == 0 and bd["sampled_dispatches"] == 0

    def test_sampling_cadence_and_extrapolation(self):
        import jax.numpy as jnp

        led = DeviceTimeLedger(3)
        for _ in range(7):
            led.run(("pool_step", 2), lambda: jnp.zeros(4))
        bd = led.breakdown()
        fam = bd["by_family"]["pool_step/2"]
        assert fam["executions"] == 7
        assert fam["sampled"] == 3  # executions 0, 3, 6 — deterministic
        # est_total extrapolates mean x executions (snapshot fields are
        # independently rounded, hence the loose tolerance)
        assert fam["est_total_ms"] == pytest.approx(
            fam["mean_ms"] * 7, rel=0.05
        )
        assert bd["sampled_dispatches"] == 3
        assert sum(
            f["share"] for f in bd["by_family"].values()
        ) == pytest.approx(1.0, abs=1e-3)

    def test_registry_histograms_reach_prometheus(self):
        import jax.numpy as jnp

        reg = MetricsRegistry("serve")
        led = DeviceTimeLedger(1, registry=reg)
        led.run(("pairwise", 2, 48, 64, 2), lambda: jnp.zeros(2))
        text = reg.prometheus_text()
        assert "device_ms_pairwise" in text
        # the device-time instrument carries the sub-ms bucket set
        fam = led._fam(("pairwise", 2, 48, 64, 2))
        assert fam.hist.bounds == tuple(DEVICE_TIME_BUCKETS_MS)

    def test_drift_tracks_slowdown(self):
        led = DeviceTimeLedger(1)
        fam = led._fam("f")
        for _ in range(16):
            fam.record(1.0)
        assert led.drift() == pytest.approx(1.0, abs=0.05)
        for _ in range(8):
            fam.record(10.0)  # the hot path got 10x slower
        assert led.drift() > 1.5

    def test_telemetry_failure_never_fails_dispatch(self):
        led = DeviceTimeLedger(1)
        marker = object()  # not blockable-until-ready; must still return
        assert led.run("f", lambda: marker) is marker

    def test_validation(self):
        with pytest.raises(ValueError):
            DeviceTimeLedger(-1)


# ---------------------------------------------------------------------------
# Burn-rate alert engine (ISSUE 11): unit
# ---------------------------------------------------------------------------


class TestAlertEngine:
    def _engine(self, rules, recorder=None):
        return AlertEngine(rules, recorder=recorder, now=lambda: 0.0)

    def test_fire_requires_both_windows(self):
        rule = AlertRule("r", rate("x"), threshold=5.0, short_s=2.0,
                         long_s=10.0)
        eng = self._engine([rule])
        for t in range(9):
            eng.observe({"x": 0}, t=float(t))
        # a 2 s burst: short-window burn 15 > 5, long-window burn
        # diluted to ~3.3 < 5 — multi-window rejects the blip
        eng.observe({"x": 30}, t=9.0)
        assert not eng.is_active("r") and eng.fired == 0
        # sustained: the long window burns too -> fire
        eng.observe({"x": 120}, t=11.0)
        assert eng.is_active("r") and eng.fired == 1
        active = eng.active()
        assert active[0]["rule"] == "r" and active[0]["burn"] > 5.0

    def test_resolve_hysteresis(self):
        rule = AlertRule("r", rate("x"), threshold=5.0, short_s=1.0,
                         long_s=2.0, resolve_ratio=0.5)
        eng = self._engine([rule])
        eng.observe({"x": 0}, t=0.0)
        eng.observe({"x": 100}, t=1.0)
        assert eng.is_active("r")
        # burn drops to 4/s: below threshold but above the 2.5 floor —
        # hysteresis keeps the alert active (no flapping)
        x = 100.0
        for t in (2.0, 3.0, 4.0, 5.0):
            x += 4.0
            eng.observe({"x": x}, t=t)
        assert eng.is_active("r") and eng.resolved == 0
        # burn drops to zero on both windows -> resolve
        for t in (6.0, 7.0, 8.0):
            eng.observe({"x": x}, t=t)
        assert not eng.is_active("r") and eng.resolved == 1

    def test_page_severity_dumps_postmortem_with_alert(self):
        rec = FlightRecorder()
        rule = AlertRule("slo_burn", rate("x"), 1.0, 1.0, 1.0,
                         severity="page")
        eng = self._engine([rule], recorder=rec)
        rec.alerts_provider = eng.active
        eng.observe({"x": 0}, t=0.0)
        eng.observe({"x": 50}, t=1.0)
        assert eng.is_active("slo_burn")
        b = rec.last_bundle
        assert b is not None and b["reason"] == "alert:slo_burn"
        assert validate_bundle(b) == []
        fire = [e for e in b["events"] if e["kind"] == "alert_fire"]
        assert fire and fire[0]["rule"] == "slo_burn"
        assert fire[0]["severity"] == "page"
        assert [a["rule"] for a in b["alerts"]] == ["slo_burn"]

    def test_ticket_severity_records_but_never_dumps(self):
        rec = FlightRecorder()
        eng = self._engine(
            [AlertRule("r", rate("x"), 1.0, 1.0, 1.0)], recorder=rec
        )
        eng.observe({"x": 0}, t=0.0)
        eng.observe({"x": 50}, t=1.0)
        assert rec.events("alert_fire") and rec.dumps == 0

    def test_broken_sink_isolated(self):
        eng = self._engine([AlertRule("r", rate("x"), 1.0, 1.0, 1.0)])
        got = []
        eng.add_sink(lambda info: 1 / 0)
        eng.add_sink(got.append)
        eng.observe({"x": 0}, t=0.0)
        eng.observe({"x": 10}, t=1.0)
        assert [i["rule"] for i in got] == ["r"]  # later sinks still fire

    def test_broken_burn_fn_is_zero(self):
        eng = self._engine(
            [AlertRule("r", lambda p, c, dt: 1 / 0, 0.0, 1.0, 1.0)]
        )
        eng.observe({}, t=0.0)
        eng.observe({}, t=1.0)
        assert not eng.is_active("r")

    def test_validation(self):
        with pytest.raises(ValueError):
            AlertRule("", rate("x"), 1.0)
        with pytest.raises(ValueError):
            AlertRule("r", rate("x"), 1.0, short_s=5.0, long_s=1.0)
        with pytest.raises(ValueError):
            AlertRule("r", rate("x"), 1.0, severity="warn")
        with pytest.raises(ValueError):
            AlertEngine([AlertRule("r", rate("x"), 1.0),
                         AlertRule("r", rate("x"), 2.0)])


# ---------------------------------------------------------------------------
# Histogram per-instrument buckets (ISSUE 11 satellite fix)
# ---------------------------------------------------------------------------


class TestHistogramBounds:
    def test_per_instrument_bounds_and_conflict_detection(self):
        reg = MetricsRegistry()
        h = reg.histogram("device_ms", bounds=DEVICE_TIME_BUCKETS_MS)
        assert h.bounds[0] < 1.0  # sub-ms resolution
        # None = "whatever it already uses"; identical bounds re-register
        assert reg.histogram("device_ms") is h
        assert reg.histogram(
            "device_ms", bounds=DEVICE_TIME_BUCKETS_MS
        ) is h
        # conflicting explicit bounds fail loudly instead of silently
        # keeping the old instrument (the pre-ISSUE-11 behavior)
        with pytest.raises(ValueError):
            reg.histogram("device_ms", bounds=(1.0, 2.0))
        # default instruments still get the latency buckets
        from raft_tpu.obs import LATENCY_BUCKETS_MS

        assert reg.histogram("latency_ms").bounds == LATENCY_BUCKETS_MS


# ---------------------------------------------------------------------------
# Convergence telemetry (ISSUE 11): residual parity + trajectories
# ---------------------------------------------------------------------------


class TestConvergenceTelemetry:
    def test_instrumented_step_is_bitwise_identical(self, tiny_model, rng):
        """The residual reduce is a pure observer: N instrumented pool
        steps produce coords/hidden BITWISE equal to N raw
        ``iterate_step`` calls — the telemetry can never move the flow."""
        import jax
        from functools import partial

        from raft_tpu.serve.pool import PoolPrograms

        model, variables = tiny_model
        progs = PoolPrograms(model, resid_len=4)
        p1 = rng.uniform(-1, 1, (2, 48, 64, 3)).astype(np.float32)
        p2 = rng.uniform(-1, 1, (2, 48, 64, 3)).astype(np.float32)
        cur = dict(progs.begin_pair(variables, p1, p2))
        ref_step = jax.jit(
            partial(model.apply, train=False, method="iterate_step")
        )
        # convergence disabled (thresh <= 0, the ISSUE 12 default): the
        # instrumented step must still be a pure observer
        th, sk, mi = np.float32(0.0), np.int32(2), np.int32(1)
        ref = {k: cur[k] for k in ("pyramid", "coords1", "hidden", "context")}
        for _ in range(3):
            c1, hid, hist, conv, _tok = progs.step(
                variables, cur, th, sk, mi
            )
            cur = {
                **cur, "coords1": c1, "hidden": hid, "resid_hist": hist,
                "converged": conv,
            }
            out = ref_step(variables, ref)
            ref = {**ref, "coords1": out["coords1"],
                   "hidden": out["hidden"]}
            assert np.array_equal(np.asarray(c1), np.asarray(ref["coords1"]))
            assert np.array_equal(np.asarray(hid), np.asarray(ref["hidden"]))
            assert not np.asarray(conv).any()   # disabled: never converges
        # and the history actually holds the measured residuals (older
        # positions hold the admission sentinel, not fake zeros)
        h = np.asarray(hist)
        assert h.shape == (2, 4)
        assert (h[:, -3:] > 0).all() and np.isfinite(h).all()

    @pytest.mark.chaos
    def test_residual_trajectory_on_result_and_stats(self, pool_engine, rng):
        res = pool_engine.submit(
            _image(rng), _image(rng), num_flow_updates=2
        )
        # traced request: the per-iteration trajectory rides the result
        assert res.residuals is not None and len(res.residuals) == 2
        assert all(np.isfinite(v) and v > 0 for v in res.residuals)
        rec = next(
            r for r in pool_engine.tracer.snapshot()
            if r["trace_id"] == res.trace_id
        )
        assert rec["final_residual"] == pytest.approx(
            res.residuals[-1], rel=1e-3
        )
        conv = pool_engine.stats()["convergence"]
        assert conv["enabled"] and conv["n"] >= 1
        assert conv["final_residual_p50"] is not None
        assert conv["resid_by_iter"][0] is not None  # iteration 1 measured

    @pytest.mark.chaos
    def test_untraced_request_carries_no_trajectory(self, pool_engine, rng):
        pool_engine.tracer.sample_rate = 0.0
        try:
            res = pool_engine.submit(_image(rng), _image(rng))
            assert res.trace_id is None and res.residuals is None
            # ...but the aggregate convergence metrics still accumulate
            assert pool_engine.stats()["convergence"]["n"] >= 1
        finally:
            pool_engine.tracer.sample_rate = 1.0


# ---------------------------------------------------------------------------
# Device-time ledger on a live engine (ISSUE 11, chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDeviceTimeLedgerEngine:
    def test_pool_families_priced_and_exposed(self, pool_engine, rng):
        pool_engine.submit(_image(rng), _image(rng))
        bd = pool_engine.device_time_breakdown()
        fams = set(bd["by_family"])
        for prefix in ("pool_begin_pair", "pool_insert", "pool_step",
                       "pool_final", "pool_gather"):
            assert any(f.startswith(prefix) for f in fams), (prefix, fams)
        assert bd["est_total_device_ms"] > 0
        assert bd["sampled_dispatches"] > 0
        # the step family dominates a pool engine's device time
        step = next(
            v for f, v in bd["by_family"].items()
            if f.startswith("pool_step")
        )
        assert step["share"] > 0.05
        # same numbers through stats() and Prometheus
        st = pool_engine.stats()
        assert st["ledger"]["sample_every"] == 1
        assert "device_ms_pool_step" in pool_engine.prometheus()

    def test_fallback_pairwise_family(
        self, tiny_model, shared_artifact, rng
    ):
        with _engine(
            tiny_model, artifact=shared_artifact, ledger_sample_every=1
        ) as eng:
            eng.submit(_image(rng), _image(rng))
            fams = set(eng.device_time_breakdown()["by_family"])
            assert any(f.startswith("pairwise") for f in fams), fams

    def test_breakdown_accounts_for_wall_time(
        self, tiny_model, shared_artifact, rng
    ):
        """ISSUE 11 acceptance: with K=1 under a saturating load, the
        ledger's estimated device total must account for >= 90% of the
        serving loop's wall time — the host-side machinery is
        ~0.1 ms/req (PR 10) and overlaps the blocked dispatches, so on
        the tiny-CPU smoke the wall IS device time and the breakdown
        must say so."""
        im1, im2 = _image(rng), _image(rng)
        stop = threading.Event()
        with _engine(
            tiny_model, artifact=shared_artifact, ledger_sample_every=1,
            max_wait_ms=0.0, queue_capacity=32,
        ) as eng:
            eng.submit(im1, im2)  # warm the loop (staging alloc, etc.)
            s0 = eng.device_time_breakdown()["est_total_device_ms"]

            def client():
                while not stop.is_set():
                    try:
                        eng.submit(im1, im2, deadline_ms=60000.0)
                    except ServeError:
                        pass

            threads = [
                threading.Thread(target=client, daemon=True)
                for _ in range(3)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            time.sleep(1.2)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            wall_ms = (time.monotonic() - t0) * 1e3
            s1 = eng.device_time_breakdown()["est_total_device_ms"]
        measured = s1 - s0
        assert measured > 0
        coverage = measured / wall_ms
        assert coverage >= 0.9, (
            f"ledger accounts for {100 * coverage:.1f}% of wall time "
            f"({measured:.1f} of {wall_ms:.1f} ms)"
        )


# ---------------------------------------------------------------------------
# Ledger hot-path overhead (ISSUE 11 satellite): < 5% A/B
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestLedgerOverhead:
    def test_ledger_off_times_no_dispatch(
        self, tiny_model, shared_artifact, rng, monkeypatch
    ):
        """The ledger's promise, structurally (its cost in pairs/s is a
        chip number now, PERF.md; the wall-clock A/B this replaces raced
        five xdist workers): off, no dispatch is timed or blocked on —
        the ledger reads no clock, calls no ``block_until_ready`` and
        registers no family; at K=1 every dispatch is, exactly once."""
        import types

        import jax

        from raft_tpu.obs import ledger as ledger_mod

        clock_reads, blocks = [], []
        monkeypatch.setattr(ledger_mod, "time", types.SimpleNamespace(
            perf_counter=lambda: clock_reads.append(1) or time.perf_counter()
        ))
        real_block = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: blocks.append(1) or real_block(x),
        )
        im1, im2 = _image(rng), _image(rng)
        seen = {}
        for k in (0, 1):
            del clock_reads[:], blocks[:]
            with _engine(
                tiny_model, artifact=shared_artifact, ledger_sample_every=k,
            ) as eng:
                for _ in range(4):
                    eng.submit(im1, im2, deadline_ms=60000.0)
                seen[k] = (
                    len(clock_reads), len(blocks),
                    eng.device_time_breakdown(), eng.stats()["batches"],
                )
        reads, blocked, bd, _ = seen[0]
        assert (reads, blocked) == (0, 0)
        assert bd["families"] == 0 and bd["sampled_dispatches"] == 0
        reads, blocked, bd, batches = seen[1]
        assert batches >= 4
        # boot's smoke runs are dispatches too
        assert bd["sampled_dispatches"] >= batches
        assert reads == 2 * bd["sampled_dispatches"]
        assert blocked >= bd["sampled_dispatches"]
        assert all(
            f["sampled"] == f["executions"] for f in bd["by_family"].values()
        )


# ---------------------------------------------------------------------------
# Flood chaos (ISSUE 11 acceptance): the SLO burn-rate alert fires and
# its postmortem bundle carries the evidence
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestAlertFloodChaos:
    def test_sustained_flood_fires_slo_burn_with_postmortem(
        self, tiny_model, shared_artifact
    ):
        eng = _engine(
            tiny_model, artifact=shared_artifact, queue_capacity=4,
            alert_short_window_s=0.3, alert_long_window_s=0.9,
        )
        stop = threading.Event()
        rng = np.random.default_rng(7)
        im1, im2 = _image(rng), _image(rng)

        def client():
            while not stop.is_set():
                try:
                    eng.submit(im1, im2, deadline_ms=60000.0)
                except Overloaded:
                    stop.wait(0.002)  # shed: keep hammering
                except ServeError:
                    return

        with eng:
            threads = [
                threading.Thread(target=client, daemon=True)
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10.0
            while (
                not eng._alerts.is_active("slo_burn")
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            fired = eng._alerts.is_active("slo_burn")
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            stats = eng.stats()
        assert fired, (
            f"sustained flood never fired slo_burn "
            f"(shed={stats['shed']}, submitted={stats['submitted']})"
        )
        assert stats["shed"] > 0
        assert "slo_burn" in stats["alerts"]["active"]
        # the page-severity fire auto-dumped a postmortem whose ring
        # contains the alert_fire event and whose alerts block carries
        # the live alert — the acceptance evidence
        bundle = next(
            b for b in eng.recorder.bundles()
            if b["reason"] == "alert:slo_burn"
        )
        assert validate_bundle(bundle) == []
        fire = [
            e for e in bundle["events"]
            if e["kind"] == "alert_fire" and e.get("rule") == "slo_burn"
        ]
        assert fire and fire[0]["severity"] == "page"
        assert any(a["rule"] == "slo_burn" for a in bundle["alerts"])
        # shed context from before the fire rides the same ring
        assert any(e["kind"] == "shed" for e in bundle["events"])


# ---------------------------------------------------------------------------
# scripts/perf_ledger.py (ISSUE 11: the BENCH-trajectory regression gate)
# ---------------------------------------------------------------------------

_REPO_ROOT = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__))
)


class TestPerfLedgerScript:
    def test_check_passes_on_committed_trajectory(self, capsys):
        import scripts.perf_ledger as pl

        assert pl.main(["--check", "--dir", _REPO_ROOT]) == 0
        out = capsys.readouterr().out
        assert "perf ledger" in out

    def test_synthetic_regression_exits_2(self, tmp_path, capsys):
        import json as _json

        import scripts.perf_ledger as pl

        def art(n, value):
            return _json.dumps({
                "n": n, "cmd": "synthetic", "rc": 0,
                "tail": _json.dumps({
                    "metric": "raft_large_sintel_fps", "value": value,
                    "unit": "pairs/s",
                }) + "\n",
            })

        # a history of the test's own (the repo's committed rounds hold
        # no fps series since the pre-PR-1 chip records were deleted)
        for n, value in ((1, 23.8), (2, 28.98), (3, 29.01)):
            (tmp_path / f"BENCH_r{n:02d}.json").write_text(art(n, value))
        path = tmp_path / "regressed.json"
        path.write_text(art(99, 1.0))
        rc = pl.main([
            "--check", "--dir", str(tmp_path), "--candidate", str(path),
        ])
        assert rc == 2
        err = capsys.readouterr().err
        assert "REGRESSION" in err and "raft_large_sintel_fps" in err

    def test_direction_vocabulary(self):
        from scripts.perf_ledger import direction

        assert direction("serve_p99_ms") == "down"
        assert direction("serve_shed_rate") == "down"
        assert direction("serve_device_time/pool_step/p50_ms") == "down"
        assert direction("serve_throughput") == "up"
        assert direction("raft_large_sintel_fps") == "up"
        assert direction("train_steps_per_s") == "up"
        assert direction("serve_pool_occupancy") is None  # not gated

    def test_envelope_semantics(self):
        from scripts.perf_ledger import judge

        kw = dict(min_rel=0.15, spread_factor=1.5, single_prior_rel=0.5)
        improving = [10.0, 12.0, 15.0, 20.0]
        # a monotonically improving history gates at the floor...
        v = judge(improving, 25.0, "serve_throughput", **kw)
        assert not v["regressed"]
        assert v["envelope_rel"] == pytest.approx(0.15)
        # ...so sliding back to round-1 performance IS a regression
        v = judge(improving, 10.0, "serve_throughput", **kw)
        assert v["regressed"]
        # a noisy history earns a proportionally wider envelope
        noisy = [100.0, 60.0, 100.0, 55.0]
        v = judge(noisy, 50.0, "x_per_s", **kw)
        assert v["envelope_rel"] > 0.5 and not v["regressed"]
        # non-directional metrics never regress
        v = judge([1.0, 2.0], 100.0, "serve_pool_occupancy", **kw)
        assert not v["regressed"]

    def test_ledger_lines_join_the_trajectory(self):
        from scripts.perf_ledger import extract_metrics

        line = {
            "metric": "serve_device_time", "sample_every": 2,
            "est_total_device_ms": 1234.5,
            "families": {
                "pool_step/2/6/8": {"p50_ms": 1.5, "p99_ms": 2.5},
            },
        }
        got = dict(extract_metrics(line))
        assert got["serve_device_time/pool_step/2/6/8/p50_ms"] == 1.5
        assert got["serve_device_time/est_total_device_ms"] == 1234.5
        conv = {
            "metric": "serve_convergence", "n": 10,
            "final_residual_p50": 0.05, "final_residual_p99": 0.25,
        }
        got = dict(extract_metrics(conv))
        assert got["serve_convergence/final_residual_p50"] == 0.05


# ---------------------------------------------------------------------------
# Postmortem schema /2 (ISSUE 11 satellite): alert lane + legacy reader
# ---------------------------------------------------------------------------


class TestPostmortemV2:
    def test_legacy_v1_bundle_still_validates(self, tmp_path):
        import scripts.postmortem as pm

        v1 = {
            "schema": "raft-postmortem/1", "reason": "evict:r0",
            "dumped_wall": 0.0, "dumped_t": 0.0,
            "events": [], "traces": [], "extra": {},
        }
        assert validate_bundle(v1) == []  # backward-compatible reader
        path = tmp_path / "v1.json"
        path.write_text(json.dumps(v1))
        assert pm.main([str(path), "--check"]) == 0

    def test_v2_bundle_still_validates(self):
        # a /2 bundle on disk (pre-ISSUE-15: no proc/pid) stays valid
        b = dict(FlightRecorder().dump("x"), schema="raft-postmortem/2")
        del b["proc"], b["pid"]
        assert validate_bundle(b) == []
        bad = dict(b)
        del bad["alerts"]
        assert any("alerts" in p for p in validate_bundle(bad))
        bad2 = dict(b, alerts=[{"severity": "page"}])  # no rule name
        assert any("alerts[0]" in p for p in validate_bundle(bad2))

    def test_v3_requires_proc_and_pid(self):
        b = FlightRecorder(proc="engine").dump("x")
        # live dumps moved to /4 (ISSUE 16: transport + endpoint); a /3
        # bundle on disk — same shape minus the two new fields — stays
        # valid forever, and /3 still requires its own additions
        b3 = {
            k: v for k, v in b.items()
            if k not in ("transport", "endpoint")
        }
        b3["schema"] = "raft-postmortem/3"
        assert b3["proc"] == "engine" and isinstance(b3["pid"], int)
        assert validate_bundle(b3) == []
        bad = dict(b3)
        del bad["proc"]
        assert any("proc" in p for p in validate_bundle(bad))
        # a stitched span's process lane must be a lane name
        bad2 = dict(b, traces=[{
            "trace_id": "t0", "kind": "pair", "dur_ms": 1.0,
            "spans": [{"name": "rpc", "t0_ms": 0.0, "dur_ms": 1.0,
                       "proc": 7}],
        }])
        assert any(".proc" in p for p in validate_bundle(bad2))

    def test_alert_lane_rendered_with_severity(self, tmp_path, capsys):
        import scripts.postmortem as pm

        rec = FlightRecorder()
        eng = AlertEngine(
            [AlertRule("slo_burn", rate("x"), 1.0, 1.0, 1.0,
                       severity="page")],
            recorder=rec, now=lambda: 0.0,
        )
        rec.alerts_provider = eng.active
        rec.record("shed", rid=1)
        eng.observe({"x": 0}, t=0.0)
        eng.observe({"x": 50}, t=1.0)
        path = tmp_path / "bundle.json"
        path.write_text(json.dumps(rec.last_bundle, default=repr))
        assert pm.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "active alerts at dump" in out
        assert "!!" in out  # page severity annotation in the alert lane
        assert "alert_fire" in out
        assert "shed" in out  # non-alert events keep their blank lane


# ---------------------------------------------------------------------------
# Postmortem schema /4 (ISSUE 16 satellite): transport + endpoint
# ---------------------------------------------------------------------------


class TestPostmortemV4:
    def test_live_dump_is_v4_with_transport(self):
        b = FlightRecorder(
            proc="link", transport="tcp", endpoint="127.0.0.1:9999",
        ).dump("partition")
        assert b["schema"] == "raft-postmortem/4"
        assert b["transport"] == "tcp"
        assert b["endpoint"] == "127.0.0.1:9999"
        assert validate_bundle(b) == []
        # JSON round trip keeps it valid (the --fleet input is files)
        assert validate_bundle(json.loads(json.dumps(b))) == []

    def test_local_default(self):
        b = FlightRecorder().dump("x")
        assert b["transport"] == "local" and b["endpoint"] is None
        assert validate_bundle(b) == []

    def test_v4_requires_and_types_the_new_fields(self):
        good = FlightRecorder(transport="tcp", endpoint="h:1").dump("x")
        bad = dict(good)
        del bad["transport"]
        assert any("transport" in p for p in validate_bundle(bad))
        bad2 = dict(good)
        del bad2["endpoint"]
        assert any("endpoint" in p for p in validate_bundle(bad2))
        bad3 = dict(good, transport=7)
        assert any("transport" in p for p in validate_bundle(bad3))
        bad4 = dict(good, endpoint=7)
        assert any("endpoint" in p for p in validate_bundle(bad4))


# ---------------------------------------------------------------------------
# serve_bench device-time line (ISSUE 11 satellite; chaos: runs the bench)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestBenchDeviceTime:
    def test_serve_device_time_line(self, shared_artifact, capsys):
        import scripts.serve_bench as sb

        report = sb.main([
            "--tiny", "--duration", "1.0", "--clients", "3",
            "--max-batch", "2", "--ladder", "2,1", "--pool-capacity", "0",
            "--queue-capacity", "16", "--warmup-artifact", shared_artifact,
            "--ledger-sample", "2",
        ])
        assert report["ledger"]["sample_every"] == 2
        assert report["ledger"]["sampled_dispatches"] > 0
        out = capsys.readouterr().out
        line = next(
            json.loads(l) for l in out.splitlines()
            if '"serve_device_time"' in l
        )
        assert line["families"], line
        assert line["est_total_device_ms"] > 0
        shares = [f["share"] for f in line["families"].values()]
        assert sum(shares) == pytest.approx(1.0, abs=0.01)
