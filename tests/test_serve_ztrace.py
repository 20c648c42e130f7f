"""Fleet observability plane (ISSUE 15): edge-to-engine trace
propagation, clock-aligned cross-process stitching, edge SLOs at the
HTTP front door, decision-grade autoscaler telemetry, and the --fleet
postmortem view.

Layers of coverage:

* **trace-context units** — adopted trace ids bypass the local sampling
  rate (the edge decided once), ``Trace.absorb`` maps a child record's
  timestamps onto the absorbing clock via the handshake offset and tags
  process lanes, ``dedupe_traces`` keeps the richest record per id.
* **in-process join** — a rate-0 engine handed a ``TraceContext`` traces
  under the propagated id and stitches its sealed record into the edge
  trace before ``submit`` returns.
* **frontend edge** — a trace born at the HTTP front door (sampled or
  adopted from ``X-Raft-Trace``) carries http_read -> engine spans ->
  http_write; edge latency lands in the per-class stats block; the edge
  ``slo_burn`` rule pages off (miss + shed) / requests.
* **the chaos acceptance** — an HTTP request through a 2-replica
  PROCESS fleet at ``trace_sample_rate=1.0`` yields ONE trace containing
  frontend, router, transport, and worker spans in causal order (worker
  spans inside the clock-aligned rpc window), and the same stitched
  trace is recoverable from a postmortem dump directory via
  ``postmortem.py --fleet``.
* **back-compat pin** — a PR 14-wire worker (no trace field, no clock
  handshake; the ``trace_propagation=False`` arm speaks exactly that
  wire) still serves against the new parent: spans degrade to the
  parent-side transport view, nothing raises.
* **overhead** — structural: at rate 0 a request THROUGH the front
  door builds no ``Trace`` anywhere; at rate 1.0 exactly the edge trace
  and the engine's joined one (what tracing costs in pairs/s is measured
  on the chip, PERF.md).

This module is named to sort AFTER tests/test_serve_xport.py: tier-1's
870s truncation and the process-global compile-cache order dependency
both key on alphabetical module order, so the heavy fleet fixtures here
must not displace earlier modules' dots. Everything heavy shares ONE
module warmup artifact and ONE 2-replica process fleet (the
test_serve_worker fixture pattern).
"""

import json
import time

import numpy as np
import pytest

from raft_tpu.obs import TraceContext, Tracer, dedupe_traces
from raft_tpu.serve import (
    RouterConfig,
    ServeEngine,
    ServeFrontend,
    ServeRouter,
    FrontendClient,
)
from tests.test_serve_worker import (
    _WORKER_OPTS,
    WorkerFactory,
    _config,
    _image,
    _tiny_model,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    """Persistent-cache dedupe for in-process engines (this module
    sorts after tests/test_serve_aot.py)."""
    from raft_tpu.serve import aot

    aot.enable_persistent_cache(
        str(tmp_path_factory.mktemp("ztrace_jax_cache"))
    )


@pytest.fixture(scope="module")
def shared_artifact(tiny_model, tmp_path_factory):
    """ONE warmup artifact for every engine and both fleet workers."""
    from raft_tpu.serve import aot

    model, variables = tiny_model
    path = str(tmp_path_factory.mktemp("ztrace_aot") / "shared.raftaot")
    aot.save_artifact(ServeEngine(model, variables, _config()), path)
    return path


@pytest.fixture(scope="module")
def inproc_engine(tiny_model, shared_artifact):
    """A rate-0 in-process engine: propagation must trace it anyway."""
    model, variables = tiny_model
    eng = ServeEngine(
        model, variables,
        _config(warmup=True, warmup_artifact=shared_artifact,
                trace_sample_rate=0.0, queue_capacity=32),
    )
    eng.start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def fleet(shared_artifact, tmp_path_factory):
    """The acceptance rig: ONE 2-replica process fleet behind ONE HTTP
    front door, everything sampling at 1.0, all bundles landing in one
    dump directory (the --fleet input)."""
    dump_dir = str(tmp_path_factory.mktemp("ztrace_dumps"))
    router = ServeRouter.from_factory(
        WorkerFactory(
            warmup=True, warmup_artifact=shared_artifact,
            trace_sample_rate=1.0,
        ),
        2,
        RouterConfig(heartbeat_interval_s=0.1, cooldown_s=0.5),
        backend="process",
        worker_options=dict(_WORKER_OPTS, dump_dir=dump_dir),
    )
    router.start()
    frontend = ServeFrontend(
        router, trace_sample_rate=1.0, dump_dir=dump_dir,
    ).start()
    yield router, frontend, dump_dir
    frontend.close()
    router.close()


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _find_trace(tracer, tid, timeout=5.0):
    """The edge trace seals AFTER the HTTP response goes out (http_write
    is a real span), so an in-process read immediately after the client
    returns can race the handler's finally — poll briefly."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rec = tracer.find(tid)
        if rec is not None:
            return rec
        time.sleep(0.01)
    return None


# ---------------------------------------------------------------------------
# trace-context units
# ---------------------------------------------------------------------------


class TestTraceContextUnits:
    def test_adopted_id_bypasses_sampling(self):
        t = Tracer(0.0)  # rate 0: start() would return None
        assert t.start("pair") is None
        tr = t.start("pair", trace_id="edge-42")
        assert tr is not None and tr.trace_id == "edge-42"
        assert t.started == 1

    def test_record_sealed_once_and_readable(self):
        tr = Tracer(1.0).start("pair", rid=3)
        assert tr.record is None
        rec = tr.finish(ok=True)
        assert tr.record is rec
        assert tr.finish(ok=False) is None  # set-once
        assert tr.record is rec

    def test_absorb_aligns_clocks_and_tags_lanes(self):
        edge = Tracer(1.0).start("http")
        # a child sealed on a clock 2.0s AHEAD of ours, starting 10ms
        # after our trace start (in OUR clock)
        child = {
            "trace_id": edge.trace_id,
            "t_start": edge.t_start + 0.010 + 2.0,
            "spans": [
                {"name": "admit", "t0_ms": 1.0, "dur_ms": 0.5, "rung": 2},
            ],
        }
        edge.absorb(child, proc="worker-9", t_offset_s=2.0)
        rec = edge.finish(ok=True)
        sp = rec["spans"][0]
        assert sp["name"] == "admit" and sp["proc"] == "worker-9"
        assert sp["rung"] == 2  # child attrs survive
        # 10ms child start + 1ms span offset, the +2s skew removed
        assert sp["t0_ms"] == pytest.approx(11.0, abs=0.5)
        assert sp["dur_ms"] == pytest.approx(0.5, abs=1e-6)

    def test_absorb_none_and_ctx_without_trace_are_noops(self):
        edge = Tracer(1.0).start("http")
        edge.absorb(None, proc="x")
        TraceContext("tid").absorb({"t_start": 0.0, "spans": []})
        assert edge.finish()["spans"] == []

    def test_dedupe_keeps_richest_record_per_id(self):
        rich = {"trace_id": "a", "spans": [{}, {}, {}]}
        poor = {"trace_id": "a", "spans": [{}]}
        other = {"trace_id": "b", "spans": []}
        untagged = {"kind": "train_window", "spans": []}
        out = dedupe_traces([poor, untagged, rich, other])
        assert out == [rich, untagged, other]


# ---------------------------------------------------------------------------
# in-process join (rate-0 engine + external context)
# ---------------------------------------------------------------------------


class TestEngineJoin:
    def test_rate0_engine_joins_external_trace(self, inproc_engine, rng):
        edge = Tracer(1.0, prefix="edge").start("http")
        ctx = TraceContext(edge.trace_id, edge)
        res = inproc_engine.submit(
            _image(rng), _image(rng), deadline_ms=60000.0, trace_ctx=ctx,
        )
        # the engine's rate is 0, yet the request is traced — under the
        # edge's id — and its record is ALREADY stitched when we return
        assert res.trace_id == edge.trace_id
        rec = edge.finish(ok=True)
        engine_spans = [
            s for s in rec["spans"] if s.get("proc") == "engine"
        ]
        assert {"admit", "dispatch", "fetch"} <= {
            s["name"] for s in engine_spans
        }
        # every engine span lies inside the edge trace window
        for s in engine_spans:
            assert s["t0_ms"] >= -1e-6
            assert s["t0_ms"] + s["dur_ms"] <= rec["dur_ms"] + 1.0
        # and the engine ring holds the same trace_id (dedupe target)
        assert inproc_engine.tracer.find(edge.trace_id) is not None

    def test_without_ctx_rate0_traces_nothing(self, inproc_engine, rng):
        res = inproc_engine.submit(
            _image(rng), _image(rng), deadline_ms=60000.0,
        )
        assert res.trace_id is None

    def test_stream_frame_joins_trace(self, inproc_engine, rng):
        edge = Tracer(1.0, prefix="edge").start("http")
        ctx = TraceContext(edge.trace_id, edge)
        with inproc_engine.open_stream() as stream:
            stream.submit(_image(rng), deadline_ms=60000.0)
            res = stream.submit(
                _image(rng), deadline_ms=60000.0, trace_ctx=ctx,
            )
        assert res.trace_id == edge.trace_id
        rec = edge.finish(ok=True)
        assert any(s.get("proc") == "engine" for s in rec["spans"])


# ---------------------------------------------------------------------------
# frontend edge: born-at-the-edge traces + edge SLO accounting
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def edge_frontend(inproc_engine):
    fe = ServeFrontend(inproc_engine, trace_sample_rate=1.0).start()
    yield fe
    fe.close()


class TestFrontendEdge:
    def test_edge_trace_stitches_and_orders(self, edge_frontend, rng):
        fc = FrontendClient(edge_frontend.address)
        meta = fc.submit(_image(rng), _image(rng), deadline_ms=60000.0)
        tid = meta["edge_trace_id"]
        assert tid is not None and meta["trace_id"] == tid
        rec = _find_trace(edge_frontend.tracer, tid)
        assert rec is not None
        spans = sorted(rec["spans"], key=lambda s: s["t0_ms"])
        names = [s["name"] for s in spans]
        assert names[0] == "http_read" and names[-1] == "http_write"
        assert {"admit", "dispatch", "fetch"} <= set(names)
        assert rec["req_class"] == "pair"
        assert rec["edge_latency_ms"] > 0
        fc.close_connection()

    def test_header_adoption(self, edge_frontend, rng):
        fc = FrontendClient(edge_frontend.address)
        meta = fc.submit(
            _image(rng), _image(rng), deadline_ms=60000.0,
            trace_id="caller-chose-this",
        )
        assert meta["edge_trace_id"] == "caller-chose-this"
        assert _find_trace(edge_frontend.tracer, "caller-chose-this") is not None
        fc.close_connection()

    def test_edge_latency_and_slo_accounting(self, edge_frontend):
        before = edge_frontend.snapshot()
        edge_frontend.note_edge("pair", 120.0, 50.0)   # a miss
        edge_frontend.note_edge("pair", 10.0, 50.0)    # within SLO
        edge_frontend.note_edge("pair", 999.0, None)   # no deadline: no miss
        snap = edge_frontend.snapshot()
        assert snap["http_slo_miss"] == before["http_slo_miss"] + 1
        assert (
            snap["edge_latency"]["pair"]["n"]
            == before["edge_latency"]["pair"]["n"] + 3
        )
        assert snap["alerts"]["rules"] == ["slo_burn"]

    def test_metrics_exposition_includes_edge_histograms(
        self, edge_frontend
    ):
        fc = FrontendClient(edge_frontend.address)
        text = fc.metrics_text()
        assert "frontend_edge_latency_ms_pair" in text
        assert "frontend_alerts_active" in text
        fc.close_connection()


# ---------------------------------------------------------------------------
# the chaos acceptance: one trace across four processes
# ---------------------------------------------------------------------------


class TestFleetStitching:
    def _lanes(self, rec):
        return {s.get("proc") for s in rec["spans"]}

    def test_one_trace_across_four_processes(self, fleet, rng):
        """The acceptance criterion: an HTTP request through a
        2-replica process fleet at trace_sample_rate=1.0 yields ONE
        trace containing frontend, router, transport, and worker spans
        in causal order."""
        router, frontend, _ = fleet
        fc = FrontendClient(frontend.address)
        meta = fc.submit(_image(rng), _image(rng), deadline_ms=120000.0)
        tid = meta["edge_trace_id"]
        assert tid is not None
        rec = _find_trace(frontend.tracer, tid)
        assert rec is not None
        lanes = self._lanes(rec)
        assert "frontend" in lanes
        assert "router" in lanes
        assert "transport" in lanes
        worker_lanes = {
            p for p in lanes if p and p.startswith("worker-")
        }
        assert len(worker_lanes) == 1  # exactly one worker served it
        by_name = {}
        for s in rec["spans"]:
            by_name.setdefault(s["name"], s)
        # causal order: read -> pick -> rpc -> write
        assert by_name["http_read"]["t0_ms"] <= by_name["route_pick"]["t0_ms"]
        assert by_name["route_pick"]["t0_ms"] <= by_name["rpc"]["t0_ms"]
        rpc = by_name["rpc"]
        assert (
            by_name["http_write"]["t0_ms"]
            >= rpc["t0_ms"] + rpc["dur_ms"] - 0.5
        )
        # worker spans inside the clock-aligned rpc window: the offset
        # estimate is good to +-rtt/2, so allow a small epsilon
        reps = [r for r in router.replicas if r.engine is not None]
        rtts = [
            (r.engine.clock_rtt_s or 0.0) for r in reps
            if hasattr(r.engine, "clock_rtt_s")
        ]
        eps_ms = max(5.0, 1e3 * max(rtts, default=0.0))
        worker_spans = [
            s for s in rec["spans"]
            if (s.get("proc") or "").startswith("worker-")
        ]
        assert {"admit", "dispatch", "fetch"} <= {
            s["name"] for s in worker_spans
        }
        for s in worker_spans:
            assert s["t0_ms"] >= rpc["t0_ms"] - eps_ms, (s, rpc)
            assert (
                s["t0_ms"] + s["dur_ms"]
                <= rpc["t0_ms"] + rpc["dur_ms"] + eps_ms
            ), (s, rpc)
        # the route_pick span names the replica that served it
        assert by_name["route_pick"]["replica"] in {
            r.replica_id for r in reps
        }
        fc.close_connection()

    def test_negotiation_and_clock_visible_in_transport_stats(self, fleet):
        router, _, _ = fleet
        for rep in router.replicas:
            ts = rep.engine.transport_stats()
            assert ts["trace_propagation"] is True
            assert ts["clock_rtt_ms"] is not None
            # same-host monotonic clocks: the offset must be tiny
            assert abs(ts["clock_offset_ms"]) < 1e3

    def test_dedupe_across_frontend_and_worker_rings(self, fleet, rng):
        """The satellite fix: a propagated request exists in the
        frontend ring (stitched) AND the worker ring (its own record) —
        merged streams must count it once, keeping the stitched one."""
        router, frontend, _ = fleet
        fc = FrontendClient(frontend.address)
        meta = fc.submit(_image(rng), _image(rng), deadline_ms=120000.0)
        tid = meta["edge_trace_id"]
        merged = list(frontend.tracer.snapshot())
        for rep in router.replicas:
            merged.extend(rep.engine.tracer.snapshot())
        ids = [r.get("trace_id") for r in merged]
        assert ids.count(tid) >= 2  # genuinely duplicated before dedupe
        deduped = dedupe_traces(merged)
        mine = [r for r in deduped if r.get("trace_id") == tid]
        assert len(mine) == 1
        assert any("proc" in s for s in mine[0]["spans"])  # stitched won
        fc.close_connection()

    def test_statz_fleet_tree_and_labeled_metrics(self, fleet, rng):
        router, frontend, _ = fleet
        fc = FrontendClient(frontend.address)
        fc.submit(_image(rng), _image(rng), deadline_ms=120000.0)
        stats = fc.stats()
        assert "fleet" in stats
        tree = stats["fleet"]
        assert tree["replica_count"] == 2
        for rid, info in tree["replicas"].items():
            assert info["backend"] == "process"
            assert isinstance(info["pid"], int)
        assert "edge_latency" in stats["frontend"]
        # per-replica labeled series from one scrape surface
        text = fc.metrics_text()
        assert 'replica="r0"' in text
        assert 'replica="r1"' in text
        assert "frontend_edge_latency_ms_pair" in text
        fc.close_connection()

    def test_fleet_postmortem_recovers_stitched_trace(
        self, fleet, rng, capsys
    ):
        """The second half of the acceptance: the stitched trace is
        recoverable from a postmortem dump directory via
        postmortem.py --fleet (parent bundles + worker bundles)."""
        import scripts.postmortem as pm

        from raft_tpu.obs import file_sink

        router, frontend, dump_dir = fleet
        fc = FrontendClient(frontend.address)
        meta = fc.submit(_image(rng), _image(rng), deadline_ms=120000.0)
        tid = meta["edge_trace_id"]
        fc.close_connection()
        # freeze the incident: frontend + router bundles, and each
        # worker's own bundle pulled into the SAME dump_dir (the PR 13
        # eviction path's mechanism, invoked directly here). Distinct
        # reasons: each process's file_sink numbers its own files, so
        # the reason slug is what keeps them apart in one directory.
        router.recorder.add_sink(file_sink(dump_dir))
        frontend.dump_postmortem("chaos-edge")
        router.dump_postmortem("chaos-router")
        for rep in router.replicas:
            assert rep.dump_worker_postmortem(f"chaos-{rep.replica_id}")
        # every bundle in the dir is schema-valid (/3)
        assert pm.main(["--check", dump_dir]) == 0
        capsys.readouterr()
        assert pm.main(["--fleet", dump_dir]) == 0
        out = capsys.readouterr().out
        assert tid in out
        assert "frontend" in out and "router" in out
        assert "worker-" in out
        # the stitched record renders with its cross-process lane chain
        assert "stitched across processes" in out
        # bundle identity: worker bundles carry proc=engine + their pid
        bundles = pm.load_bundles_dir(dump_dir)
        procs = {b.get("proc") for b in bundles}
        assert {"frontend", "router", "engine"} <= procs


# ---------------------------------------------------------------------------
# back-compat: the PR 14 wire against the new parent
# ---------------------------------------------------------------------------


class TestBackCompatPR14Wire:
    def test_pr14_wire_worker_degrades_to_parent_view(
        self, shared_artifact, rng
    ):
        """trace_propagation=False speaks EXACTLY the PR 14 wire: no
        trace field on submit records, no clock RPC, no ready echo. The
        new parent must keep serving traffic — spans degrade to the
        parent-side transport view, nothing raises."""
        from raft_tpu.serve.worker import ProcessEngineClient

        client = ProcessEngineClient(
            WorkerFactory(warmup=True, warmup_artifact=shared_artifact),
            trace_propagation=False,
            **_WORKER_OPTS,
        )
        client.start()
        try:
            assert client.trace_propagation is False
            assert client.clock_rtt_s is None  # no clock handshake ran
            edge = Tracer(1.0, prefix="edge").start("http")
            ctx = TraceContext(edge.trace_id, edge)
            res = client.submit(
                _image(rng), _image(rng), deadline_ms=120000.0,
                trace_ctx=ctx,
            )
            assert np.isfinite(res.flow).all()
            rec = edge.finish(ok=True)
            lanes = {s.get("proc") for s in rec["spans"]}
            assert "transport" in lanes  # the parent-side view survives
            assert not any(
                p and p.startswith("worker-") for p in lanes
            )
            # the worker never traced it under the edge id either
            assert client.tracer.find(edge.trace_id) is None
            assert (
                client.transport_stats()["trace_propagation"] is False
            )
        finally:
            client.close()


# ---------------------------------------------------------------------------
# overhead: the off path through the front door builds no Trace
# ---------------------------------------------------------------------------


class TestEdgeTracingOverhead:
    def test_tracing_off_builds_no_trace_through_the_front_door(
        self, tiny_model, shared_artifact, monkeypatch
    ):
        """The propagation machinery's promise, structurally (what
        tracing costs in pairs/s is a chip number now, PERF.md; the
        wall-clock A/B this replaces raced five xdist workers): at rate
        0 a request THROUGH the HTTP front door builds no ``Trace``
        anywhere — frontend, engine, scheduler loop — and no record; at
        rate 1.0 each request builds its edge trace and the engine's
        joined one, stitched under ONE id."""
        from raft_tpu.obs import trace as trace_mod

        built = []
        real_init = trace_mod.Trace.__init__

        def counting_init(self, trace_id, kind, *a, **kw):
            built.append(kind)
            real_init(self, trace_id, kind, *a, **kw)

        monkeypatch.setattr(trace_mod.Trace, "__init__", counting_init)
        model, variables = tiny_model
        rng = np.random.default_rng(0)
        im1, im2 = _image(rng), _image(rng)
        n = 4
        for rate in (0.0, 1.0):
            del built[:]
            eng = ServeEngine(
                model, variables,
                _config(warmup=True, warmup_artifact=shared_artifact,
                        trace_sample_rate=rate, queue_capacity=32),
            )
            eng.start()
            fe = ServeFrontend(eng, trace_sample_rate=rate).start()
            try:
                fc = FrontendClient(fe.address)
                metas = [
                    fc.submit(im1, im2, deadline_ms=60000.0) for _ in range(n)
                ]
                fc.close_connection()
                deadline = time.monotonic() + 5.0
                while (  # an edge trace seals after its response went out
                    len(fe.tracer.snapshot()) < n * rate
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.01)
                edge, inner = fe.tracer.snapshot(), eng.tracer.snapshot()
            finally:
                fe.close()
                eng.stop()
            if rate == 0.0:
                assert built == []
                assert edge == [] and inner == []
                assert all(m.get("edge_trace_id") is None for m in metas)
                assert eng.stats()["obs"]["traces_started"] == 0
            else:
                requests = [k for k in built if k != "sched"]
                assert len(requests) == 2 * n  # edge + engine, per request
                ids = [m["edge_trace_id"] for m in metas]
                assert len(set(ids)) == n
                assert sorted(r["trace_id"] for r in edge) == sorted(ids)
                for rec in edge:  # the engine's spans joined the edge trace
                    names = {s["name"] for s in rec["spans"]}
                    assert {"http_read", "dispatch", "http_write"} <= names


# ---------------------------------------------------------------------------
# bench + ledger wiring (the serve_edge_slo satellite)
# ---------------------------------------------------------------------------


class TestBenchAndLedgerEdge:
    def test_ledger_flattens_serve_edge_slo_with_directions(self):
        import scripts.perf_ledger as pl

        line = {
            "metric": "serve_edge_slo",
            "classes": {
                "pairwise": {
                    "deadline_ms": 2000.0,
                    "edge_p50_ms": 25.0, "edge_p99_ms": 60.0,
                    "engine_p50_ms": 20.0, "engine_p99_ms": 50.0,
                    "wire_tax_p50_ms": 5.0, "wire_tax_p99_ms": 10.0,
                    "slo_miss_rate": 0.01,
                },
            },
            "config": "c",
        }
        got = dict(pl.extract_metrics(line))
        assert got["serve_edge_slo/pairwise/edge_p99_ms"] == 60.0
        assert got["serve_edge_slo/pairwise/wire_tax_p50_ms"] == 5.0
        assert "serve_edge_slo/pairwise/deadline_ms" not in got  # a pin
        assert pl.direction("serve_edge_slo/pairwise/edge_p99_ms") == "down"
        assert pl.direction(
            "serve_edge_slo/pairwise/wire_tax_p50_ms"
        ) == "down"
        assert pl.direction(
            "serve_edge_slo/pairwise/slo_miss_rate"
        ) == "down"

    def test_bench_frontend_arm_emits_edge_slo_line(
        self, shared_artifact, capsys
    ):
        import scripts.serve_bench as sb

        report = sb.main([
            "--tiny", "--frontend", "--duration", "1.5", "--clients", "3",
            "--max-batch", "2", "--ladder", "2,1", "--pool-capacity", "0",
            "--queue-capacity", "16", "--warmup-artifact", shared_artifact,
            "--trace-sample", "1.0",
        ])
        assert report["edge_slo"], report.get("edge_slo")
        es = report["edge_slo"]["pairwise"]
        assert es["edge_p50_ms"] is not None
        assert es["engine_p50_ms"] is not None
        # the edge can never be cheaper than the engine it wraps
        assert es["wire_tax_p50_ms"] >= 0.0
        assert report["frontend"]["http_completed"] > 0
        # the stitched traces feed the phase breakdown (edge lanes in)
        assert report["phase_breakdown"].get("http_read"), (
            report["phase_breakdown"]
        )
        out = capsys.readouterr().out
        line = next(
            json.loads(l) for l in out.splitlines()
            if '"serve_edge_slo"' in l
        )
        assert line["classes"]["pairwise"]["edge_p99_ms"] is not None
        assert line["http_requests"] >= line["classes"]["pairwise"].get(
            "n", 0
        )

    def test_committed_r10_passes_the_gate(self):
        """BENCH_r10 (this PR's measured round — the first through the
        HTTP front door): the ledger accepts the full r01-r10
        trajectory, with the serve_edge_slo series joining it."""
        import scripts.perf_ledger as pl

        assert pl.main(["--check"]) == 0


# ---------------------------------------------------------------------------
# postmortem --fleet on synthetic bundles (cheap, no fleet needed)
# ---------------------------------------------------------------------------


class TestPostmortemFleetSynthetic:
    def _bundle(self, proc, pid, reason, traces):
        return {
            "schema": "raft-postmortem/3", "reason": reason,
            "proc": proc, "pid": pid,
            "dumped_wall": 0.0, "dumped_t": 100.0,
            "events": [], "traces": traces, "alerts": [], "extra": {},
        }

    def test_fleet_view_merges_and_dedupes(self, tmp_path, capsys):
        import scripts.postmortem as pm

        stitched = {
            "trace_id": "edge-1", "kind": "http", "rid": None,
            "t_start": 0.0, "wall_start": 0.0, "dur_ms": 50.0,
            "ok": True, "error": None,
            "spans": [
                {"name": "http_read", "t0_ms": 0.0, "dur_ms": 1.0,
                 "proc": "frontend"},
                {"name": "route_pick", "t0_ms": 1.0, "dur_ms": 0.1,
                 "proc": "router", "replica": "r0"},
                {"name": "rpc", "t0_ms": 2.0, "dur_ms": 40.0,
                 "proc": "transport"},
                {"name": "dispatch", "t0_ms": 5.0, "dur_ms": 30.0,
                 "proc": "worker-123"},
                {"name": "http_write", "t0_ms": 45.0, "dur_ms": 2.0,
                 "proc": "frontend"},
            ],
        }
        worker_own = {
            "trace_id": "edge-1", "kind": "pair", "rid": 0,
            "t_start": 0.0, "wall_start": 0.0, "dur_ms": 35.0,
            "ok": True, "error": None,
            "spans": [
                {"name": "dispatch", "t0_ms": 0.0, "dur_ms": 30.0},
            ],
        }
        (tmp_path / "postmortem_0000_edge.json").write_text(
            json.dumps(self._bundle("frontend", 1, "edge", [stitched]))
        )
        (tmp_path / "postmortem_0001_worker.json").write_text(
            json.dumps(self._bundle("engine", 123, "evict", [worker_own]))
        )
        assert pm.main(["--fleet", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "1 stitched across processes" in out
        # lanes render in causal order, once per trace_id
        assert out.count("trace edge-1") == 1
        assert "frontend -> router -> transport -> worker-123" in out
        assert pm.main(["--check", str(tmp_path)]) == 0
