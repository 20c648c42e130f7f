"""Horizontal-tier chaos suite: the router ladder evict -> re-route ->
shed -> drain (ISSUE 9, docs/failure_model.md router section).

Every router mechanic is exercised against REAL ServeEngine replicas
(tiny model, CPU): consistent-hash stream affinity and its ~1/N remap
bound, health-driven eviction (reported-dead, stalled heartbeat,
error-rate budget) with cooldown re-admission rebuilding the engine,
cross-replica shedding with retry_after aggregation, and draining
restarts that drop zero accepted requests while stream sessions migrate
by re-priming. Chaos is injected through `FaultInjector.patch_router`
(`router.heartbeat` / `router.dispatch`) composed with the per-engine
`patch_engine` sites. The acceptance scenario at the bottom kills a
replica mid-flood with live stream traffic and a concurrent draining
restart — the "million users" claim reduced to: nothing accepted is
ever lost.
"""

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from raft_tpu.serve import (
    ConsistentHashRing,
    DeadlineExceeded,
    Draining,
    EngineStopped,
    InvalidInput,
    Overloaded,
    ReplicaState,
    RouterConfig,
    ServeConfig,
    ServeEngine,
    ServeError,
    ServeRouter,
)
from raft_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.chaos


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


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    """This module builds dozens of short-lived engines (every replica
    rebuild is a fresh engine with per-instance jits by design, PR 8);
    the JAX persistent compilation cache dedupes their identical XLA
    compiles so the chaos ladder spends its budget on chaos, not
    recompiles. Process-global and harmless to later modules (it is the
    engine's own production boot tier, PR 7)."""
    from raft_tpu.serve import aot

    aot.enable_persistent_cache(
        str(tmp_path_factory.mktemp("router_jax_cache"))
    )


@pytest.fixture(scope="module")
def shared_artifact(tiny_model, tmp_path_factory):
    """ONE warmup artifact shared by every replica in this module — the
    production boot path (the fingerprint keys on config + weights, not
    replica identity): replicas and their rebuilds load the compiled
    program set instead of compiling it, so multi-engine tests stay fast
    and no replica ever compiles under flood."""
    from raft_tpu.serve import aot

    model, variables = tiny_model
    path = str(tmp_path_factory.mktemp("router_aot") / "shared.raftaot")
    builder = ServeEngine(model, variables, _config())
    aot.save_artifact(builder, path)
    return path


def _image(rng, hw=(45, 60)):
    return rng.integers(0, 255, (*hw, 3), dtype=np.uint8)


def _config(**kw):
    # the fallback whole-request engine keeps per-replica compiles small;
    # pool-mode drain/restart is covered explicitly where it matters
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
        heartbeat_interval_s=0.05,
        heartbeat_timeout_s=1.0,
        cooldown_s=0.5,
    )
    rkw.update(router_kw or {})
    return ServeRouter.from_factory(factory, n, RouterConfig(**rkw))


# ---------------------------------------------------------------------------
# Consistent-hash ring
# ---------------------------------------------------------------------------


class TestConsistentHashRing:
    def test_only_removed_members_keys_remap(self):
        """The affinity contract: dropping one of N replicas remaps
        ONLY the streams it owned (~1/N of them); every other stream
        keeps its home. Re-adding restores the original map exactly."""
        ring = ConsistentHashRing(64)
        for m in ("r0", "r1", "r2"):
            ring.add(m)
        keys = [str(i) for i in range(300)]
        before = {k: ring.lookup(k) for k in keys}
        ring.remove("r1")
        after = {k: ring.lookup(k) for k in keys}
        moved = [k for k in keys if before[k] != after[k]]
        # zero collateral remaps: a stream never migrates because an
        # UNRELATED replica left
        assert all(before[k] == "r1" for k in moved)
        assert 0.15 < len(moved) / len(keys) < 0.55   # ~1/3, hash jitter
        ring.add("r1")
        assert {k: ring.lookup(k) for k in keys} == before

    def test_deterministic_across_instances(self):
        a, b = ConsistentHashRing(32), ConsistentHashRing(32)
        for m in ("x", "y", "z"):
            a.add(m)
            b.add(m)
        assert [a.lookup(str(i)) for i in range(64)] == [
            b.lookup(str(i)) for i in range(64)
        ]

    def test_empty_and_membership(self):
        ring = ConsistentHashRing(8)
        assert ring.lookup("anything") is None
        ring.add("solo")
        assert ring.lookup("anything") == "solo"
        ring.remove("solo")
        ring.remove("never-added")            # tolerated
        assert ring.lookup("anything") is None

    def test_validation(self):
        with pytest.raises(ValueError):
            ConsistentHashRing(0)


# ---------------------------------------------------------------------------
# RouterConfig validation
# ---------------------------------------------------------------------------


class TestRouterConfig:
    @pytest.mark.parametrize(
        "kw",
        [
            {"virtual_nodes": 0},
            {"heartbeat_interval_s": 0},
            {"heartbeat_timeout_s": 0},
            {"error_rate_budget": 0.0},
            {"error_rate_budget": 1.5},
            {"error_window": 0},
            {"watchdog_trip_budget": 0},
            {"cooldown_s": -1},
            {"max_attempts": 0},
        ],
    )
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            RouterConfig(**kw)

    def test_defaults_valid(self):
        RouterConfig()


# ---------------------------------------------------------------------------
# ServeEngine drain seam (satellite: graceful close)
# ---------------------------------------------------------------------------


class TestEngineDrain:
    def test_drain_refuses_new_work_with_typed_error(self, tiny_model, rng):
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config())
        with eng:
            eng.submit(_image(rng), _image(rng))
            assert not eng.is_draining
            assert eng.drain(timeout=10.0)
            assert eng.is_draining
            assert eng.health()["draining"]
            with pytest.raises(Draining) as ei:
                eng.submit(_image(rng), _image(rng))
            assert ei.value.retryable
            assert ei.value.retry_after_ms == 50.0
            # Draining is an Overloaded: fleet backoff paths need no change
            assert isinstance(ei.value, Overloaded)

    @pytest.mark.parametrize("pool_capacity", [0, 2])
    def test_drain_finishes_inflight_fails_queued(
        self, tiny_model, rng, pool_capacity
    ):
        """The three-phase contract, both engine modes: in-flight
        dispatches finish, queued requests get the typed Draining, the
        engine quiesces (queue empty, pool retired)."""
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(pool_capacity=pool_capacity, queue_capacity=16),
        )
        inj = FaultInjector()
        inj.on("infer.slow_apply", when=lambda i, c: True, action=0.1)
        results, errors = [], []

        def client():
            try:
                results.append(eng.submit(_image(rng), _image(rng)))
            except ServeError as e:
                errors.append(e)

        with eng:
            eng.submit(_image(rng), _image(rng))       # compile first
            with inj.patch_engine(eng):
                threads = [
                    threading.Thread(target=client) for _ in range(10)
                ]
                for t in threads:
                    t.start()
                time.sleep(0.08)                       # let a batch dispatch
                assert eng.drain(timeout=30.0)
                for t in threads:
                    t.join()
            stats, health = eng.stats(), eng.health()
            # in-flight work finished; queued failed typed + retryable
            assert results, "in-flight dispatches must finish"
            assert errors, "queued requests must be failed by the drain"
            assert all(isinstance(e, Draining) for e in errors)
            assert stats["drained"] == len(errors)
            assert health["queue_depth"] == 0
            if pool_capacity:
                assert stats["pool"]["occupied"] == 0
            eng.close(graceful=True)

    def test_graceful_close_vs_stop(self, tiny_model, rng):
        """close(graceful=True) = drain + stop: pending work gets the
        retryable Draining, not the blunt EngineStopped."""
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(queue_capacity=16))
        inj = FaultInjector()
        inj.on("infer.slow_apply", when=lambda i, c: True, action=0.1)
        errors = []

        def client():
            try:
                eng.submit(_image(rng), _image(rng))
            except ServeError as e:
                errors.append(e)

        with inj.patch_engine(eng):
            eng.start()
            threads = [threading.Thread(target=client) for _ in range(6)]
            for t in threads:
                t.start()
            time.sleep(0.05)
            eng.close(graceful=True)
            for t in threads:
                t.join()
        assert all(
            isinstance(e, (Draining, Overloaded)) for e in errors
        ), errors

    def test_drain_unstarted_engine_is_harmless(self, tiny_model):
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config())
        assert eng.drain(timeout=1.0)
        assert eng.is_draining


class TestArtifactSmokeDegrade:
    def test_unrunnable_artifact_degrades_to_compile(
        self, tiny_model, shared_artifact, monkeypatch, rng
    ):
        """A replica fleet boots many engines from one artifact; an
        artifact whose executables load but cannot RUN (the persistent-
        cache round-trip symbol loss) must cost boot time, never
        readiness: the smoke check fails, the overlay is dropped, the
        boot recompiles and serves."""
        model, variables = tiny_model
        calls = {"n": 0}
        orig = ServeEngine._smoke

        def smoke_once_broken(self):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("Symbols not found (simulated)")
            return orig(self)

        monkeypatch.setattr(ServeEngine, "_smoke", smoke_once_broken)
        eng = ServeEngine(
            model, variables,
            _config(warmup=True, warmup_artifact=shared_artifact),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["programs_loaded"] == 0
            assert boot["programs_compiled"] > 0
            assert "failed to execute" in (boot["artifact_error"] or "")
            res = eng.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()
        assert calls["n"] == 2


# ---------------------------------------------------------------------------
# Router basics: boot, least-loaded dispatch, stream affinity, API surface
# ---------------------------------------------------------------------------


class TestRouterBasics:
    def test_boots_and_serves_single_engine_api(self, tiny_model, rng):
        router = _router(tiny_model, n=2)
        with router:
            res = router.submit(_image(rng), _image(rng))
            assert res.flow.shape == (45, 60, 2)
            assert np.isfinite(res.flow).all()
            health = router.health()
            assert health["healthy"] and health["healthy_count"] == 2
            assert all(
                s["state"] == ReplicaState.HEALTHY and s["generation"] == 1
                for s in health["replicas"].values()
            )
            stats = router.stats()
            assert stats["router"]["completed"] == 1
            assert stats["aggregate"]["completed"] == 1

    def test_load_spreads_across_replicas(
        self, tiny_model, shared_artifact, rng
    ):
        """Least-loaded + inflight tiebreak: a concurrent burst must not
        pile onto one replica while the other idles."""
        router = _router(tiny_model, n=2, artifact=shared_artifact)
        with router:
            with ThreadPoolExecutor(8) as pool:
                futs = [
                    pool.submit(
                        router.submit, _image(rng), _image(rng)
                    )
                    for _ in range(16)
                ]
                for f in futs:
                    assert np.isfinite(f.result().flow).all()
            per_engine = [
                st["completed"]
                for st in router.stats()["engines"].values()
            ]
            assert len(per_engine) == 2
            assert all(c > 0 for c in per_engine), per_engine

    def test_stream_affinity_one_home_cache_hits(self, tiny_model, rng):
        """All frames of one stream land on its consistent-hash home —
        the PR 4 shared-frame cache only works with stickiness."""
        router = _router(tiny_model, n=2)
        with router:
            with router.open_stream() as stream:
                results = [stream.submit(_image(rng)) for _ in range(4)]
                sid = stream.stream_id
                home = router._ring.lookup(str(sid))
                assert home is not None
                assert results[0].primed and results[0].flow is None
                for r in results[1:]:
                    assert not r.primed and np.isfinite(r.flow).all()
                homes = [
                    rep.replica_id
                    for rep in router.replicas
                    if sid in rep.engine._streams
                ]
                assert homes == [home]
                home_stats = router.stats()["engines"][home]
                assert home_stats["encode_cache_hits"] >= 3
            assert router.stats()["router"]["stream_remaps"] == 0

    def test_terminal_errors_never_rerouted(self, tiny_model, rng):
        router = _router(tiny_model, n=2)
        with router:
            with pytest.raises(InvalidInput):
                router.submit(
                    np.full((45, 60, 3), np.nan, np.float32), _image(rng)
                )
            assert router.stats()["router"]["rerouted"] == 0

    def test_duplicate_ids_and_empty_rejected(self, tiny_model):
        model, variables = tiny_model
        from raft_tpu.serve import Replica

        factory = lambda **kw: ServeEngine(model, variables, _config())
        with pytest.raises(ValueError):
            ServeRouter([])
        with pytest.raises(ValueError):
            ServeRouter([Replica("a", factory), Replica("a", factory)])
        with pytest.raises(ValueError):
            ServeRouter.from_factory(factory, 0)


# ---------------------------------------------------------------------------
# Eviction + cooldown re-admission
# ---------------------------------------------------------------------------


class TestEvictionReadmission:
    def test_dead_replica_rerouted_then_readmitted(
        self, tiny_model, shared_artifact, rng
    ):
        """The engine behind r0 stops abruptly mid-service. Submits keep
        succeeding (rescued/re-routed), the monitor evicts r0, and after
        cooldown it is rebuilt from the factory and re-admitted with a
        bumped generation (booting from the shared warmup artifact — the
        re-admission path replicas actually take in production)."""
        router = _router(tiny_model, n=2, artifact=shared_artifact)
        with router:
            r0 = router.replicas[0]
            router.submit(_image(rng), _image(rng))
            r0.engine.stop()                      # replica death
            for _ in range(4):
                res = router.submit(_image(rng), _image(rng))
                assert np.isfinite(res.flow).all()
            t0 = time.monotonic()
            while (
                router.stats()["router"]["readmissions"] < 1
                and time.monotonic() - t0 < 30.0
            ):
                time.sleep(0.02)
            stats = router.stats()["router"]
            assert stats["evictions"] >= 1
            assert stats["readmissions"] >= 1
            assert r0.generation >= 2              # rebuilt, not resumed
            assert r0.state == ReplicaState.HEALTHY
            assert "r0" in router._ring.members()
            # the rebuilt replica really serves
            res = router.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()

    def test_heartbeat_report_of_death_evicts(self, tiny_model, rng):
        """`router.heartbeat` chaos: the probe reports a dead worker
        (FaultInjector.replica_dead) — the router must evict on the
        report alone and stop feeding the replica."""
        router = _router(
            tiny_model, n=2, router_kw=dict(cooldown_s=60.0)
        )
        inj = FaultInjector()
        dead = [True]
        inj.on(
            "router.heartbeat",
            when=lambda i, ctx: dead[0] and ctx["replica"] == "r0",
            action=FaultInjector.replica_dead,
        )
        with router:
            with inj.patch_router(router):
                t0 = time.monotonic()
                while (
                    router.replicas[0].state != ReplicaState.UNHEALTHY
                    and time.monotonic() - t0 < 10.0
                ):
                    time.sleep(0.02)
                dead[0] = False
                r0 = router.replicas[0]
                assert r0.state == ReplicaState.UNHEALTHY
                assert "unhealthy" in (r0.last_evict_reason or "")
                assert "r0" not in router._ring.members()
                # traffic flows on without it
                res = router.submit(_image(rng), _image(rng))
                assert np.isfinite(res.flow).all()
            assert inj.fired["router.heartbeat"] >= 1

    def test_heartbeat_stall_evicts(self, tiny_model):
        """A probe that stalls past heartbeat_timeout_s IS the failure:
        'stops heartbeating' must evict even though nothing raised."""
        router = _router(
            tiny_model, n=2,
            router_kw=dict(
                heartbeat_timeout_s=0.2, cooldown_s=60.0,
                heartbeat_interval_s=0.05,
            ),
        )
        inj = FaultInjector()
        stalled = [True]
        inj.on(
            "router.heartbeat",
            when=lambda i, ctx: stalled[0] and ctx["replica"] == "r1",
            action=1.0,                       # probe sleeps 1s >> 0.2s
        )
        with router:
            with inj.patch_router(router):
                t0 = time.monotonic()
                while (
                    router.replicas[1].state != ReplicaState.UNHEALTHY
                    and time.monotonic() - t0 < 10.0
                ):
                    time.sleep(0.02)
                stalled[0] = False
            r1 = router.replicas[1]
            assert r1.state == ReplicaState.UNHEALTHY
            assert "heartbeat" in (r1.last_evict_reason or "")
            assert router.stats()["router"]["heartbeat_misses"] >= 1

    def test_error_rate_budget_evicts_on_dispatch_path(
        self, tiny_model, rng
    ):
        """`router.dispatch` chaos: r0 fails every dispatch. Requests
        re-route and succeed; once the outcome window fills past the
        budget, r0 is evicted without waiting for the monitor."""
        router = _router(
            tiny_model, n=2,
            router_kw=dict(
                error_window=4, error_rate_budget=0.5, cooldown_s=60.0,
            ),
        )
        inj = FaultInjector()
        inj.on(
            "router.dispatch",
            when=lambda i, ctx: ctx["replica"] == "r0",
            action=RuntimeError("injected: replica dispatch failure"),
        )
        with router:
            with inj.patch_router(router):
                for _ in range(8):
                    res = router.submit(_image(rng), _image(rng))
                    assert np.isfinite(res.flow).all()
            stats = router.stats()
            r0 = router.replicas[0]
            assert stats["router"]["rerouted"] >= 4
            assert r0.state == ReplicaState.UNHEALTHY
            assert "error rate" in (r0.last_evict_reason or "")
            assert stats["replicas"]["r0"]["errors"] >= 4

    def test_deadline_misses_do_not_evict(self, tiny_model, rng):
        """Deadline misses are load-correlated (queue wait), not replica
        faults: a burst of tight-deadline traffic must be tracked but
        kept OUT of the eviction error window — budgeting it would let a
        load spike evict every replica at once (a metastable total
        outage) instead of shedding."""
        router = _router(
            tiny_model, n=2,
            router_kw=dict(
                error_window=4, error_rate_budget=0.5, cooldown_s=60.0,
            ),
        )
        inj = FaultInjector()
        inj.on(
            "router.dispatch",
            when=lambda i, ctx: True,              # EVERY replica misses
            action=DeadlineExceeded("injected: caller deadline expired"),
        )
        with router:
            with inj.patch_router(router):
                for _ in range(8):                 # 2x the error window
                    with pytest.raises(DeadlineExceeded):
                        router.submit(_image(rng), _image(rng))
            stats = router.stats()
            assert stats["router"]["evictions"] == 0
            assert sum(
                s["deadline_misses"] for s in stats["replicas"].values()
            ) == 8
            for rep in router.replicas:
                assert rep.state == ReplicaState.HEALTHY
                assert rep.error_rate() == 0.0     # window untouched
            # the fleet still serves the moment the misses stop
            res = router.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()

    def test_readmit_yields_to_concurrent_restart(self, tiny_model):
        """_readmit's UNHEALTHY -> STARTING claim is a CAS under the
        router lock: once restart_replica has claimed the replica
        (DRAINING under the same lock), a racing monitor readmit must be
        a no-op rather than building a second engine for the replica."""
        router = _router(tiny_model, n=2, router_kw=dict(cooldown_s=60.0))
        with router:
            r0 = router.replicas[0]
            with router._lock:
                r0.state = ReplicaState.DRAINING   # restart_replica's claim
            gen = r0.generation
            router._readmit(r0)                    # racing monitor pass
            assert r0.generation == gen            # no rebuild happened
            assert r0.state == ReplicaState.DRAINING
            with router._lock:
                r0.state = ReplicaState.HEALTHY    # hand the claim back


# ---------------------------------------------------------------------------
# Cross-replica shedding
# ---------------------------------------------------------------------------


class TestCrossReplicaShed:
    def test_single_overloaded_replica_spills(self, tiny_model, rng):
        router = _router(tiny_model, n=2)
        with router:
            r0 = router.replicas[0]
            orig = r0.engine.submit
            r0.engine.submit = lambda *a, **kw: (_ for _ in ()).throw(
                Overloaded("full", retry_after_ms=500.0)
            )
            try:
                for _ in range(3):
                    res = router.submit(_image(rng), _image(rng))
                    assert np.isfinite(res.flow).all()
            finally:
                r0.engine.submit = orig
            assert router.stats()["router"]["shed_all_replicas"] == 0

    def test_all_overloaded_aggregates_min_retry_after(
        self, tiny_model, rng
    ):
        """Router-level Overloaded ONLY when every healthy replica shed,
        with retry_after = the minimum of the replicas' hints (the
        soonest any slot frees anywhere)."""
        router = _router(tiny_model, n=2)
        with router:
            originals = []
            for i, rep in enumerate(router.replicas):
                originals.append(rep.engine.submit)
                hint = 300.0 + 100.0 * i

                def _shed(*a, _h=hint, **kw):
                    raise Overloaded("full", retry_after_ms=_h)

                rep.engine.submit = _shed
            try:
                with pytest.raises(Overloaded) as ei:
                    router.submit(_image(rng), _image(rng))
            finally:
                for rep, orig in zip(router.replicas, originals):
                    rep.engine.submit = orig
            assert not isinstance(ei.value, Draining)
            assert ei.value.retryable
            assert ei.value.retry_after_ms == 300.0
            assert router.stats()["router"]["shed_all_replicas"] == 1
            # sheds are not faults: nobody was evicted for being full
            assert router.stats()["router"]["evictions"] == 0


# ---------------------------------------------------------------------------
# Draining restarts
# ---------------------------------------------------------------------------


class TestDrainingRestart:
    def test_restart_drops_zero_accepted_requests(
        self, tiny_model, shared_artifact, rng
    ):
        """Flood while r0 drains + restarts: every accepted request
        completes (queued work on the drained replica re-routes through
        its caller); the only allowed failures are retryable sheds."""
        router = _router(
            tiny_model, n=2, queue_capacity=16, artifact=shared_artifact,
        )
        results, errors = [], []

        def client():
            try:
                results.append(router.submit(_image(rng), _image(rng)))
            except Overloaded as e:
                errors.append(("shed", e))
            except ServeError as e:
                errors.append(("lost", e))

        with router:
            threads = [threading.Thread(target=client) for _ in range(20)]
            for t in threads:
                t.start()
            router.restart_replica("r0")
            for t in threads:
                t.join()
            lost = [e for tag, e in errors if tag == "lost"]
            assert not lost, lost
            assert results, "flood must complete requests through a drain"
            for res in results:
                assert np.isfinite(res.flow).all()
            stats = router.stats()["router"]
            assert stats["drains"] == 1 and stats["restarts"] == 1
            assert router.replicas[0].generation == 2
            assert router.replicas[0].state == ReplicaState.HEALTHY

    def test_stream_survives_synchronous_restart_with_reprime(
        self, tiny_model, shared_artifact, rng
    ):
        """Restart the stream's home between frames: the session
        survives, the rebuilt home has an empty encoder cache, so the
        next frame re-primes (one ``primed`` result) and flow resumes —
        no errors, no remap needed (the ring is restored before the next
        frame)."""
        router = _router(tiny_model, n=3, artifact=shared_artifact)
        with router:
            stream = router.open_stream()
            sid = stream.stream_id
            home = router._ring.lookup(str(sid))
            r_pre = [stream.submit(_image(rng)) for _ in range(3)]
            assert r_pre[0].primed and not r_pre[1].primed
            router.restart_replica(home)
            r_post = [stream.submit(_image(rng)) for _ in range(3)]
            # the rebuilt home lost its cache: fresh prime, then flow
            assert r_post[0].primed, "rebuilt home must re-prime"
            assert not r_post[-1].primed
            assert np.isfinite(r_post[-1].flow).all()
            # affinity preserved: the very same replica is home again
            assert router._ring.lookup(str(sid)) == home
            stream.close()

    def test_stream_migrates_during_drain_window(
        self, tiny_model, shared_artifact, rng
    ):
        """Frames submitted WHILE the home drains migrate to the interim
        ring home (counted as a remap), re-prime there, and flow on —
        the live-migration half of 'streams survive a draining
        restart'."""
        model, variables = tiny_model
        scfg = _config(warmup=True, warmup_artifact=shared_artifact)
        rebuild_gate = threading.Event()

        def factory(**overrides):
            if not rebuild_gate.is_set():
                rebuild_gate.wait(timeout=30.0)   # hold DRAINING open
            return ServeEngine(model, variables, scfg)

        # first boots must not block on the gate
        rebuild_gate.set()
        router = ServeRouter.from_factory(
            factory, 3,
            RouterConfig(heartbeat_interval_s=0.05, cooldown_s=60.0),
        )
        with router:
            stream = router.open_stream()
            sid = stream.stream_id
            home = router._ring.lookup(str(sid))
            assert stream.submit(_image(rng)).primed
            assert not stream.submit(_image(rng)).primed
            rebuild_gate.clear()                   # next rebuild blocks
            restarter = threading.Thread(
                target=router.restart_replica, args=(home,), daemon=True,
            )
            restarter.start()
            t0 = time.monotonic()
            while (
                router._by_id[home].state != ReplicaState.DRAINING
                and time.monotonic() - t0 < 10.0
            ):
                time.sleep(0.005)
            # the home is draining: frames must flow on an interim home
            mid = [stream.submit(_image(rng)) for _ in range(3)]
            assert any(r.primed for r in mid), "migration must re-prime"
            assert not mid[-1].primed
            assert np.isfinite(mid[-1].flow).all()
            interim = router._ring.lookup(str(sid))
            assert interim is not None and interim != home
            rebuild_gate.set()
            restarter.join(timeout=60.0)
            assert not restarter.is_alive()
            stats = router.stats()["router"]
            assert stats["stream_remaps"] >= 1
            # drain over: the original home owns the stream again
            assert router._ring.lookup(str(sid)) == home
            post = [stream.submit(_image(rng)) for _ in range(2)]
            assert post[0].primed and not post[1].primed
            # the interim home's cached frame must NOT survive the remap
            # back: if the home drains again later, the stream must
            # re-prime on the interim replica, never silently pair a new
            # frame against the stale one from this drain window
            assert sid not in router._by_id[interim].engine._streams
            stream.close()
            # close clears every home the stream ever touched
            assert sid not in router._by_id[home].engine._streams

    def test_restart_swaps_config_through_factory(self, tiny_model, rng):
        """The rolling-reload seam: restart_replica(**overrides) reaches
        the replica factory, so config (or checkpoint) swaps ride the
        same drain path."""
        router = _router(tiny_model, n=2)
        with router:
            assert router.replicas[0].engine.config.ladder == (2, 1)
            router.restart_replica("r0", ladder=(1,))
            assert router.replicas[0].engine.config.ladder == (1,)
            assert router.replicas[1].engine.config.ladder == (2, 1)
            res = router.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()


# ---------------------------------------------------------------------------
# Acceptance: replica death mid-flood + draining restart + live streams
# ---------------------------------------------------------------------------


class TestAcceptanceScenario:
    def test_flood_replica_death_and_drain(
        self, tiny_model, shared_artifact, rng
    ):
        """ISSUE 9 acceptance: 3 artifact-booted replicas under a
        4x-capacity flood with live stream traffic; one replica dies
        mid-run, another is drain-restarted. Zero accepted requests lost
        (every failure is a retryable shed), streams survive with
        re-primes, the dead replica is evicted, and the tier ends
        healthy."""
        router = _router(
            tiny_model, n=3, queue_capacity=8, artifact=shared_artifact,
            router_kw=dict(cooldown_s=60.0),
        )
        results, sheds, lost = [], [], []
        stream_frames = {"ok": 0, "primed": 0}
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
                    with lock:
                        sheds.append(e)
                    # honor the hint (capped): a shed client that spins
                    # starves single-core CI instead of offering load
                    stop.wait(min(e.retry_after_ms, 100.0) / 1e3)
                except ServeError as e:
                    with lock:
                        lost.append(e)

        def stream_client(i):
            r = np.random.default_rng(200 + i)
            with router.open_stream() as stream:
                while not stop.is_set():
                    try:
                        res = stream.submit(
                            _image(r), deadline_ms=60000.0
                        )
                        with lock:
                            stream_frames[
                                "primed" if res.primed else "ok"
                            ] += 1
                    except Overloaded as e:
                        stop.wait(min(e.retry_after_ms, 100.0) / 1e3)
                    except ServeError as e:
                        with lock:
                            lost.append(e)

        with router:
            flood = 4 * 8                                 # 4x one queue
            threads = [
                threading.Thread(target=client, args=(i,))
                for i in range(flood - 2)
            ] + [
                threading.Thread(target=stream_client, args=(i,))
                for i in range(2)
            ]
            for t in threads:
                t.start()
            time.sleep(0.6)
            router.replicas[0].engine.stop()              # death mid-flood
            time.sleep(0.6)
            victim = next(
                rep.replica_id for rep in router.replicas[1:]
                if rep.state == ReplicaState.HEALTHY
            )
            router.restart_replica(victim)                # rolling restart
            time.sleep(0.6)
            stop.set()
            for t in threads:
                t.join(timeout=90.0)
            stats = router.stats()
            health = router.health()

        # zero lost accepted requests: the only failures are retryable
        assert not lost, [repr(e) for e in lost[:5]]
        assert results, "the flood must complete requests"
        for res in results:
            assert np.isfinite(res.flow).all()
        # streams really flowed and survived the churn (re-primes are the
        # migration fingerprint, not failures)
        assert stream_frames["ok"] >= 1
        # the dead replica was evicted; the drained one came back
        assert stats["router"]["evictions"] >= 1
        assert stats["router"]["restarts"] == 1
        assert health["healthy"] and health["healthy_count"] >= 2
        # the router really re-routed around the death/drain
        assert (
            stats["router"]["rerouted"] >= 1
            or stats["router"]["evictions"] >= 1
        )


# ---------------------------------------------------------------------------
# serve_bench 1-vs-N replica A/B (CPU smoke; PR 8 overhead convention)
# ---------------------------------------------------------------------------


class TestReplicaBenchAB:
    def test_replica_ab_smoke(self, shared_artifact):
        """The acceptance A/B: 1 vs 3 replicas at equal per-replica
        config, EVERY engine booted from the module's shared warmup
        artifact so both sides measure serving, not compiling (the
        bench tiny model is this module's architecture, so the
        fingerprint matches — asserted via the boot source). What is
        held is structural: three replicas, one artifact behind all four
        engines, every replica served. The throughput ratio is reported
        and not asserted: on a CPU beside five other xdist workers it is
        not a fact about the system (it read under 2.0 in the driver's
        run of PR 31's tree; the chip's numbers are the benchmark's)."""
        import scripts.serve_bench as sb

        report = sb.main([
            "--tiny", "--replicas", "3", "--duration", "1.5",
            "--clients", "6", "--max-batch", "2", "--ladder", "2,1",
            "--pool-capacity", "0", "--queue-capacity", "16",
            "--warmup-artifact", shared_artifact,
        ])
        assert report["replicas"] == 3
        # ONE artifact really warmed all four engines (1-side + 3 replicas)
        assert set(report["boot"].values()) == {"artifact"}, report["boot"]
        ab = report["replica_ab"]
        assert ab["throughput_rps_1"] > 0 and ab["throughput_rps_n"] > 0
        # every replica actually served
        assert all(c > 0 for c in ab["per_replica_completed"])
        assert len(ab["per_replica_completed"]) == 3

    def test_load_model_classes_and_slo_report(self):
        """The realistic load model: bursty arrivals, mixed
        pairwise/stream/bucket traffic classes, and a per-class SLO
        block (p99 vs deadline, SLO miss rate, shed rate) in the
        report."""
        import scripts.serve_bench as sb

        report = sb.main([
            "--tiny", "--duration", "1.5", "--clients", "6",
            "--max-batch", "2", "--ladder", "2,1",
            "--pool-capacity", "0", "--no-warmup",
            "--queue-capacity", "16",
            "--class-mix", "0.5,0.25,0.25", "--bucket2", "64x80",
            "--arrival", "bursty", "--arrival-rate", "8",
            "--class-deadline-ms", "30000,30000,45000",
        ])
        assert report["arrival"] == "bursty"
        assert report["class_mix"] == [0.5, 0.25, 0.25]
        classes = report["classes"]
        assert set(classes) == {"pairwise", "stream", "bucket"}
        for cls, block in classes.items():
            assert block["requests"] > 0, (cls, block)
            for key in (
                "p99_ms", "deadline_ms", "slo_p99_met", "slo_miss_rate",
                "shed_rate",
            ):
                assert key in block
        assert classes["bucket"]["deadline_ms"] == 45000.0
        # the bucket class really ran at the second resolution: the
        # stream class primed at least its first frame
        assert classes["stream"]["primed"] >= 1
