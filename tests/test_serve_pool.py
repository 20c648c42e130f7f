"""Resident GRU-iteration pool suite (iteration-level continuous batching).

The pooled engine (``ServeConfig.pool_capacity > 0``, the default)
dispatches one GRU iteration across a slot array of per-request recurrent
state instead of whole requests. This file proves, on the CPU tiny model:

  * the model-level split (``begin_pair`` / ``begin_refinement`` /
    ``iterate_step`` / ``finalize_flow``) decomposes ``iterate`` exactly;
  * pooled serving with MIXED per-request iteration counts is allclose to
    the whole-batch ``iterate`` per request — including a stream-session
    request refining from cached frame features;
  * the serving fault ladder (deadline, shed, degrade, poison quarantine,
    watchdog) holds at slot granularity, with slot-isolated quarantine
    (no singles retry needed) and deadline-driven mid-flight early exit;
  * the compiled-program set stays closed after warmup;
  * ``serve_bench --pool-capacity`` runs a pooled engine for a handful of
    ticks under ``JAX_PLATFORMS=cpu``.

Float tolerance note: N pooled single-iteration dispatches vs one
N-length scan is the scan-vs-unrolled XLA fusion drift (the PR 5 class),
amplified per iteration by the coordinate-dependent correlation lookup —
measured ~2e-4 at N=1 growing to ~5e-3 at N=3 on the random-init tiny
net, hence the 1e-2 golden tolerance at N<=3.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from raft_tpu.serve import (
    DeadlineExceeded,
    InvalidInput,
    MicroBatchQueue,
    Overloaded,
    PoisonedInput,
    Request,
    ServeConfig,
    ServeEngine,
    ServeError,
)
from raft_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.chaos


def _tiny_model(corr_block=None):
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
    model = build_raft(
        cfg, corr_block=corr_block or CorrBlock(num_levels=2, radius=3)
    )
    return model, init_variables(model)


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


def _image(rng, hw=(45, 60)):
    return rng.integers(0, 255, hw + (3,), dtype=np.uint8)


def _config(**kw):
    base = dict(
        buckets=((48, 64),),
        ladder=(3, 2, 1),
        max_batch=4,
        pool_capacity=3,
        queue_capacity=8,
        max_wait_ms=4.0,
        default_deadline_ms=30000.0,
        cooldown_batches=1,
        recover_after=1,
        # the shared engine must not degrade spontaneously under test
        # concurrency: parity tests need targets honored exactly
        high_watermark=1.0,
        low_watermark=0.25,
    )
    base.update(kw)
    return ServeConfig(**base)


def _oracle(model, variables, im1, im2, iters, hw=(45, 60)):
    """Whole-batch ``iterate`` reference for one raw pair at ``iters``."""
    from raft_tpu.inference import FlowEstimator
    from raft_tpu.serve.bucketing import BucketRouter

    p1 = BucketRouter.pad_to(FlowEstimator._normalize(im1), (48, 64))
    p2 = BucketRouter.pad_to(FlowEstimator._normalize(im2), (48, 64))
    flow = np.asarray(
        model.apply(
            variables, p1, p2, train=False, num_flow_updates=iters,
            emit_all=False,
        )
    )[0]
    return flow[: hw[0], : hw[1]]


@pytest.fixture(scope="module")
def engine(tiny_model):
    """One started pooled engine shared by the cheap tests."""
    model, variables = tiny_model
    eng = ServeEngine(model, variables, _config())
    with eng:
        yield eng


# ---------------------------------------------------------------------------
# Config + queue: slot-granularity knobs
# ---------------------------------------------------------------------------


class TestPoolConfig:
    @pytest.mark.parametrize(
        "kw", [{"pool_capacity": -1}, {"pool_min_iters": 0}]
    )
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            ServeConfig(**kw)

    def test_resolved_admit_ladder(self):
        assert ServeConfig(
            max_batch=8, pool_capacity=3
        ).resolved_admit_ladder() == (1, 2, 3)
        assert ServeConfig(
            max_batch=8, pool_capacity=8
        ).resolved_admit_ladder() == (1, 2, 4, 8)
        assert ServeConfig(
            max_batch=2, pool_capacity=8
        ).resolved_admit_ladder() == (1, 2)
        assert ServeConfig(
            max_batch=8, pool_capacity=1
        ).resolved_admit_ladder() == (1,)

    def test_queue_cap_selects_seed_with_headroom(self):
        """A bucket whose pool is full must not head-of-line-block
        admission into another bucket (slot-granularity admission)."""
        q = MicroBatchQueue(8)
        t = time.monotonic()
        full = Request(0, (48, 64), None, None, (45, 60), t + 1.0)
        free = Request(1, (64, 80), None, None, (60, 75), t + 5.0)
        q.put(full)
        q.put(free)
        headroom = {(48, 64): 0, (64, 80): 2}
        batch = q.next_batch(
            4, 0.0, poll=0.0, cap=lambda b, k: headroom[b]
        )
        assert [r.rid for r in batch] == [1]     # EDF among admittable only
        assert q.depth() == 1                    # the blocked one stays
        # headroom bounds the batch size for the seed's class
        q.put(Request(2, (64, 80), None, None, (60, 75), t + 5.0))
        q.put(Request(3, (64, 80), None, None, (60, 75), t + 5.0))
        headroom[(64, 80)] = 1
        batch = q.next_batch(4, 0.0, poll=0.0, cap=lambda b, k: headroom[b])
        assert len(batch) == 1


# ---------------------------------------------------------------------------
# Model-level: the iterate_step split is an exact decomposition of iterate
# ---------------------------------------------------------------------------


def _assert_stepwise_matches_scan(model, variables, rng):
    """N ``iterate_step`` calls on ``begin_pair``'s state reproduce the
    N-step scan; returns the state (for what it holds)."""
    im1 = (rng.random((2, 48, 64, 3)).astype(np.float32)) * 2 - 1
    im2 = (rng.random((2, 48, 64, 3)).astype(np.float32)) * 2 - 1
    state = model.apply(variables, im1, im2, train=False,
                        method="begin_pair")
    for n in (1, 2, 3):
        state = model.apply(variables, state, train=False,
                            method="iterate_step")
        got = np.asarray(
            model.apply(
                variables, state["coords1"], state["hidden"],
                train=False, method="finalize_flow",
            )
        )
        want = np.asarray(
            model.apply(
                variables, im1, im2, train=False, num_flow_updates=n,
                emit_all=False,
            )
        )
        np.testing.assert_allclose(
            got, want, rtol=1e-2, atol=1e-2,
            err_msg=f"iterate_step diverged from the scan at N={n}",
        )
    return state


class TestIterateStepParity:
    def test_stepwise_matches_scanned_iterate(self, tiny_model, rng):
        _assert_stepwise_matches_scan(*tiny_model, rng)

    def test_begin_refinement_matches_begin_pair(self, tiny_model, rng):
        """The stream-admission path (cached per-frame features) builds
        the same state as the pairwise path."""
        import jax

        model, variables = tiny_model
        im1 = (rng.random((1, 48, 64, 3)).astype(np.float32)) * 2 - 1
        im2 = (rng.random((1, 48, 64, 3)).astype(np.float32)) * 2 - 1
        via_pair = model.apply(variables, im1, im2, train=False,
                               method="begin_pair")
        f1, _ = model.apply(variables, im1, train=False,
                            method="encode_frame")
        f2, _ = model.apply(variables, im2, train=False,
                            method="encode_frame")
        _, ctx = model.apply(variables, im1, train=False,
                             method="encode_frame")
        via_feats = model.apply(variables, f1, f2, ctx, train=False,
                                method="begin_refinement")
        for a, b in zip(jax.tree_util.tree_leaves(via_pair),
                        jax.tree_util.tree_leaves(via_feats)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )


# ---------------------------------------------------------------------------
# Pooled serving: mixed iteration counts, golden parity, counters
# ---------------------------------------------------------------------------


def _assert_mixed_iters_match_oracle(engine, model, variables, rng):
    """Requests with different iteration targets co-resident in the
    pool each get flow allclose to the whole-batch ``iterate`` at
    exactly their own target."""
    asks = [3, 2, 1, 3, 2, 1]
    pairs = [(_image(rng), _image(rng)) for _ in asks]
    with ThreadPoolExecutor(len(asks)) as pool:
        futs = [
            pool.submit(engine.submit, a, b, num_flow_updates=n)
            for (a, b), n in zip(pairs, asks)
        ]
        results = [f.result() for f in futs]
    for (a, b), n, res in zip(pairs, asks, results):
        assert res.num_flow_updates == n     # honored exactly
        want = _oracle(model, variables, a, b, n)
        # The absolute tolerance is what fp32 can promise at this
        # field's magnitude, not a fixed pixel count: the random-init
        # net emits flows up to ~200 px, and a ONE-ulp nudge of the
        # input already moves the 3-iteration oracle by up to
        # 0.014 px (6.8e-5 of the field's max — the GRU iterations
        # and the 8x convex upsample amplify rounding noise). The
        # pool (slot-wise steps, batch 3) and the oracle (one scan,
        # batch 1) are the same fp32 math reassociated, so they are
        # held to 3x that floor: 2e-4 of the largest flow value
        # (0.04 px at 200 px, 0.017 px for a 1-iteration 85 px field).
        np.testing.assert_allclose(
            res.flow, want, rtol=1e-2,
            atol=2e-4 * float(np.abs(want).max()),
            err_msg=f"pooled request at {n} iters diverged",
        )


class TestPooledServing:
    def test_serves_finite_flow_with_pool_stats(self, engine, rng):
        res = engine.submit(_image(rng), _image(rng))
        assert res.flow.shape == (45, 60, 2)
        assert np.isfinite(res.flow).all()
        assert res.num_flow_updates == 3         # full-quality target
        assert not res.early_exit
        stats = engine.stats()
        assert stats["pool_ticks"] > 0
        assert stats["pool_admitted"] >= 1
        assert stats["pool"]["capacity"] == 3
        assert stats["dispatched_slot_iters"] > 0
        assert 0.0 <= stats["padding_waste"] <= 1.0
        assert engine.health()["healthy"]

    def test_validates_per_request_iters(self, engine, rng):
        with pytest.raises(InvalidInput, match="num_flow_updates"):
            engine.submit(_image(rng), _image(rng), num_flow_updates=0)
        with pytest.raises(InvalidInput, match="num_flow_updates"):
            engine.submit(_image(rng), _image(rng), num_flow_updates=4)

    def test_mixed_iters_golden_parity(self, engine, tiny_model, rng):
        """The acceptance golden: requests with different iteration
        targets co-resident in the pool each get flow allclose to the
        whole-batch ``iterate`` at exactly their own target."""
        _assert_mixed_iters_match_oracle(engine, *tiny_model, rng)

    def test_stream_session_golden_parity(self, engine, tiny_model, rng):
        """A stream request refining from CACHED frame features through
        the pool matches the pairwise whole-batch oracle."""
        model, variables = tiny_model
        frames = [_image(rng) for _ in range(4)]
        with engine.open_stream() as stream:
            first = stream.submit(frames[0])
            assert first.primed and first.flow is None
            for t in range(1, len(frames)):
                res = stream.submit(frames[t])
                want = _oracle(
                    model, variables, frames[t - 1], frames[t],
                    res.num_flow_updates,
                )
                np.testing.assert_allclose(
                    res.flow, want, rtol=1e-2, atol=1e-2,
                    err_msg=f"pooled stream pair {t} diverged",
                )
        stats = engine.stats()
        assert stats["encode_cache_hits"] >= 3

    def test_early_exit_iters_saved_counter(self, engine, rng):
        before = engine.stats()["early_exit_iters_saved"]
        res = engine.submit(_image(rng), _image(rng), num_flow_updates=1)
        assert res.num_flow_updates == 1
        after = engine.stats()["early_exit_iters_saved"]
        assert after - before == 2               # ladder[0]=3 minus 1 run

    def test_ttfd_reported(self, engine):
        ttfd = engine.stats()["pool"]["ttfd_p50_ms"]
        assert ttfd is not None and ttfd >= 0.0



# ---------------------------------------------------------------------------
# Chaos: the fault ladder at slot granularity
# ---------------------------------------------------------------------------


class TestPoolChaos:
    def test_worker_survives_injected_admission_failure(self, engine, rng):
        inj = FaultInjector()
        inj.on("infer.slow_apply", when=0, action=ValueError("injected: boom"))
        before = engine.stats()["worker_errors"]
        with inj.patch_engine(engine):
            with pytest.raises(ServeError, match="pool admission failed"):
                engine.submit(_image(rng), _image(rng))
            res = engine.submit(_image(rng), _image(rng))
        assert np.isfinite(res.flow).all()
        assert engine.health()["healthy"]
        assert engine.stats()["worker_errors"] == before + 1

    def test_caller_deadline_beats_stalled_pool(self, engine, rng):
        inj = FaultInjector()
        steps = {"n": 0}

        def first_pool_step(i, ctx):
            # the site index counts every slow_apply fire (admission,
            # finalize...); count pool_step fires separately
            if ctx.get("stage") != "pool_step":
                return False
            steps["n"] += 1
            return steps["n"] == 1

        inj.on("infer.slow_apply", when=first_pool_step, action=0.6)
        with inj.patch_engine(engine):
            with pytest.raises(DeadlineExceeded):
                engine.submit(_image(rng), _image(rng), deadline_ms=150)
        assert engine.health()["healthy"]
        assert np.isfinite(engine.submit(_image(rng), _image(rng)).flow).all()

    def test_poisoned_request_quarantined_slot_isolated(self, engine, rng):
        """Slots are isolated by construction (inference is per-sample end
        to end): a poisoned request is quarantined directly from the pool,
        no singles retry, co-resident requests unaffected."""
        inj = FaultInjector()
        seen = {}

        def first_rid(i, ctx):
            seen.setdefault("rid", ctx["rid"])
            return ctx["rid"] == seen["rid"]

        inj.on("infer.nan_flow", when=first_rid, action=FaultInjector.nan_flow)
        before = engine.stats()
        n = 4
        with inj.patch_engine(engine):
            with ThreadPoolExecutor(n) as pool:
                futs = [
                    pool.submit(engine.submit, _image(rng), _image(rng))
                    for _ in range(n)
                ]
                outcomes = []
                for f in futs:
                    try:
                        outcomes.append(f.result())
                    except PoisonedInput as e:
                        outcomes.append(e)
        poisoned = [o for o in outcomes if isinstance(o, PoisonedInput)]
        served = [o for o in outcomes if not isinstance(o, Exception)]
        assert len(poisoned) == 1 and len(served) == n - 1
        assert all(np.isfinite(r.flow).all() for r in served)
        after = engine.stats()
        assert after["quarantined"] - before["quarantined"] == 1
        assert after["retried_singles"] == before["retried_singles"]
        assert seen["rid"] in after["quarantined_rids"]
        assert engine.health()["healthy"]

    def test_poisoned_stream_frame_invalidates_session(self, engine, rng):
        inj = FaultInjector()
        seen = {}

        def first_rid(i, ctx):
            seen.setdefault("rid", ctx["rid"])
            return ctx["rid"] == seen["rid"]

        with engine.open_stream() as stream:
            assert stream.submit(_image(rng)).primed
            assert np.isfinite(stream.submit(_image(rng)).flow).all()
            with inj.patch_engine(engine):
                inj.on(
                    "infer.nan_flow", when=first_rid,
                    action=FaultInjector.nan_flow,
                )
                with pytest.raises(PoisonedInput):
                    stream.submit(_image(rng))
            res = stream.submit(_image(rng))
            assert res.primed and res.flow is None   # re-primed, no gap pair
            assert np.isfinite(stream.submit(_image(rng)).flow).all()
        assert engine.stats()["stream_invalidations"] >= 1
        assert engine.health()["healthy"]

    def test_flood_sheds_degrades_and_recovers(self, tiny_model, rng):
        """The PR 3 ladder at slot granularity: a 4x-capacity flood sheds
        retryably, degradation assigns lower per-request targets at
        admission, and the level recovers after drain."""
        model, variables = tiny_model
        cfg = _config(
            high_watermark=0.5, default_deadline_ms=60000.0, pool_capacity=2
        )
        eng = ServeEngine(model, variables, cfg)
        flood = 4 * cfg.queue_capacity
        results, errors = [], []

        def client(im1, im2):
            try:
                results.append(eng.submit(im1, im2))
            except ServeError as e:
                errors.append(e)

        with eng:
            with ThreadPoolExecutor(flood) as pool:
                pairs = [(_image(rng), _image(rng)) for _ in range(flood)]
                futs = [pool.submit(client, a, b) for a, b in pairs]
                for f in futs:
                    f.result()
            for _ in range(4):                 # calm trickle drives recovery
                results.append(eng.submit(_image(rng), _image(rng)))
            stats = eng.stats()
            health = eng.health()
        assert results
        for res in results:
            assert np.isfinite(res.flow).all()
            assert res.num_flow_updates >= 1
        shed = [e for e in errors if isinstance(e, Overloaded)]
        assert shed and len(shed) == len(errors)   # typed sheds only
        assert all(e.retryable and e.retry_after_ms > 0 for e in shed)
        degr = stats["degradation"]
        assert degr["steps_down"] >= 1, degr
        assert degr["steps_up"] >= 1, degr
        assert degr["level"] == 0
        assert any(r.degraded for r in results)    # served at reduced targets
        assert stats["expired"] == 0 and stats["worker_errors"] == 0
        assert stats["completed"] == len(results)
        assert health["healthy"] and health["queue_depth"] == 0

    def test_deadline_early_exit_returns_anytime_flow(self, tiny_model, rng):
        """A pooled request whose deadline cannot fit its remaining
        iterations is finalized early with valid anytime flow instead of
        expiring worthlessly."""
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(ladder=(8, 1), pool_capacity=1, pipeline_depth=1),
        )
        inj = FaultInjector()
        inj.on(
            "infer.slow_apply",
            when=lambda i, ctx: ctx.get("stage") == "pool_step",
            action=0.3,
        )
        with eng:
            eng.submit(_image(rng), _image(rng), num_flow_updates=1)  # compile
            with inj.patch_engine(eng):
                res = eng.submit(_image(rng), _image(rng), deadline_ms=1500)
            assert res.early_exit
            assert res.exit_reason == "deadline"      # ISSUE 12 split
            assert 1 <= res.num_flow_updates < 8
            assert np.isfinite(res.flow).all()
            stats = eng.stats()
        assert stats["early_exits_deadline"] >= 1
        assert stats["early_exit_iters_saved_deadline"] >= 1
        assert stats["expired"] == 0

    def test_watchdog_trip_resets_pool_worker_survives(self, tiny_model, rng):
        model, variables = tiny_model
        # warmup so the only thing that can exceed the device deadline is
        # the injected stall (a first-dispatch compile would also trip it)
        eng = ServeEngine(
            model, variables,
            _config(
                apply_timeout_s=0.2, pool_capacity=1, ladder=(2, 1),
                warmup=True, stream_cache_size=0,
            ),
        )
        inj = FaultInjector()
        steps = {"n": 0}

        def first_pool_step(i, ctx):
            if ctx.get("stage") != "pool_step":
                return False
            steps["n"] += 1
            return steps["n"] == 1

        inj.on("infer.slow_apply", when=first_pool_step, action=0.6)
        with eng:
            with inj.patch_engine(eng):
                with pytest.raises(DeadlineExceeded, match="device execution"):
                    eng.submit(_image(rng), _image(rng))
            assert eng.health()["watchdog_trips"] >= 1
            assert eng.health()["healthy"]
            # the worker is abandoned inside the stalled dispatch until it
            # returns; the pool reset lands when it does
            deadline = time.monotonic() + 5.0
            while (
                eng.stats()["pool_resets"] == 0
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert eng.stats()["pool_resets"] >= 1
            res = eng.submit(_image(rng), _image(rng))  # pool recovered
            assert np.isfinite(res.flow).all()


# ---------------------------------------------------------------------------
# Warmup: the pooled program set is closed
# ---------------------------------------------------------------------------


class TestDeferredRetirement:
    """PR 37: a retirement is dispatched before the loop's admission (its
    slots free at once) and read after the loop's tick. What must hold as
    before: every request finishes once, drain waits for what is parked,
    a session's next frame is planned only after its last pair settled,
    no new host sync, and the same answers."""

    @staticmethod
    def _counting(eng, names):
        calls = {n: 0 for n in names}
        for n in names:
            real = getattr(eng, n)

            def counted(*a, _real=real, _n=n, **k):
                calls[_n] += 1
                return _real(*a, **k)

            setattr(eng, n, counted)
        return calls

    @pytest.mark.parametrize("how", ["drain", "close"])
    def test_drain_waits_for_a_parked_retirement(self, tiny_model, rng, how):
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(
            pool_capacity=2, max_batch=2, ladder=(2, 1), stream_cache_size=0,
        ))
        parked, release = threading.Event(), threading.Event()
        real = eng._pool_settle

        def settle(deferred, sessions=None):
            if eng._retiring and not release.is_set():
                parked.set()
                release.wait(60.0)
            return real(deferred, sessions)

        eng._pool_settle = settle
        eng.start()
        fired, out = [], {}
        try:
            reqs = eng.submit_many(
                [{"image1": _image(rng), "image2": _image(rng)}
                 for _ in range(2)]
            )
            for r in reqs:
                r.add_done_callback(lambda r_: fired.append(r_.rid))
            assert parked.wait(120.0)
            # both retired in one cohort: slots free, flows not yet read
            assert all(p.occupied_count() == 0 for p in eng._pools.values())
            assert not eng._quiesced()

            def quiesce():
                if how == "drain":
                    out["ok"] = eng.drain(timeout=60.0)
                else:
                    eng.close(graceful=True, timeout=60.0)
                    out["ok"] = True

            t = threading.Thread(target=quiesce)
            t.start()
            time.sleep(0.3)
            assert t.is_alive() and not any(r.done for r in reqs)
            release.set()
            t.join(60.0)
            assert out == {"ok": True}
        finally:
            release.set()
            eng.stop()
        for r in reqs:
            assert r.error is None and np.isfinite(r.result.flow).all()
        assert sorted(fired) == sorted(r.rid for r in reqs)   # once each

    def test_a_sessions_next_frame_waits_for_its_parked_pair(
        self, tiny_model, rng, monkeypatch
    ):
        """Two frames of one session queued (the first one's caller still
        waiting), the first pair poisoned: its retirement is settled —
        quarantined, the session invalidated — before the second frame is
        planned, so the second frame primes instead of pairing with what
        the poisoned pair left, and the one after pairs cold."""
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(
            pool_capacity=2, max_batch=2, ladder=(2, 1), stream_cache_size=2,
            stream_warm_start=True,
        ))
        frames = [_image(rng) for _ in range(5)]
        inj = FaultInjector()
        seen, out, planned = {}, {}, []
        with eng:
            stream = eng.open_stream()
            sid = stream.stream_id
            assert stream.submit(frames[0]).primed
            assert np.isfinite(stream.submit(frames[1]).flow).all()
            cache = eng._stream_cache
            real_begin, real_plan = cache.begin_frame, cache.plan
            real_retire = eng._pool_retire

            def third():
                # the caller of the poisoned pair still waits: queue the
                # session's next frame beside it
                monkeypatch.setattr(
                    cache, "begin_frame",
                    lambda s, b, hw: (cache.end_frame(s), real_begin(s, b, hw))[1],
                )
                out["third"] = stream.submit(frames[3])

            def retire(pool):
                real_retire(pool)
                held = [r for co in eng._retiring for r in co.live
                        if r.stream_id == sid]
                if held and "first" not in seen:
                    seen["first"] = held[0]
                    threading.Thread(target=third, daemon=True).start()
                    t_end = time.monotonic() + 10.0
                    while not eng._queue.depth() and time.monotonic() < t_end:
                        time.sleep(0.001)
                    assert eng._queue.depth() == 1

            def plan(live, rung):
                first = seen.get("first")
                planned.extend(
                    (r.rid, first is not None and first.done) for r in live
                )
                return real_plan(live, rung)

            monkeypatch.setattr(cache, "plan", plan)
            with inj.patch_engine(eng):
                inj.on(
                    "infer.nan_flow",
                    when=lambda i, ctx: ctx["rid"] == seen["first"].rid,
                    action=FaultInjector.nan_flow,
                )
                monkeypatch.setattr(eng, "_pool_retire", retire)
                with pytest.raises(PoisonedInput):
                    stream.submit(frames[2])
                t_end = time.monotonic() + 30.0
                while "third" not in out and time.monotonic() < t_end:
                    time.sleep(0.01)
            assert out["third"].primed and out["third"].flow is None
            # planned once the poisoned pair was finished and forgotten
            third_rid = out["third"].rid
            assert [done for rid, done in planned if rid == third_rid] == [True]
            last = stream.submit(frames[4])
            assert not last.warm_started and np.isfinite(last.flow).all()
            stream.close()
        st = eng.stats()
        assert st["quarantined"] == 1 and st["stream_invalidations"] >= 1
        assert st["worker_errors"] == 0

    @pytest.mark.parametrize(
        "clients", [1, 4], ids=["one_at_a_time", "closed_loop"]
    )
    def test_no_new_host_sync(self, tiny_model, rng, clients, monkeypatch):
        """``HostSyncTripwire`` over a window of served pairs, with the
        engine's ``np.asarray`` of a device array counted beside it (on
        the CPU numpy reads such an array through the buffer protocol,
        which the tripwire does not see): the syncs are the parent's
        sites and no more — one ``await_rows`` an admission, one pacing
        fetch a drained tick, flow + residuals a retirement cohort
        (``copy_to_host_async`` and ``is_ready`` are no syncs). Alone in
        the pool a request costs 4, as before."""
        import jax

        from raft_tpu.serve import engine as engine_mod
        from raft_tpu.utils.tripwire import HostSyncTripwire

        class Fetches:
            n = 0

            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, a, *args, **kw):
                if isinstance(a, jax.Array):
                    Fetches.n += 1
                return np.asarray(a, *args, **kw)

        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(
            pool_capacity=2, max_batch=2, ladder=(3, 1), stream_cache_size=0,
            warmup=True,
        ))
        pairs = [(_image(rng), _image(rng)) for _ in range(8)]
        with eng:
            for a, b in pairs[:2]:       # every program has run once
                eng.submit(a, b)
            calls = self._counting(
                eng, ("_pool_insert_live", "_pool_tick_drain",
                      "_pool_complete"),
            )
            monkeypatch.setattr(engine_mod, "np", Fetches())
            with HostSyncTripwire() as tw:
                with ThreadPoolExecutor(clients) as ex:
                    res = list(ex.map(lambda p: eng.submit(*p), pairs))
                syncs = tw.total + Fetches.n
            monkeypatch.undo()
        assert all(np.isfinite(r.flow).all() for r in res)
        assert syncs == (
            calls["_pool_insert_live"] + calls["_pool_tick_drain"]
            + 2 * calls["_pool_complete"]
        ), (tw.snapshot(), calls)
        if clients == 1:
            assert syncs == 4 * len(pairs)

    def test_closed_loop_answers_as_one_at_a_time(self, tiny_model, rng):
        """Flows, residual trajectories, iteration counts and exit reasons
        of a closed loop (retirements read after a later tick) are those
        of the same requests served one at a time (each read at once),
        bit for bit: ``max_batch`` 1 keeps admission and retirement at
        rung 1 on both sides, and a slot's step does not depend on its
        neighbours."""
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(
            pool_capacity=2, max_batch=1, ladder=(3, 2, 1),
            stream_cache_size=0, trace_sample_rate=1.0,
        ))
        asks = [3, 2, 1, 3, 1, 2, 3, 3]
        pairs = [(_image(rng), _image(rng)) for _ in asks]

        def serve(a_b_n):
            a, b, n = a_b_n
            return eng.submit(a, b, num_flow_updates=n)

        work = [(a, b, n) for (a, b), n in zip(pairs, asks)]
        with eng:
            alone = [serve(w) for w in work]
            before = eng.stats()["retire_deferred"]
            with ThreadPoolExecutor(4) as ex:
                loop = list(ex.map(serve, work))
            deferred = eng.stats()["retire_deferred"] - before
        assert deferred >= 1                     # the closed loop deferred
        for x, y in zip(alone, loop):
            assert np.array_equal(x.flow, y.flow)
            assert x.residuals == y.residuals and x.residuals
            assert (x.num_flow_updates, x.exit_reason) == (
                y.num_flow_updates, y.exit_reason
            )


class TestPoolWarmup:
    def test_no_compile_after_warmup(self, tiny_model, rng):
        """After warmup no admitted traffic pattern — mixed per-request
        iteration counts, mixed admission sizes, stream sessions, and
        retirement waves wider than ``max_batch`` (pool_capacity=3 >
        max_batch=2 forces chunked finalization at the warmed rungs) —
        may compile on the worker thread: per bucket the set is admission
        rungs x {begin, insert, gather, final} (+ encode/begin_features)
        plus ONE capacity-wide step program, and per-request iteration
        counts add NOTHING (the pool's whole point)."""
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(
                max_batch=2, pool_capacity=3, ladder=(3, 1), warmup=True,
                stream_cache_size=2,
            ),
        )
        with eng:
            warm = eng.program_counts()
            assert warm["pool_step"] == 1
            assert warm["pool_begin_pair"] == 2      # admit rungs (1, 2)
            assert warm["pool_final"] == 2
            # insert/gather counts come from the pjit fast-path signature
            # cache, which can hold several entries per compiled
            # executable — the bound that matters is warmed coverage
            # (>= one per rung) plus the no-growth assert below
            assert warm["pool_insert"] >= 2
            assert warm["pool_gather"] >= 2
            assert warm["pairwise"] == 0             # no whole-request programs
            assert warm["iterate"] == 0
            for n, k in ((3, 1), (1, 2), (2, 2), (3, 3)):
                with ThreadPoolExecutor(k) as pool:
                    futs = [
                        pool.submit(
                            eng.submit, _image(rng), _image(rng),
                            num_flow_updates=n,
                        )
                        for _ in range(k)
                    ]
                    for f in futs:
                        assert np.isfinite(f.result().flow).all()
            with eng.open_stream() as stream:
                for _ in range(3):
                    stream.submit(_image(rng))
            assert eng.program_counts() == warm, (
                "traffic after warmup compiled a new program"
            )


# ---------------------------------------------------------------------------
# The fused block's packed pyramid: the pool holds its raw-volume levels in
# whole (8, 128) tiles — the shapes whose default TPU layout the lookup
# kernel reads in place — whoever made the state, so that no program
# re-lays a level and none compiles twice
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fused_model():
    """The tiny net on the fused lookup block (interpret mode on the
    CPU): ``begin_pair`` returns the packed ``levels`` / ``flats``
    pyramid, level 0 a raw volume, level 1 a flat."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    return _tiny_model(FusedLookupCorrBlock(num_levels=2, radius=3))


@pytest.fixture(scope="module")
def fused_engine(fused_model):
    model, variables = fused_model
    eng = ServeEngine(model, variables, _config(warmup=True))
    with eng:
        yield eng


class TestPackedPyramidPool:
    BUCKET, CAP, R = (48, 64), 3, 3
    # the 6x8 grid's levels as the pool holds them: level 0 (a raw
    # volume) in whole tiles, level 1 (it travels as a flat) as built
    HELD = [(8, 128, 1), (3, 4, 1)]

    def test_spec_zero_state_rows_and_insert_agree(self, fused_model, rng):
        """``state_spec``, ``zero_state``, ``begin_pair``'s row and what
        ``insert`` returns agree on every leaf's shape and dtype, the
        raw-volume level in whole tiles; ``step`` after ``insert`` after
        ``zero_state`` (twice over) is ONE compiled program each: nobody
        hands on a differently shaped state."""
        import jax

        from raft_tpu.serve.pool import PoolPrograms, state_spec, zero_state

        model, variables = fused_model
        progs = PoolPrograms(model, resid_len=self.R)
        spec = state_spec(
            model, variables, self.CAP, self.BUCKET, resid_len=self.R
        )
        assert [v.shape for v in spec["pyramid"]["levels"]] == [
            (self.CAP, 48) + tail for tail in self.HELD
        ]
        # 6x8 and 3x4 cells in lane-dense rows, as the kernel's flat
        # path has always taken them
        assert [v.shape for v in spec["pyramid"]["flats"]] == [
            (self.CAP, 48, 128)
        ]

        def same(tree, lead):
            got = jax.tree_util.tree_map(lambda v: (v.shape, v.dtype), tree)
            want = jax.tree_util.tree_map(
                lambda v: ((lead,) + v.shape[1:], v.dtype), spec
            )
            return got == want

        state = zero_state(
            model, variables, self.CAP, self.BUCKET, resid_len=self.R
        )
        im = (rng.random((1, 48, 64, 3)).astype(np.float32)) * 2 - 1
        th, sk, mi = np.float32(0.0), np.int32(1), np.int32(1)
        for slot in (0, 2):
            assert same(state, self.CAP)
            rows = progs.begin_pair(variables, im, im)
            assert same(rows, 1)
            state = progs.insert(
                state, rows, np.asarray([slot], np.int32),
                np.asarray([True]),
            )
            assert same(state, self.CAP)
            c1, hid, hist, conv, _ = progs.step(variables, state, th, sk, mi)
            state = {**state, "coords1": c1, "hidden": hid,
                     "resid_hist": hist, "converged": conv}
        counts = progs.counts()
        assert counts["pool_step"] == 1
        assert counts["pool_insert"] == 1
        assert counts["pool_begin_pair"] == 1

    def test_padding_is_zero_and_the_volume_is_unchanged(
        self, fused_model, rng
    ):
        """What the pool holds is the scan's own pyramid with zeros past
        the grid (an out-of-range tap reads zero either way)."""
        model, variables = fused_model
        im1 = (rng.random((1, 48, 64, 3)).astype(np.float32)) * 2 - 1
        im2 = (rng.random((1, 48, 64, 3)).astype(np.float32)) * 2 - 1
        f1, ctx = model.apply(variables, im1, train=False,
                              method="encode_frame")
        f2, _ = model.apply(variables, im2, train=False,
                            method="encode_frame")
        held = model.apply(variables, f1, f2, ctx, train=False,
                           method="begin_refinement")["pyramid"]
        built = model.corr_block.build_pyramid(f1, f2)
        lvl0 = np.asarray(held["levels"][0], np.float32)[0]
        np.testing.assert_array_equal(
            lvl0[:, :6, :8], np.asarray(built["levels"][0], np.float32)
        )
        assert not lvl0[:, 6:].any() and not lvl0[:, :, 8:].any()
        np.testing.assert_array_equal(
            np.asarray(held["levels"][1], np.float32)[0],
            np.asarray(built["levels"][1], np.float32),
        )

    def test_dense_pyramid_keeps_its_shapes(self, tiny_model):
        """The rule reads the pyramid's form: the dense block's tuple of
        levels is held as built."""
        from raft_tpu.serve.pool import state_spec

        model, variables = tiny_model
        spec = state_spec(model, variables, self.CAP, self.BUCKET)
        assert isinstance(spec["pyramid"], tuple)
        assert [v.shape for v in spec["pyramid"]] == [
            (self.CAP, 48, 6, 8, 1), (self.CAP, 48, 3, 4, 1)
        ]

    def test_stepwise_matches_scanned_iterate(self, fused_model, rng):
        """``TestIterateStepParity``'s decomposition, on the packed
        pyramid as the pool holds it (the scan reads it as built)."""
        state = _assert_stepwise_matches_scan(*fused_model, rng)
        assert [v.shape for v in state["pyramid"]["levels"]] == [
            (2, 48) + tail for tail in self.HELD
        ]

    def test_mixed_iters_golden_parity_closed_program_set(
        self, fused_engine, fused_model, rng
    ):
        """``TestPooledServing``'s golden through the warmed engine on the
        packed pyramid (AOT programs lowered from ``state_spec``, state
        from ``zero_state``): every request's flow matches the scanned
        ``iterate`` (which reads the unpadded pyramid) at its own target,
        and serving compiles nothing."""
        warm = fused_engine.program_counts()
        assert warm["pool_step"] == 1
        _assert_mixed_iters_match_oracle(fused_engine, *fused_model, rng)
        assert fused_engine.program_counts() == warm
        held = fused_engine._pools[self.BUCKET].state["pyramid"]["levels"]
        assert [v.shape[2:] for v in held] == self.HELD


# ---------------------------------------------------------------------------
# serve_bench smoke: pooled engine + mixed-iteration traffic mode
# ---------------------------------------------------------------------------


class TestPoolBenchSmoke:
    def test_pooled_bench_reports_occupancy_and_ttfd(self, capsys):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "script_serve_bench_pool",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts",
                "serve_bench.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.main(
            [
                "--tiny", "--duration", "0.5", "--clients", "4",
                "--ladder", "2,1", "--iters-mix", "2,1",
                "--pool-capacity", "2", "--max-batch", "2",
                "--queue-capacity", "8", "--no-warmup",
            ]
        )
        assert report["completed"] > 0
        assert report["pool_capacity"] == 2
        assert report["iters_mix"] == [2, 1]
        assert report["pool_ticks"] > 0
        assert 0.0 <= report["pool_occupancy"] <= 1.0
        assert 0.0 <= report["padding_waste"] <= 1.0
        # a pooled run reports its scheduler's loop by phase and
        # surfaces the residual-vs-iters table (the serve_convergence
        # BENCH line feeds scripts/perf_ledger.py)
        sched = report["sched"]
        assert sched["loops"] > 0
        assert any(
            k.startswith("serve/pool_step@") and v["n"] > 0
            for k, v in sched["phases"].items()
        )
        conv = report["convergence"]
        assert conv["enabled"] and conv["n"] > 0
        assert conv["final_residual_p50"] is not None
        assert report["ttfd_p50_ms"] is not None
        assert report["dispatched_slot_iters"] > 0
        out = capsys.readouterr().out
        assert '"metric": "serve_pool_occupancy"' in out
        assert '"metric": "serve_ttfd_p50_ms"' in out
        assert '"metric": "serve_report"' in out
