"""Convergence-adaptive compute (ISSUE 12): residual-driven early exit
and stream flow warm-start.

Coverage map:

* **Program level** (tiny model, tier-1) — converged-freeze bitwise
  stability (a frozen slot's coords/hidden/history are IDENTICAL across
  subsequent ticks), unconverged-slot pass-through bitwise identity
  (convergence machinery can never move an unconverged slot's flow),
  sentinel-seeded history (a fresh slot can't fake a streak), packed-mask
  pacing token round-trip, and the zero-new-host-syncs tripwire: the
  converged mask arrives on the pacing fetch the tick loop already pays.
* **Model level** (tier-1) — ``begin_refinement(init_flow=0)`` is
  bitwise the cold start, a nonzero seed lands exactly on
  ``coords0 + init_flow``, and ``forward_interpolate``'s nearest-point
  semantics (upstream's warm start).
* **Engine level** (tiny model, tier-1) — exit-reason split (converged
  exits counted distinctly from deadline exits, per-reason iters-saved
  attribution, ``early_exit`` back-compat property), warm-start flag and
  flow-row lifecycle (invalidation clears the seed — no warm start
  across a gap), pre-ISSUE-12 artifact version refusal degrading to
  compile, and the serve_bench adaptive-A/B machinery smoke.
* **Trained fixture** (slow) — the equal-EPE gate: at the calibrated
  threshold the pooled engine's early-exited flows match the
  fixed-iteration protocol's EPE within tolerance while measurably
  cutting iterations, and warm start cuts iters-to-converge further at
  equal-or-better EPE (the ISSUE 12 acceptance, engine-level).

Tiny-model note: random-init weights are NOT contractive (residuals
plateau around 3 px and never converge), so tier-1 threshold tests use
thresholds far above the plateau to exercise the mechanics; quality
claims live with the trained fixture under ``slow``.
"""

import json
import os
import pickle

import numpy as np
import pytest

from raft_tpu.serve import (
    PoisonedInput,
    ServeConfig,
    ServeEngine,
)
from raft_tpu.serve.engine import ServeResult
from raft_tpu.serve.pool import (
    RESID_SENTINEL,
    PoolPrograms,
    unpack_converged,
)
from raft_tpu.serve.stream_cache import forward_interpolate

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "epe_golden"
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


def _image(rng, hw=(45, 60)):
    return rng.integers(0, 255, hw + (3,), dtype=np.uint8)


def _config(**kw):
    base = dict(
        buckets=((48, 64),),
        ladder=(3, 1),
        max_batch=2,
        pool_capacity=2,
        queue_capacity=8,
        max_wait_ms=4.0,
        default_deadline_ms=30000.0,
        cooldown_batches=1,
        recover_after=1,
        high_watermark=1.0,
        low_watermark=0.25,
    )
    base.update(kw)
    return ServeConfig(**base)


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------


class TestAdaptiveConfig:
    @pytest.mark.parametrize(
        "kw",
        [
            {"pool_converge_thresh": 0.0},
            {"pool_converge_thresh": -0.1},
            {"pool_converge_streak": 0},
            # streak must fit the residual history (ladder[0]) when the
            # feature is enabled
            {"ladder": (3, 1), "pool_converge_streak": 4,
             "pool_converge_thresh": 0.1},
        ],
    )
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            ServeConfig(**kw)

    def test_defaults_are_off(self):
        cfg = ServeConfig()
        assert cfg.pool_converge_thresh is None
        assert cfg.stream_warm_start is False
        # the default streak must not invalidate short-ladder configs
        # while the feature is off
        assert ServeConfig(ladder=(1,)).pool_converge_streak == 2

    def test_early_exit_property_derives_from_reason(self):
        base = dict(
            flow=None, rid=0, bucket=(8, 8), num_flow_updates=1, level=0,
            degraded=False, latency_ms=1.0,
        )
        assert ServeResult(**base, exit_reason="target").early_exit is False
        assert ServeResult(**base, exit_reason="deadline").early_exit is True
        assert ServeResult(**base, exit_reason="converged").early_exit is True


# ---------------------------------------------------------------------------
# Program level: freeze stability, pass-through identity, pacing mask
# ---------------------------------------------------------------------------


class TestConvergedFreeze:
    def _state(self, tiny_model, rng, n=2):
        model, variables = tiny_model
        progs = PoolPrograms(model, resid_len=4)
        p1 = rng.uniform(-1, 1, (n, 48, 64, 3)).astype(np.float32)
        p2 = rng.uniform(-1, 1, (n, 48, 64, 3)).astype(np.float32)
        return progs, variables, dict(progs.begin_pair(variables, p1, p2))

    def test_history_seeded_with_sentinel(self, tiny_model, rng):
        _, _, state = self._state(tiny_model, rng)
        h = np.asarray(state["resid_hist"])
        assert (h == RESID_SENTINEL).all()
        assert not np.asarray(state["converged"]).any()

    def test_sentinel_blocks_premature_streak(self, tiny_model, rng):
        """A fresh slot with streak=3 cannot converge at tick 1 even
        under an absurdly large threshold: the unwritten history
        positions hold the sentinel, not fake sub-threshold zeros."""
        progs, variables, state = self._state(tiny_model, rng)
        th, sk, mi = np.float32(1e6), np.int32(3), np.int32(1)
        c1, hid, hist, conv, _ = progs.step(variables, state, th, sk, mi)
        assert not np.asarray(conv).any()         # 1 real entry < streak 3
        state = {**state, "coords1": c1, "hidden": hid,
                 "resid_hist": hist, "converged": conv}
        c1, hid, hist, conv, _ = progs.step(variables, state, th, sk, mi)
        assert not np.asarray(conv).any()         # 2 < 3
        state = {**state, "coords1": c1, "hidden": hid,
                 "resid_hist": hist, "converged": conv}
        *_, conv, _tok = progs.step(variables, state, th, sk, mi)
        assert np.asarray(conv).all()             # 3 real entries: fires

    def test_frozen_slot_is_bitwise_stable(self, tiny_model, rng):
        """ISSUE 12 acceptance: once converged, a slot's flow state is
        IDENTICAL across subsequent ticks — jnp.where freeze, no state
        churn, so the finalized flow is exactly the freeze-tick flow."""
        progs, variables, state = self._state(tiny_model, rng)
        th, sk, mi = np.float32(1e6), np.int32(1), np.int32(1)
        c1, hid, hist, conv, tok = progs.step(variables, state, th, sk, mi)
        assert np.asarray(conv).all()
        frozen = {**state, "coords1": c1, "hidden": hid,
                  "resid_hist": hist, "converged": conv}
        for _ in range(3):
            c1b, hidb, histb, convb, tokb = progs.step(
                variables, frozen, th, sk, mi
            )
            assert np.array_equal(np.asarray(c1b), np.asarray(c1))
            assert np.array_equal(np.asarray(hidb), np.asarray(hid))
            assert np.array_equal(np.asarray(histb), np.asarray(hist))
            assert np.asarray(convb).all()
            frozen = {**frozen, "coords1": c1b, "hidden": hidb,
                      "resid_hist": histb, "converged": convb}

    def test_unconverged_slot_passthrough_is_bitwise(self, tiny_model, rng):
        """A frozen neighbor cannot move an unconverged slot: its
        outputs are bitwise the convergence-free step's outputs."""
        progs, variables, state = self._state(tiny_model, rng, n=2)
        # advance once so coords differ from the grid
        th0, sk, mi = np.float32(0.0), np.int32(1), np.int32(1)
        c1, hid, hist, conv, _ = progs.step(variables, state, th0, sk, mi)
        base = {**state, "coords1": c1, "hidden": hid,
                "resid_hist": hist, "converged": conv}
        # freeze slot 0 only, leave slot 1 live
        mixed = {
            **base,
            "converged": np.asarray([True, False]),
        }
        ref = progs.step(variables, base, th0, sk, mi)   # nobody frozen
        got = progs.step(variables, mixed, th0, sk, mi)
        # slot 1 (unconverged) bitwise identical to the reference step
        for a, b in ((got[0], ref[0]), (got[1], ref[1]), (got[2], ref[2])):
            assert np.array_equal(np.asarray(a)[1], np.asarray(b)[1])
        # slot 0 (frozen) bitwise unchanged from its input
        assert np.array_equal(np.asarray(got[0])[0], np.asarray(c1)[0])
        assert np.array_equal(np.asarray(got[1])[0], np.asarray(hid)[0])

    def test_packed_mask_rides_the_token(self, tiny_model, rng):
        progs, variables, state = self._state(tiny_model, rng, n=2)
        mixed = {**state, "converged": np.asarray([True, False])}
        *_, conv, tok = progs.step(
            variables, mixed, np.float32(0.0), np.int32(1), np.int32(1)
        )
        bits = unpack_converged(np.asarray(tok), 2)
        assert bits.tolist() == np.asarray(conv).tolist() == [True, False]

    def test_mask_fetch_adds_zero_host_syncs(self, tiny_model, rng):
        """The tripwire assertion behind 'zero new host syncs': a tick +
        pacing fetch with convergence ON costs exactly the same sync
        count as with convergence OFF — the mask IS the pacing token."""
        from raft_tpu.utils.tripwire import HostSyncTripwire

        progs, variables, state = self._state(tiny_model, rng)

        def syncs(thresh):
            th, sk, mi = np.float32(thresh), np.int32(1), np.int32(1)
            cur = dict(state)
            with HostSyncTripwire() as tw:
                for _ in range(3):
                    c1, hid, hist, conv, tok = progs.step(
                        variables, cur, th, sk, mi
                    )
                    cur = {**cur, "coords1": c1, "hidden": hid,
                           "resid_hist": hist, "converged": conv}
                # the ONE pacing fetch per drained tick (engine:
                # _pool_tick's np.asarray on the popped token)
                np.asarray(tok)
                total = sum(tw.counts.values())
            return total

        assert syncs(0.0) == syncs(1e6)


# ---------------------------------------------------------------------------
# Model level: warm-start seeding + forward warp
# ---------------------------------------------------------------------------


class TestWarmStartModel:
    def test_zero_init_flow_is_bitwise_cold(self, tiny_model, rng):
        import jax

        model, variables = tiny_model
        im1 = rng.uniform(-1, 1, (1, 48, 64, 3)).astype(np.float32)
        im2 = rng.uniform(-1, 1, (1, 48, 64, 3)).astype(np.float32)
        cold = model.apply(variables, im1, im2, train=False,
                           method="begin_pair")
        warm0 = model.apply(
            variables, im1, im2, np.zeros((1, 6, 8, 2), np.float32),
            train=False, method="begin_pair",
        )
        for a, b in zip(jax.tree_util.tree_leaves(cold),
                        jax.tree_util.tree_leaves(warm0)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_nonzero_seed_lands_on_coords0_plus_flow(self, tiny_model, rng):
        model, variables = tiny_model
        im1 = rng.uniform(-1, 1, (1, 48, 64, 3)).astype(np.float32)
        im2 = rng.uniform(-1, 1, (1, 48, 64, 3)).astype(np.float32)
        init = rng.uniform(-2, 2, (1, 6, 8, 2)).astype(np.float32)
        cold = model.apply(variables, im1, im2, train=False,
                           method="begin_pair")
        warm = model.apply(variables, im1, im2, init, train=False,
                           method="begin_pair")
        np.testing.assert_allclose(
            np.asarray(warm["coords1"]),
            np.asarray(cold["coords1"]) + init, rtol=1e-6, atol=1e-6,
        )
        # everything else (pyramid, hidden, context) is seed-independent
        assert np.array_equal(
            np.asarray(warm["hidden"]), np.asarray(cold["hidden"])
        )

    def test_bad_seed_shape_raises(self, tiny_model, rng):
        model, variables = tiny_model
        im = rng.uniform(-1, 1, (1, 48, 64, 3)).astype(np.float32)
        with pytest.raises(ValueError, match="init_flow"):
            model.apply(
                variables, im, im, np.zeros((1, 5, 8, 2), np.float32),
                train=False, method="begin_pair",
            )

    def test_forward_interpolate_semantics(self):
        """Upstream's warm start (``forward_interpolate``): every cell
        takes the flow of the nearest point that landed strictly inside
        the frame — no holes, no wrap-around, zeros only when nothing
        lands."""
        interp = lambda f: np.asarray(forward_interpolate(f))
        flow = np.zeros((4, 6, 2), np.float32)
        assert np.array_equal(interp(flow), flow)              # identity
        # one vector (+2 in x) lands on cell (1, 3), where that cell's own
        # point (zero flow) already is: equidistant, and the lower source
        # index — (1, 1) — wins
        flow[1, 1] = (2.0, 0.0)
        out = interp(flow)
        assert tuple(out[1, 3]) == (2.0, 0.0)
        # the cell it left is no hole: it takes a neighbour's (zero) flow
        assert tuple(out[1, 1]) == (0.0, 0.0)
        assert (out[..., 1] == 0).all()
        # a point that leaves the frame is dropped, never wrapped, and the
        # cell it left is filled from the nearest point that stayed
        flow2 = np.full((4, 6, 2), (1.0, 0.0), np.float32)
        flow2[0, 5] = (30.0, 0.0)
        assert (interp(flow2) == np.asarray((1.0, 0.0), np.float32)).all()
        # nothing lands inside: zeros
        assert (interp(np.full((4, 6, 2), 99.0, np.float32)) == 0).all()


# ---------------------------------------------------------------------------
# Engine level: exit reasons, warm-start lifecycle, artifact refusal
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestExitReasonAccounting:
    def test_converged_exit_reason_and_counters(self, tiny_model, rng):
        """The tiny net's residuals plateau ~3 px: a threshold above the
        plateau makes every request converge after `streak` ticks —
        retired with reason 'converged', distinct counters, per-reason
        iters-saved attribution, early_exit back-compat True."""
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(
                ladder=(8, 1), pool_capacity=1, pool_converge_thresh=50.0,
                pool_converge_streak=2, stream_cache_size=0,
            ),
        )
        with eng:
            res = eng.submit(_image(rng), _image(rng))
            assert res.exit_reason == "converged"
            assert res.early_exit is True
            # froze at the streak (2) — pipeline lag only delays the
            # HOST learning it, never inflates the effective count
            assert 2 <= res.num_flow_updates < 8
            assert res.residuals is None          # untraced request
            stats = eng.stats()
        assert stats["early_exits_converged"] >= 1
        assert stats["early_exits_deadline"] == 0
        assert stats["early_exit_iters_saved_converged"] > 0
        assert (
            stats["early_exit_iters_saved"]
            >= stats["early_exit_iters_saved_converged"]
        )

    def test_converged_exit_respects_min_iters(self, tiny_model, rng):
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(
                ladder=(12, 1), pool_capacity=1, pool_converge_thresh=50.0,
                pool_converge_streak=1, pool_min_iters=4,
                stream_cache_size=0, pipeline_depth=1,
            ),
        )
        with eng:
            res = eng.submit(_image(rng), _image(rng))
        assert res.exit_reason == "converged"
        assert res.num_flow_updates >= 4

    def test_threshold_off_never_converges(self, tiny_model, rng):
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(ladder=(3, 1), pool_capacity=2, stream_cache_size=0),
        )
        with eng:
            res = eng.submit(_image(rng), _image(rng))
            stats = eng.stats()
        assert res.exit_reason == "target"
        assert res.num_flow_updates == 3
        assert stats["early_exits_converged"] == 0


@pytest.mark.chaos
class TestWarmStartEngine:
    def test_warm_start_flags_and_gap_invalidation(self, tiny_model, rng):
        """Warm-start lifecycle: first pair cold (no cached flow), later
        pairs warm; a poisoned frame invalidates the session so the
        stream re-primes and the next pair is cold again — never a warm
        start across a gap."""
        from raft_tpu.utils.faults import FaultInjector

        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            _config(stream_warm_start=True, pool_capacity=2),
        )
        with eng:
            with eng.open_stream() as stream:
                assert stream.submit(_image(rng)).primed
                first = stream.submit(_image(rng))
                assert first.warm_started is False     # nothing cached yet
                second = stream.submit(_image(rng))
                assert second.warm_started is True     # seeded from first
                assert eng.stats()["stream_warm_starts"] == 1

                inj = FaultInjector()
                seen = {}

                def first_rid(i, ctx):
                    seen.setdefault("rid", ctx["rid"])
                    return ctx["rid"] == seen["rid"]

                with inj.patch_engine(eng):
                    inj.on("infer.nan_flow", when=first_rid,
                           action=FaultInjector.nan_flow)
                    with pytest.raises(PoisonedInput):
                        stream.submit(_image(rng))
                re_primed = stream.submit(_image(rng))
                assert re_primed.primed                # gap: session reset
                after_gap = stream.submit(_image(rng))
                assert after_gap.warm_started is False  # cold again
        assert eng.stats()["stream_invalidations"] >= 1

    def test_warm_start_off_never_flags(self, tiny_model, rng):
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _config(pool_capacity=2))
        with eng:
            with eng.open_stream() as stream:
                stream.submit(_image(rng))
                for _ in range(3):
                    assert stream.submit(_image(rng)).warm_started is False
            assert eng.stats()["stream_warm_starts"] == 0


@pytest.mark.chaos
class TestArtifactVersionRefusal:
    def test_pre_issue12_artifact_refuses_typed(self, tmp_path):
        """A v2 (pre-ISSUE-12) artifact's executables no longer match
        the step/begin signatures: load refuses on 'format' — typed,
        never a runtime signature explosion."""
        from raft_tpu.serve import aot
        from raft_tpu.serve.errors import ArtifactMismatch

        path = tmp_path / "v2.raftaot"
        path.write_bytes(pickle.dumps(
            {"fingerprint": {"format": 2}, "programs": {}}
        ))
        with pytest.raises(ArtifactMismatch) as ei:
            aot.load_artifact(str(path))
        assert ei.value.field == "format"

    def test_boot_degrades_to_compile(self, tiny_model, tmp_path):
        """An engine handed a stale v2 artifact must boot anyway:
        artifact_error recorded, programs compiled, traffic served."""
        model, variables = tiny_model
        path = tmp_path / "v2.raftaot"
        path.write_bytes(pickle.dumps(
            {"fingerprint": {"format": 2}, "programs": {}}
        ))
        eng = ServeEngine(
            model, variables,
            _config(
                ladder=(2, 1), pool_capacity=1, stream_cache_size=0,
                warmup=True, warmup_artifact=str(path),
            ),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["programs_loaded"] == 0
            assert boot["programs_compiled"] > 0
            assert "format" in boot["artifact_error"]
            rng = np.random.default_rng(0)
            res = eng.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()


# ---------------------------------------------------------------------------
# Bench + ledger machinery (tier-1 smoke)
# ---------------------------------------------------------------------------


def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"script_{name}_adaptive",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", f"{name}.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestAdaptiveBenchMachinery:
    def test_adaptive_ab_smoke_tiny(self, capsys):
        """--adaptive-ab machinery on the tiny net: both arms run, the
        BENCH line carries every gated field. (Quality numbers are only
        meaningful with trained weights — the slow fixture test and
        BENCH_r07 carry those.)"""
        mod = _load_script("serve_bench")
        report = mod.main([
            "--tiny", "--adaptive-ab", "--ab-model", "tiny",
            "--ab-iters", "8", "--ab-frames", "2",
            "--converge-thresh", "50.0",
        ])
        assert report["metric"] == "serve_adaptive_ab"
        assert report["model"] == "tiny-random"
        assert report["pairs"] >= 2
        assert report["iters_per_req_fixed"] == 8.0
        # plateau-level threshold: the tiny net 'converges' immediately
        assert report["iters_per_req_adaptive"] < 8.0
        assert report["exit_reasons_adaptive"].get("converged", 0) > 0
        assert report["warm_starts_adaptive"] > 0
        assert report["epe_delta_px"] >= 0.0
        out = capsys.readouterr().out
        assert '"metric": "serve_adaptive_ab"' in out

    def test_bench_report_carries_exit_occupancy(self):
        mod = _load_script("serve_bench")
        report = mod.main([
            "--tiny", "--duration", "1.0", "--clients", "2",
            "--ladder", "8,1", "--pool-capacity", "2", "--max-batch", "2",
            "--queue-capacity", "8", "--no-warmup",
            "--converge-thresh", "50.0", "--converge-streak", "1",
        ])
        assert report["converge_thresh"] == 50.0
        assert report["iters_per_request_mean"] is not None
        occ = report["exit_reason_occupancy"]
        assert set(occ) >= {"target", "deadline", "converged"}
        assert occ["converged"] > 0       # plateau threshold: all exits
        assert report["early_exits_converged"] > 0

    def test_perf_ledger_gates_adaptive_ab_line(self):
        """serve_adaptive_ab flattens into gated series with the right
        directions: iters/request + EPE degradation down, reduction /
        speedup / throughput up."""
        mod = _load_script("perf_ledger")
        line = {
            "metric": "serve_adaptive_ab",
            "iters_per_req_fixed": 32.0,
            "iters_per_req_adaptive": 14.3,
            "iters_reduction_frac": 0.55,
            "throughput_rps_fixed": 6.3,
            "throughput_rps_adaptive": 11.4,
            "speedup": 1.8,
            "epe_delta_px": 0.0,
            "config": "adaptive_ab test",
        }
        flat = dict(mod.extract_metrics(line))
        assert flat["serve_adaptive_ab/iters_per_req_adaptive"] == 14.3
        assert flat["serve_adaptive_ab/epe_delta_px"] == 0.0
        assert mod.direction(
            "serve_adaptive_ab/iters_per_req_adaptive"
        ) == "down"
        assert mod.direction("serve_adaptive_ab/epe_delta_px") == "down"
        assert mod.direction(
            "serve_adaptive_ab/iters_reduction_frac"
        ) == "up"
        assert mod.direction("serve_adaptive_ab/speedup") == "up"
        assert mod.direction(
            "serve_adaptive_ab/throughput_rps_adaptive"
        ) == "up"

    def test_perf_ledger_regresses_on_adaptive_backslide(self, tmp_path):
        """End-to-end: a candidate round whose adaptive arm pays more
        iterations and degrades EPE past the envelope exits 2."""
        mod = _load_script("perf_ledger")
        good = {
            "metric": "serve_adaptive_ab",
            "iters_per_req_adaptive": 14.0,
            "epe_delta_px": 0.0,
            "speedup": 1.8,
            "config": "adaptive_ab pinned",
        }
        prior = tmp_path / "BENCH_r01.json"
        prior.write_text(json.dumps(
            {"n": 1, "tail": json.dumps(good)}
        ))
        prior2 = tmp_path / "BENCH_r02.json"
        prior2.write_text(json.dumps(
            {"n": 2, "tail": json.dumps(good)}
        ))
        bad = dict(good, iters_per_req_adaptive=30.0, speedup=1.0)
        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps({"n": 3, "tail": json.dumps(bad)}))
        rc = mod.main([
            "--dir", str(tmp_path), "--candidate", str(cand), "--check",
        ])
        assert rc == 2

    def test_calibrate_convergence_exit_rule(self):
        mod = _load_script("calibrate_convergence")
        resids = [1.0, 0.5, 0.09, 0.08, 0.02, 0.01, 0.01, 0.01]
        assert mod.exit_iter(resids, 0.1, 2, 1) == 4
        assert mod.exit_iter(resids, 0.1, 2, 6) == 6      # min-iters floor
        assert mod.exit_iter(resids, 0.015, 3, 1) == 8
        assert mod.exit_iter(resids, 1e-6, 2, 1) == len(resids)  # never

    def test_calibrate_convergence_picks_largest_passing(self):
        mod = _load_script("calibrate_convergence")
        # one sample: exits late for small thresholds (no cost), early
        # for the big one (costly)
        resids = [0.5, 0.2, 0.1, 0.05, 0.02, 0.02, 0.02, 0.02]
        epes = [4.0, 3.0, 2.5, 2.2, 2.05, 2.02, 2.01, 2.0]
        rows, best = mod.calibrate(
            [(resids, epes)], [0.03, 0.06, 0.3], streak=2, min_iters=1,
            tolerance=0.05,
        )
        by_t = {r["thresh"]: r for r in rows}
        assert by_t[0.3]["ok"] is False       # exits @3: dEPE 0.5
        assert by_t[0.06]["ok"] is True       # exits @6: dEPE 0.02
        assert best == 0.06


# ---------------------------------------------------------------------------
# Trained fixture: the equal-EPE gate (slow — real EPE sweeps)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fixture_model():
    if not os.path.isdir(FIXTURE):
        pytest.skip("epe_golden fixture not present")
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    import flax.serialization
    import jax

    from raft_tpu.models.zoo import build_raft, init_variables
    from scripts.make_epe_fixture import fixture_arch

    model = build_raft(fixture_arch())
    tmpl = jax.tree.map(
        np.zeros_like, jax.device_get(init_variables(model))
    )
    with open(os.path.join(FIXTURE, "weights.msgpack"), "rb") as f:
        trained = flax.serialization.from_bytes(tmpl, f.read())
    return model, trained


def _fixture_scenes():
    import glob

    from raft_tpu.data.io import read_flow, read_image

    scenes = []
    for scene_dir in sorted(
        glob.glob(os.path.join(FIXTURE, "training", "clean", "*"))
    ):
        frames = [
            read_image(p).astype(np.float32)
            for p in sorted(glob.glob(os.path.join(scene_dir, "*.png")))
        ]
        gts = [
            read_flow(p)[0]
            for p in sorted(glob.glob(os.path.join(
                FIXTURE, "training", "flow",
                os.path.basename(scene_dir), "*.flo",
            )))
        ]
        scenes.append((frames, gts))
    return scenes


@pytest.mark.slow
class TestEqualEpeGateTrainedFixture:
    """The ISSUE 12 acceptance at engine level, on trained weights and
    real frames: at the calibrated threshold, residual-driven early exit
    (+ warm start) must cut iterations >= 20% at an EPE degradation
    <= 1e-2 px vs the fixed 32-iteration protocol."""

    TOL_PX = 1e-2
    THRESH = 0.03          # scripts/calibrate_convergence.py, 32 iters

    def _serve_scenes(self, fixture_model, **cfg_kw):
        model, trained = fixture_model
        scenes = _fixture_scenes()
        h, w = scenes[0][0][0].shape[:2]
        bucket = ((h + 7) // 8 * 8, (w + 7) // 8 * 8)
        eng = ServeEngine(
            model, trained,
            ServeConfig(
                buckets=(bucket,), ladder=(32,), pool_capacity=2,
                max_batch=2, stream_cache_size=4, queue_capacity=16,
                default_deadline_ms=600000.0, pool_min_iters=2,
                **cfg_kw,
            ),
        )
        iters, epes, warm = [], [], 0
        with eng:
            for frames, gts in scenes:
                with eng.open_stream() as stream:
                    for t, f in enumerate(frames):
                        res = stream.submit(f)
                        if res.primed:
                            continue
                        gt = gts[t - 1]
                        err = np.sqrt((
                            (res.flow[: gt.shape[0], : gt.shape[1]] - gt)
                            ** 2
                        ).sum(-1))
                        iters.append(res.num_flow_updates)
                        epes.append(float(err.mean()))
                        warm += int(res.warm_started)
        return float(np.mean(iters)), float(np.mean(epes)), warm

    def test_equal_epe_at_calibrated_threshold(self, fixture_model):
        fixed_iters, fixed_epe, _ = self._serve_scenes(fixture_model)
        a_iters, a_epe, warm = self._serve_scenes(
            fixture_model,
            pool_converge_thresh=self.THRESH,
            pool_converge_streak=2,
            stream_warm_start=True,
        )
        assert fixed_iters == 32.0
        saved = 1.0 - a_iters / fixed_iters
        assert saved >= 0.20, (a_iters, fixed_iters)
        # equal-EPE gate: degradation (not improvement) bounded
        assert max(0.0, a_epe - fixed_epe) <= self.TOL_PX, (
            a_epe, fixed_epe
        )
        assert warm >= 1          # the non-first pairs warm-started

    def test_warm_start_cuts_iters_to_converge(self, fixture_model):
        """Warm start on top of early exit: the warm-started pairs of a
        multi-pair scene converge in fewer iterations than the same
        pairs served cold-adaptive, and their EPE stays within tolerance
        of the fixed 32-iteration protocol (the equal-EPE reference —
        cold-adaptive and warm-adaptive land on slightly different
        near-fixed-point flows, so they are compared to the protocol,
        not to each other)."""
        model, trained = fixture_model
        scenes = [s for s in _fixture_scenes() if len(s[0]) >= 3]
        assert scenes, "fixture lost its multi-pair scene"

        def run(warm_start, thresh):
            h, w = scenes[0][0][0].shape[:2]
            bucket = ((h + 7) // 8 * 8, (w + 7) // 8 * 8)
            eng = ServeEngine(
                model, trained,
                ServeConfig(
                    buckets=(bucket,), ladder=(32,), pool_capacity=2,
                    max_batch=2, stream_cache_size=4, queue_capacity=16,
                    default_deadline_ms=600000.0, pool_min_iters=2,
                    pool_converge_thresh=thresh,
                    pool_converge_streak=2,
                    stream_warm_start=warm_start,
                ),
            )
            out = []
            with eng:
                for frames, gts in scenes:
                    with eng.open_stream() as stream:
                        for t, f in enumerate(frames):
                            res = stream.submit(f)
                            if res.primed or t < 2:
                                # pair (0,1) is cold either way; only
                                # pairs with a cached previous flow
                                # differ between the arms
                                continue
                            gt = gts[t - 1]
                            err = np.sqrt((
                                (res.flow[: gt.shape[0], : gt.shape[1]]
                                 - gt) ** 2
                            ).sum(-1))
                            out.append(
                                (res.num_flow_updates, float(err.mean()),
                                 res.warm_started)
                            )
            return out

        fixed = run(False, None)
        cold = run(False, self.THRESH)
        warm = run(True, self.THRESH)
        assert all(not w for *_, w in fixed + cold)
        assert all(w for *_, w in warm)
        cold_iters = np.mean([it for it, *_ in cold])
        warm_iters = np.mean([it for it, *_ in warm])
        assert warm_iters < cold_iters, (warm_iters, cold_iters)
        fixed_epe = np.mean([e for _, e, _ in fixed])
        warm_epe = np.mean([e for _, e, _ in warm])
        assert max(0.0, warm_epe - fixed_epe) <= self.TOL_PX, (
            warm_epe, fixed_epe
        )
