"""Divergence-resilience chaos suite (docs/failure_model.md model-fault
ladder): the in-step skip guard, the grad-norm spike detector, known-good
checkpoint tagging, and the rollback-with-reseed escalation — every rung
exercised on CPU with `utils.faults.FaultInjector`, not claimed. Tier-1
collected via the registered ``chaos`` marker; the multi-rollback death
scenario stays behind ``slow``.
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.train.stability import (
    DivergenceError,
    StabilityMonitor,
    StabilityPolicy,
    perturb_seed,
)
from raft_tpu.utils.faults import FaultInjector, StallError

pytestmark = pytest.mark.chaos


def _tiny_model_and_tx():
    from tests.test_train import tiny_cfg

    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.train import TrainState, make_optimizer

    model = build_raft(tiny_cfg(large=False))
    variables = init_variables(model)
    tx = make_optimizer(1e-3, weight_decay=1e-5)
    return model, tx, TrainState.create(variables, tx)


def _batch(seed=0, b=2, hw=(128, 128)):
    from tests.test_train import make_batch

    return make_batch(np.random.default_rng(seed), b=b, h=hw[0], w=hw[1])


def _nan_batch(batch):
    bad = dict(batch)
    bad["image1"] = jnp.full_like(batch["image1"], jnp.nan)
    return bad


def _tree_equal(a, b) -> bool:
    return all(
        bool(jnp.array_equal(x, y, equal_nan=True))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# In-step guard (tentpole part 1): apply-or-skip on device
# ---------------------------------------------------------------------------


class TestGuardedStep:
    def test_no_fault_identical_to_unguarded(self):
        """Guard enabled + no fault = bitwise the unguarded trajectory
        (the guard is a select, never a perturbation of the update)."""
        from raft_tpu.train import make_train_step

        model, tx, state0 = _tiny_model_and_tx()
        plain = make_train_step(model, tx, num_flow_updates=2, donate=False)
        guarded = make_train_step(
            model, tx, num_flow_updates=2, donate=False,
            numerics_policy="skip", spike_factor=20.0,
        )
        batch = _batch()
        sp, mp = plain(state0, batch)
        sg, mg = guarded(state0, batch)
        assert _tree_equal(sp.params, sg.params)
        assert _tree_equal(sp.opt_state, sg.opt_state)
        assert float(mp["loss"]) == float(mg["loss"])
        assert float(mg["skipped"]) == 0.0
        assert int(sg.skipped_steps) == 0 and int(sg.good_steps) == 1

    def test_jaxpr_is_host_callback_free(self):
        """Hot-path purity: the guarded step lowers to pure device code —
        no host callbacks, no infeed/outfeed."""
        from raft_tpu.train.step import make_train_step_fn

        model, tx, state = _tiny_model_and_tx()
        fn = make_train_step_fn(
            model, tx, num_flow_updates=2,
            numerics_policy="skip", spike_factor=20.0,
        )
        jaxpr = str(jax.make_jaxpr(fn)(state, _batch()))
        for forbidden in ("callback", "infeed", "outfeed", "outside_call"):
            assert forbidden not in jaxpr, f"host op {forbidden!r} in step"

    def test_nan_grads_skip_whole_update(self):
        """A NaN-grad step keeps params, opt_state AND the step's EMA at
        their old values; only step/skipped_steps advance."""
        from raft_tpu.train import make_train_step

        model, tx, state = _tiny_model_and_tx()
        guarded = make_train_step(
            model, tx, num_flow_updates=2, donate=False,
            numerics_policy="skip",
        )
        batch = _batch()
        s1, _ = guarded(state, batch)  # one good step first
        s2, m2 = guarded(s1, _nan_batch(batch))
        assert float(m2["nonfinite_grads"]) > 0
        assert float(m2["skipped"]) == 1.0
        assert _tree_equal(s1.params, s2.params)
        assert _tree_equal(s1.opt_state, s2.opt_state)
        assert float(s2.grad_ema) == float(s1.grad_ema)
        assert int(s2.skipped_steps) == 1
        assert int(s2.good_steps) == int(s1.good_steps)
        assert int(s2.step) == int(s1.step) + 1  # data position advances

    def test_spike_detected_and_skipped(self):
        """A finite grad-norm spike (the loss concentrated on one pixel:
        ``FaultInjector.loss_spike``) is skipped once the EMA is warm;
        the EMA ignores the spike."""
        from raft_tpu.train import make_train_step

        model, tx, state = _tiny_model_and_tx()
        guarded = make_train_step(
            model, tx, num_flow_updates=2, donate=False,
            numerics_policy="skip", spike_factor=3.0,
            ema_decay=0.5, spike_warmup=3,
        )
        batch = _batch()
        s = state
        for _ in range(6):
            s, m = guarded(s, batch)
        assert int(s.skipped_steps) == 0
        spike = dict(batch)
        FaultInjector.loss_spike(spike)
        spike = {k: jnp.asarray(v) for k, v in spike.items()}
        s2, m2 = guarded(s, spike)
        assert np.isfinite(float(m2["grad_norm"]))
        assert float(m2["grad_norm"]) > 3.0 * float(s.grad_ema)
        assert float(m2["skipped"]) == 1.0
        assert _tree_equal(s.params, s2.params)
        assert float(s2.grad_ema) == float(s.grad_ema)

    def test_spike_disabled_below_warmup(self):
        """Before spike_warmup applied updates the detector must stay
        quiet — the un-warmed EMA would misfire on normal variance."""
        from raft_tpu.train import make_train_step

        model, tx, state = _tiny_model_and_tx()
        guarded = make_train_step(
            model, tx, num_flow_updates=2, donate=False,
            numerics_policy="skip", spike_factor=1e-6, spike_warmup=100,
        )
        s, m = guarded(state, _batch())
        assert float(m["skipped"]) == 0.0  # tiny factor, but below warmup

    def test_raise_policy_is_the_old_behavior(self):
        """numerics_policy='raise' applies even a NaN update (the trainer
        raises at the boundary) — backward compatible."""
        from raft_tpu.train import make_train_step

        model, tx, state = _tiny_model_and_tx()
        step = make_train_step(
            model, tx, num_flow_updates=2, donate=False,
            check_numerics=True,
        )
        s, m = step(state, _nan_batch(_batch()))
        assert float(m["nonfinite_grads"]) > 0
        assert not bool(
            jnp.isfinite(jax.tree.leaves(s.params)[0]).all()
        )  # poisoned, as before
        assert "skipped" not in m

    def test_invalid_policy_rejected(self):
        from raft_tpu.train.step import make_train_step_fn

        model, tx, _ = _tiny_model_and_tx()
        with pytest.raises(ValueError, match="numerics_policy"):
            make_train_step_fn(model, tx, numerics_policy="ignore")

    def test_guard_composes_with_mesh(self):
        """Under the 8-device mesh the skip decision is a replicated
        scalar from all-reduced grads: every device selects the same
        branch, and a NaN batch still costs one skipped step."""
        from raft_tpu.parallel import (
            make_mesh, make_sharded_train_step, shard_batch, shard_state,
        )

        model, tx, state = _tiny_model_and_tx()
        mesh = make_mesh(space=1)
        state = shard_state(state, mesh)
        step = make_sharded_train_step(
            model, tx, mesh, num_flow_updates=2, donate=False,
            numerics_policy="skip",
        )
        batch = shard_batch(_batch(b=8), mesh)
        s1, m1 = step(state, batch)
        assert float(m1["skipped"]) == 0.0
        bad = shard_batch(
            {k: np.asarray(v) for k, v in _nan_batch(_batch(b=8)).items()},
            mesh,
        )
        s2, m2 = step(s1, bad)
        assert float(m2["skipped"]) == 1.0
        assert int(s2.skipped_steps) == 1
        assert _tree_equal(s1.params, s2.params)


# ---------------------------------------------------------------------------
# Per-leaf nonfinite attribution (NumericsError satellite)
# ---------------------------------------------------------------------------


class TestNonfiniteLeafCounts:
    def test_counts_and_paths_align(self):
        from raft_tpu.utils.debug import leaf_paths, nonfinite_leaf_counts

        tree = {
            "a": jnp.asarray([1.0, jnp.nan, jnp.inf]),
            "b": jnp.asarray([1.0, 2.0]),
            "n": jnp.asarray([3], jnp.int32),  # non-float: constant 0
        }
        counts = np.asarray(nonfinite_leaf_counts(tree))
        paths = leaf_paths(tree)
        assert len(counts) == len(paths)
        report = {p: int(c) for p, c in zip(paths, counts) if c}
        assert report == {"['a']": 2}

    def test_empty_tree(self):
        from raft_tpu.utils.debug import nonfinite_leaf_counts

        assert nonfinite_leaf_counts({}).shape == (0,)


# ---------------------------------------------------------------------------
# StabilityMonitor (escalation bookkeeping)
# ---------------------------------------------------------------------------


class TestStabilityMonitor:
    def test_breach_threshold(self):
        mon = StabilityMonitor(StabilityPolicy(skip_budget=3))
        assert not mon.breached(3)  # at budget = tolerated
        assert mon.breached(4)
        assert mon.total_skipped == 7

    def test_escalation_raises_with_trail(self):
        mon = StabilityMonitor(
            StabilityPolicy(skip_budget=0, max_rollbacks=2,
                            rollback_lr_scale=0.5),
            base_seed=7,
        )
        mon.check_escalation(100, 5)  # budget left: no raise
        a1 = mon.record_rollback(100, 90, 5)
        assert a1.seed == perturb_seed(7, 1) and a1.lr_scale == 0.5
        a2 = mon.record_rollback(200, 190, 6)
        assert a2.seed == perturb_seed(7, 2) and a2.lr_scale == 0.25
        with pytest.raises(DivergenceError) as ei:
            mon.check_escalation(300, 9)
        assert ei.value.attempts == (a1, a2)
        msg = str(ei.value)
        assert "step 300" in msg and "rolled back to step 90" in msg

    def test_fail_is_unconditional(self):
        mon = StabilityMonitor(StabilityPolicy())
        with pytest.raises(DivergenceError, match="no checkpoint"):
            mon.fail(10, 6, "no checkpoint dir")

    def test_perturbed_seeds_distinct(self):
        seeds = {perturb_seed(0, k) for k in range(5)}
        assert len(seeds) == 5
        assert perturb_seed(3, 2) == perturb_seed(3, 2)  # deterministic

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="skip_budget"):
            StabilityPolicy(skip_budget=-1)
        with pytest.raises(ValueError, match="max_rollbacks"):
            StabilityPolicy(max_rollbacks=-1)
        with pytest.raises(ValueError, match="rollback_lr_scale"):
            StabilityPolicy(rollback_lr_scale=0.0)
        with pytest.raises(ValueError, match="rollback_lr_scale"):
            StabilityPolicy(rollback_lr_scale=1.5)


# ---------------------------------------------------------------------------
# Known-good checkpoint tagging (tentpole part 4)
# ---------------------------------------------------------------------------


class TestKnownGoodTags:
    def _mgr(self, directory, specs, keep=None):
        from tests.test_faults import _state

        from raft_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(directory), max_to_keep=keep or len(specs))
        for step, val in specs:
            assert mgr.save(step, _state(val, step), force=True)
        mgr.wait()
        return mgr

    def test_tag_roundtrip_and_untag(self, tmp_path):
        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0), (2, 2.0)])
        mgr.tag_good(1, {"loss": 0.5})
        mgr.tag_good(2)
        assert mgr.good_steps() == {1: {"loss": 0.5}, 2: {}}
        mgr.untag_good(1)
        assert set(mgr.good_steps()) == {2}
        mgr.close()

    def test_restore_prefers_tagged_over_newer_untagged(self, tmp_path):
        from tests.test_faults import _template

        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0), (2, 2.0), (3, 3.0)])
        mgr.tag_good(2)
        restored = mgr.restore_known_good(_template())
        assert int(restored["step"]) == 2  # newest GOOD beats newest
        mgr.close()

    def test_restore_falls_back_to_untagged(self, tmp_path):
        from tests.test_faults import _template

        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0), (2, 2.0)])
        restored = mgr.restore_known_good(_template())
        assert int(restored["step"]) == 2  # merely readable beats nothing
        mgr.close()

    def test_before_excludes_diverged_steps(self, tmp_path):
        from tests.test_faults import _template

        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0), (2, 2.0), (3, 3.0)])
        mgr.tag_good(1)
        mgr.tag_good(3)
        restored = mgr.restore_known_good(_template(), before=3)
        assert int(restored["step"]) == 1
        mgr.close()

    def test_corrupt_tagged_step_quarantined_and_untagged(self, tmp_path):
        from tests.test_faults import _template

        from raft_tpu.utils.faults import tear_checkpoint

        ckpt = tmp_path / "ckpt"
        mgr = self._mgr(ckpt, [(1, 1.0), (2, 2.0)])
        mgr.tag_good(1)
        mgr.tag_good(2)
        tear_checkpoint(str(ckpt), 2)
        restored = mgr.restore_known_good(_template())
        assert int(restored["step"]) == 1
        assert mgr.quarantined_steps == [2]
        assert set(mgr.good_steps()) == {1}  # tag followed the quarantine
        mgr.close()

    def test_delete_drops_step_and_tag(self, tmp_path):
        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0), (2, 2.0)])
        mgr.tag_good(2)
        mgr.delete(2)
        assert mgr.all_steps() == [1]
        assert mgr.good_steps() == {}
        mgr.close()

    def test_corrupt_tag_file_is_empty(self, tmp_path):
        mgr = self._mgr(tmp_path / "ckpt", [(1, 1.0)])
        with open(os.path.join(mgr.directory, "known_good.json"), "w") as f:
            f.write("{not json")
        assert mgr.good_steps() == {}
        mgr.tag_good(1)  # and tagging recovers the file
        assert set(mgr.good_steps()) == {1}
        mgr.close()


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------


def _trainer(tmp_path, monkeypatch, **kw):
    from tests.test_faults import TrainerDS, _tiny_raft_small

    from raft_tpu.models import zoo
    from raft_tpu.train.trainer import TrainConfig, Trainer

    monkeypatch.setitem(zoo.CONFIGS, "raft_small", _tiny_raft_small())
    defaults = dict(
        arch="raft_small", num_steps=10, global_batch_size=2,
        num_flow_updates=2, crop_size=(128, 128), log_every=5,
        data_mesh=False,
    )
    defaults.update(kw)
    config = TrainConfig(**defaults)
    return Trainer(config, TrainerDS(n=50)), config


class TestNumericsErrorDiagnosis:
    def test_raise_mode_names_step_and_grad_leaves(self, tmp_path, monkeypatch):
        """Satellite: a raise-mode death is diagnosable from the log —
        the message carries the failing step number and the offending
        gradient leaf paths."""
        from raft_tpu.utils.debug import NumericsError

        tr, _ = _trainer(tmp_path, monkeypatch, check_numerics=True)
        inj = FaultInjector()
        inj.on("step.nan_grads", when=2, action=FaultInjector.nan_grads)
        with inj.patch_batches(tr):
            with pytest.raises(NumericsError) as ei:
                tr.run(log_fn=lambda *_: None)
        msg = str(ei.value)
        assert "at step 3" in msg  # 0-based injection index 2 = step 3
        assert "offending gradient leaves" in msg
        assert "kernel" in msg  # real leaf paths, not just a count
        assert "numerics_policy='skip'" in msg  # points at the recovery


class TestTrainerSkipGuard:
    def test_burst_skipped_run_completes(self, tmp_path, monkeypatch):
        """A transient NaN burst under 'skip' costs exactly its steps: the
        run completes, train/skipped is logged, loss stays finite."""
        scalars = []
        tr, _ = _trainer(
            tmp_path, monkeypatch, num_steps=10,
            numerics_policy="skip", skip_budget=5,
        )
        inj = FaultInjector()
        inj.on("step.nan_grads", when=(2, 3), action=FaultInjector.nan_grads)
        with inj.patch_batches(tr):
            state = tr.run(log_fn=lambda s, m: scalars.append((s, m)))
        assert int(state.step) == 10
        assert int(state.skipped_steps) == 2
        skipped_logged = {s: m.get("train/skipped") for s, m in scalars}
        assert skipped_logged[5] == 2.0 and skipped_logged[10] == 0.0
        assert all(
            np.isfinite(m["loss"]) for _, m in scalars if "loss" in m
        )

    def test_no_rollback_without_checkpoints_raises(self, tmp_path, monkeypatch):
        """Budget breach with no checkpoint_dir cannot recover: the run
        dies with DivergenceError, not a silent skip-forever loop."""
        tr, _ = _trainer(
            tmp_path, monkeypatch, num_steps=10,
            numerics_policy="skip", skip_budget=2,
        )
        inj = FaultInjector()
        inj.on(
            "step.nan_grads", when=(0, 1, 2, 3), action=FaultInjector.nan_grads
        )
        with inj.patch_batches(tr):
            with pytest.raises(DivergenceError, match="no checkpoint_dir"):
                tr.run(log_fn=lambda *_: None)


class TestRollbackWatchdog:
    def test_hung_rollback_restore_stalls_out(self, tmp_path, monkeypatch):
        """Satellite: the recovery path itself is watchdog-armed — a
        wedged known-good restore dumps stacks and raises StallError
        instead of hanging the rollback forever."""
        tr, _ = _trainer(
            tmp_path, monkeypatch, num_steps=20, log_every=5,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=5,
            log_dir=str(tmp_path / "logs"),
            numerics_policy="skip", skip_budget=1, watchdog_timeout=1.0,
        )
        orig = tr.manager.restore_known_good
        t0 = time.monotonic()
        marks = {}

        def wedged(*a, **kw):
            # wedge long enough that only the watchdog can free the run,
            # scaled to this machine's measured speed (a constant 30 s is
            # indistinguishable from a slow machine's healthy prefix)
            calib = marks["t_fault"] - t0
            time.sleep(max(30.0, 5.0 * calib))
            return orig(*a, **kw)

        monkeypatch.setattr(tr.manager, "restore_known_good", wedged)
        inj = FaultInjector()

        def faulting_steps(i, ctx):
            if 5 <= i < 10:
                # calibration mark: compile + 5 healthy steps + ckpt, as
                # measured on THIS machine — the wall bound below scales
                # from it instead of assuming machine speed
                marks.setdefault("t_fault", time.monotonic())
                return True
            return False

        inj.on(
            "step.nan_grads", when=faulting_steps,
            action=FaultInjector.nan_grads,
        )
        with inj.patch_batches(tr):
            with pytest.raises(StallError, match="rollback"):
                tr.run(log_fn=lambda *_: None)
        elapsed = time.monotonic() - t0
        calib = marks["t_fault"] - t0
        # freed by the watchdog: everything after the calibration point is
        # a few faulting steps + the 1 s watchdog, so 2x the measured
        # prefix + slack always discriminates from the wedge, which sleeps
        # max(30, 5 * calib) — strictly past this bound on any machine
        assert elapsed < 2.0 * calib + 15.0, (elapsed, calib)
        dump = tmp_path / "logs" / "stall_stacks.log"
        assert dump.exists() and "rollback" in dump.read_text()


class TestChaosEndToEnd:
    def test_divergence_acceptance_scenario(self, tmp_path, monkeypatch):
        """The ISSUE acceptance run: a 60-step run with an injected
        NaN-grad burst and one injected persistent-divergence window.
        Early NaN steps are skipped (train/skipped >= 1, params protected
        on those steps), the divergence window triggers exactly ONE
        rollback to a known-good step with a perturbed data order (and a
        scaled LR), and the run finishes with finite loss."""
        scalars = []
        tr, config = _trainer(
            tmp_path, monkeypatch, num_steps=60, log_every=10, seed=3,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=10,
            log_dir=str(tmp_path / "logs"),
            numerics_policy="skip", spike_factor=0.0, skip_budget=3,
            max_rollbacks=3, rollback_lr_scale=0.5,
        )
        inj = FaultInjector()
        # transient burst: steps 5-6 (skippable, far under budget/window)
        inj.on("step.nan_grads", when=(4, 5), action=FaultInjector.nan_grads)
        # persistent divergence: every step of the 31..40 window faults
        inj.on(
            "step.nan_grads",
            when=lambda i, ctx: 30 <= i < 40,
            action=FaultInjector.nan_grads,
        )
        with inj.patch_batches(tr):
            state = tr.run(log_fn=lambda s, m: scalars.append((s, dict(m))))
        tr.manager.wait()

        # run completed, with the burst skipped and exactly one rollback
        assert int(state.step) == 60
        assert len(tr.stability.rollbacks) == 1
        attempt = tr.stability.rollbacks[0]
        assert attempt.at_step == 40 and attempt.to_step == 30
        assert attempt.window_skips == 10
        # data order was perturbed and the LR scaled for the replay
        assert attempt.seed == perturb_seed(3, 1) != config.seed
        assert tr.pipeline.seed == attempt.seed
        assert tr._lr_scale == 0.5
        # the burst was skipped and surfaced at its boundary
        by_step = {}
        for s, m in scalars:
            by_step.setdefault(s, {}).update(m)
        assert by_step[10]["train/skipped"] >= 2.0
        assert by_step[40]["stability/rollback_to"] == 30.0
        # post-rollback the replayed trajectory is clean and finite
        assert by_step[60]["train/skipped"] == 0.0
        assert by_step[60]["stability/rollbacks"] == 1.0
        assert np.isfinite(by_step[60]["loss"])
        # durable scalars carry the same story
        lines = [
            json.loads(l)
            for l in open(tmp_path / "logs" / "scalars.jsonl")
            .read()
            .splitlines()
        ]
        assert any(l.get("train/skipped", 0) >= 1 for l in lines)
        # post-run checkpoints tagged known-good again
        assert len(tr.manager.good_steps()) >= 1

    def test_raise_mode_still_fails_fast(self, tmp_path, monkeypatch):
        """Backward compat: the same injection under
        numerics_policy='raise' + check_numerics dies with NumericsError
        at the first boundary after the burst."""
        from raft_tpu.utils.debug import NumericsError

        tr, _ = _trainer(
            tmp_path, monkeypatch, num_steps=60, log_every=10,
            checkpoint_dir=str(tmp_path / "ckpt2"), checkpoint_every=10,
            numerics_policy="raise", check_numerics=True,
        )
        inj = FaultInjector()
        inj.on("step.nan_grads", when=(4, 5), action=FaultInjector.nan_grads)
        with inj.patch_batches(tr):
            with pytest.raises(NumericsError, match="at step 5"):
                tr.run(log_fn=lambda *_: None)

    @pytest.mark.slow
    def test_persistent_divergence_exhausts_rollbacks(self, tmp_path, monkeypatch):
        """Every window diverges: after max_rollbacks the run dies with
        DivergenceError carrying the full attempt trail."""
        tr, _ = _trainer(
            tmp_path, monkeypatch, num_steps=40, log_every=5, seed=11,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=5,
            numerics_policy="skip", skip_budget=2, max_rollbacks=2,
            rollback_lr_scale=0.5,
        )
        inj = FaultInjector()
        inj.on(
            "step.nan_grads",
            when=lambda i, ctx: i >= 10,
            action=FaultInjector.nan_grads,
        )
        with inj.patch_batches(tr):
            with pytest.raises(DivergenceError) as ei:
                tr.run(log_fn=lambda *_: None)
        tr.manager.wait()
        tr.manager.close()  # drain async saves the raise left queued
        assert len(ei.value.attempts) == 2
        assert ei.value.attempts[0].lr_scale == 0.5
        assert ei.value.attempts[1].lr_scale == 0.25
        assert "attempt trail" in str(ei.value)
