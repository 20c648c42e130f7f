"""Fused multi-step window dispatch: parity + tripwire suite (ISSUE 5).

Proves, not claims, the window contract:

  * ``window_size=k`` equals the per-step loop step for step — final
    params/opt state allclose (XLA fuses a scan body slightly differently
    than straight-line code, so float trajectories drift at the ~1e-5
    relative level per step), while the DISCRETE semantics the stability
    ladder depends on (skip decisions, skip/good counters, NaN-poisoned
    metric patterns, rollback escalation) are bitwise-equal — under
    injected ``step.nan_grads`` / ``step.loss_spike`` faults at window
    boundaries and mid-window alike;
  * the hot path never syncs with the host inside a window
    (``utils.tripwire.HostSyncTripwire`` monkeypatch-counts every
    device->host leak and asserts zero);
  * the pipeline's stacked windows carry the same batches, in the same
    order, as ``k`` per-step draws, through ONE device transfer.

All CPU tier-1; the longer multi-rollback fault ladder stays behind
``slow``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.utils.faults import FaultInjector
from raft_tpu.utils.tripwire import HostSyncError, HostSyncTripwire


import functools


@functools.lru_cache(maxsize=1)
def _tiny_model_and_tx():
    # cached: every direct-step test reuses the same (read-only) model,
    # optimizer and initial state — all step fns here use donate=False
    from tests.test_train import tiny_cfg

    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.train import TrainState, make_optimizer

    model = build_raft(tiny_cfg(large=False))
    variables = init_variables(model)
    tx = make_optimizer(1e-3, weight_decay=1e-5)
    return model, tx, TrainState.create(variables, tx)


def _batches(n, seed=0, b=2, hw=(128, 128)):
    from tests.test_train import make_batch

    rng = np.random.default_rng(seed)
    return [
        {k: np.asarray(v) for k, v in
         make_batch(rng, b=b, h=hw[0], w=hw[1]).items()}
        for _ in range(n)
    ]


def _stack(batches):
    return {k: np.stack([b[k] for b in batches]) for k in batches[0]}


def _tree_allclose(a, b, rtol, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x, np.float64), np.asarray(y, np.float64),
            rtol=rtol, atol=atol,
        )


GUARD_KW = dict(
    num_flow_updates=2, numerics_policy="skip",
    spike_factor=3.0, ema_decay=0.5, spike_warmup=2,
)


def _ulp_nudged(batches):
    """The same batches with ``image1`` moved ONE fp32 ulp: the smallest
    input change fp32 can represent. Running the per-step loop on these
    measures how far this (chaotic, unrolled-GRU) training trajectory
    amplifies rounding-level noise — i.e. what fp32 can promise for a
    scan-vs-loop comparison, whose only difference is XLA reassociating
    the same fp32 math. Tolerances below are multiples of that measured
    floor instead of hand-picked constants."""
    out = []
    for b in batches:
        nb = dict(b)
        nb["image1"] = np.nextafter(
            np.asarray(b["image1"], np.float32), np.float32(np.inf)
        )
        out.append(nb)
    return out


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(a)), 1e-30)


def _run_per_step(model, tx, state, batches, step=None, **kw):
    from raft_tpu.train import make_train_step

    if step is None:
        step = make_train_step(model, tx, donate=False, **kw)
    metrics = []
    for b in batches:
        state, m = step(state, b)
        metrics.append(jax.device_get(m))
    return state, metrics


def _run_windows(model, tx, state, batches, k, **kw):
    from raft_tpu.train import make_window_step

    win = make_window_step(model, tx, window_size=k, donate=False, **kw)
    metrics = []
    for i in range(0, len(batches), k):
        state, stacked = win(state, _stack(batches[i: i + k]))
        stacked = jax.device_get(stacked)
        metrics.extend(
            {key: v[j] for key, v in stacked.items()} for j in range(k)
        )
    return state, metrics


# ---------------------------------------------------------------------------
# Window step (tentpole part 1): lax.scan of the per-step body
# ---------------------------------------------------------------------------


class TestWindowStep:
    def test_matches_per_step_loop(self):
        """k=4 windows over 8 steps land where 8 per-step dispatches land
        (params/opt allclose; loss trajectory step for step).

        SGD at a small LR, like the repo's DP-vs-single-device parity
        tests use SGD: one scanned step is near-bitwise (measured 3e-7
        abs param drift — pure XLA scan-vs-straight-line fusion noise),
        but any per-step perturbation amplifies chaotically through the
        unrolled-GRU loss landscape at training LRs (measured 2.7e-2 abs
        after 4 steps at lr=1e-3, optimizer-independent), so the
        multi-step comparison is run where the trajectory map is
        well-conditioned. The semantic claim — scan(k) IS k sequential
        steps — is LR-independent; realistic-LR trajectories are covered
        by the trainer-level parity test's loss/epe bounds and the
        bitwise counter tests below."""
        import optax

        from raft_tpu.train import TrainState

        model, _, state_a = _tiny_model_and_tx()
        tx = optax.sgd(1e-6)
        state0 = TrainState.create({"params": state_a.params}, tx)
        batches = _batches(8)
        from raft_tpu.train import make_train_step

        step = make_train_step(model, tx, donate=False, num_flow_updates=2)
        s1, m1 = _run_per_step(model, tx, state0, batches, step=step)
        s2, m2 = _run_windows(model, tx, state0, batches, 4,
                              num_flow_updates=2)
        assert int(s1.step) == int(s2.step) == 8
        _tree_allclose(s1.params, s2.params, rtol=1e-3, atol=1e-5)
        _tree_allclose(s1.opt_state, s2.opt_state, rtol=1e-3, atol=1e-5)
        # Loss trajectory, step for step. The tolerance is what fp32 can
        # promise at this magnitude, measured: a ONE-ulp nudge of image1
        # moves the loss (~180) by 3e-7 relative at step 1 and 1.07e-4 at
        # step 8 (the trajectory amplifies rounding noise ~3x per step,
        # even at this LR), and scan-vs-loop reassociation lands on the
        # same curve (0 at step 1, 1.08e-4 at step 8 under jax 0.9's
        # XLA). So each step is held to 10x the running ulp-noise floor,
        # never below 1e-6 (a few ulps of a sum this size).
        _, m3 = _run_per_step(
            model, tx, state0, _ulp_nudged(batches), step=step
        )
        floor = 1e-6
        for a, b, c in zip(m1, m2, m3):
            floor = max(floor, _rel(a["loss"], c["loss"]))
            assert _rel(a["loss"], b["loss"]) <= 10 * floor, (
                a["loss"], b["loss"], floor
            )

    def test_guard_counters_bitwise_under_faults(self):
        """NaN faults mid-window (step idx 1) AND at a window boundary
        (idx 4 = first step of window 2): skip/good counters and the
        per-step skipped/NaN-metric pattern are bitwise those of the
        per-step guarded loop."""
        model, tx, state0 = _tiny_model_and_tx()
        batches = _batches(8)
        for idx in (1, 4):
            FaultInjector.nan_grads(batches[idx])
        s1, m1 = _run_per_step(model, tx, state0, batches, **GUARD_KW)
        s2, m2 = _run_windows(model, tx, state0, batches, 4, **GUARD_KW)
        assert int(s1.skipped_steps) == int(s2.skipped_steps) == 2
        assert int(s1.good_steps) == int(s2.good_steps) == 6
        skipped1 = [float(m["skipped"]) for m in m1]
        skipped2 = [float(m["skipped"]) for m in m2]
        assert skipped1 == skipped2 == [0, 1, 0, 0, 1, 0, 0, 0]
        # a skipped step's metrics carry the poisoned loss in BOTH paths
        nan1 = [bool(np.isnan(m["loss"])) for m in m1]
        nan2 = [bool(np.isnan(m["loss"])) for m in m2]
        assert nan1 == nan2
        assert np.isfinite(float(s2.grad_ema))
        np.testing.assert_allclose(
            float(s1.grad_ema), float(s2.grad_ema), rtol=5e-2
        )

    def test_spike_detector_parity(self):
        """A finite grad-norm spike inside a window is skipped exactly as
        in the per-step loop, and the EMA ignores it in both."""
        model, tx, state0 = _tiny_model_and_tx()
        batches = _batches(8)
        from raft_tpu.train import make_train_step

        FaultInjector.loss_spike(batches[5])
        step = make_train_step(model, tx, donate=False, **GUARD_KW)
        s1, m1 = _run_per_step(model, tx, state0, batches, step=step)
        s2, m2 = _run_windows(model, tx, state0, batches, 4, **GUARD_KW)
        assert int(s1.skipped_steps) == int(s2.skipped_steps) == 1
        assert [float(m["skipped"]) for m in m2] == [0, 0, 0, 0, 0, 1, 0, 0]
        assert np.isfinite(float(m2[5]["grad_norm"]))
        # The EMA after 8 AdamW steps at lr=1e-3 sits at the end of a
        # chaotic trajectory: held to 10x what a ONE-ulp input nudge does
        # to it (the fp32 noise floor of this comparison, measured in
        # place), never below the 5e-2 the skip semantics need.
        s3, _ = _run_per_step(
            model, tx, state0, _ulp_nudged(batches), step=step
        )
        floor = max(5e-3, _rel(s1.grad_ema, s3.grad_ema))
        assert _rel(s1.grad_ema, s2.grad_ema) <= 10 * floor, (
            float(s1.grad_ema), float(s2.grad_ema), floor
        )

    def test_jaxpr_is_host_callback_free(self):
        """Hot-path purity: the fused window lowers to pure device code."""
        from raft_tpu.train.step import make_window_step_fn

        model, tx, state = _tiny_model_and_tx()
        fn = make_window_step_fn(model, tx, window_size=2, **GUARD_KW)
        jaxpr = str(jax.make_jaxpr(fn)(state, _stack(_batches(2))))
        for forbidden in ("callback", "infeed", "outfeed", "outside_call"):
            assert forbidden not in jaxpr, f"host op {forbidden!r} in window"

    def test_metrics_stack_shape(self):
        """Metrics come out as ONE (k, ...) stacked tree — including the
        per-leaf diagnostic vector under check_numerics."""
        from raft_tpu.train import make_window_step

        model, tx, state = _tiny_model_and_tx()
        win = make_window_step(
            model, tx, window_size=3, donate=False,
            num_flow_updates=2, check_numerics=True,
        )
        _, m = win(state, _stack(_batches(3)))
        assert m["loss"].shape == (3,)
        assert m["nonfinite_grads"].shape == (3,)
        assert m["_nonfinite_leaves"].ndim == 2
        assert m["_nonfinite_leaves"].shape[0] == 3

    def test_invalid_window_size(self):
        from raft_tpu.train.step import make_window_step_fn

        model, tx, _ = _tiny_model_and_tx()
        with pytest.raises(ValueError, match="window_size"):
            make_window_step_fn(model, tx, window_size=0)

    def test_sharded_window_matches_single_device(self):
        """The mesh-sharded window (scan axis unsharded, batch over
        `data`) lands where the single-device window lands."""
        import optax

        from raft_tpu.parallel import (
            make_mesh, make_sharded_window_step, shard_state,
            window_batch_sharding,
        )
        from raft_tpu.train import TrainState, make_window_step
        from raft_tpu.models import build_raft, init_variables
        from tests.test_train import tiny_cfg

        model = build_raft(tiny_cfg(large=False))
        variables = init_variables(model)
        # SGD at a small LR: linear in the grad AND a well-conditioned
        # trajectory map, so the comparison bounds all-reduce reduction
        # noise + scan fusion noise, not chaotic amplification (see
        # test_matches_per_step_loop)
        tx = optax.sgd(1e-6)
        state = TrainState.create(variables, tx)
        batches = _batches(4, b=8)

        single = make_window_step(
            model, tx, window_size=2, donate=False, num_flow_updates=2
        )
        s1 = state
        for i in (0, 2):
            s1, m1 = single(s1, _stack(batches[i: i + 2]))

        mesh = make_mesh(data=8, space=1)
        sharded = make_sharded_window_step(
            model, tx, mesh, window_size=2, donate=False, num_flow_updates=2
        )
        s2 = shard_state(state, mesh)
        for i in (0, 2):
            win = jax.device_put(
                _stack(batches[i: i + 2]), window_batch_sharding(mesh)
            )
            s2, m2 = sharded(s2, win)
        np.testing.assert_allclose(
            np.asarray(m1["loss"]), np.asarray(m2["loss"]), rtol=1e-4
        )
        _tree_allclose(s1.params, s2.params, rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# Pipeline windows (tentpole part 2): staged, stacked, one transfer
# ---------------------------------------------------------------------------


class _UniformDS:
    """Synthetic uniform-resolution dataset (no augmentor needed)."""

    def __init__(self, n=32, hw=(64, 64)):
        self.n, self.hw = n, hw

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        h, w = self.hw
        return {
            "image1": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            "image2": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            "flow": rng.uniform(-5, 5, (h, w, 2)).astype(np.float32),
            "valid": np.ones((h, w), np.float32),
        }


class TestWindowPipeline:
    def _pipe(self, **kw):
        from raft_tpu.data.pipeline import TrainPipeline

        return TrainPipeline(_UniformDS(), 2, seed=7, **kw)

    def test_window_data_order_matches_per_step(self):
        """A k=2 window holds exactly the two batches the per-step
        pipeline would have yielded, in order."""
        per = self._pipe()
        it = iter(per)
        flat = [next(it) for _ in range(4)]
        it.close()
        win = self._pipe(window_size=2)
        wit = iter(win)
        windows = [next(wit) for _ in range(2)]
        wit.close()
        for w_idx, window in enumerate(windows):
            for j in range(2):
                ref = flat[2 * w_idx + j]
                for key in ref:
                    np.testing.assert_array_equal(
                        np.asarray(window[key])[j], ref[key]
                    )
        assert per.step == win.step == 4  # same step bookkeeping

    def test_windows_outlive_the_staging_ring(self):
        """A window already handed out stays intact while later windows
        are staged. On the CPU backend ``jax.device_put`` of an aligned
        numpy array is zero-copy (the device array IS the host buffer),
        so a ring slot that was transferred there must never be written
        again — it is retired (``_WindowStaging.transferred``) and the slot
        allocates anew. (This was the
        load-dependent windowed-vs-per-step drift: under CPU contention
        the producer thread rewrote a slot the train step had not read
        yet.)"""
        per = self._pipe()
        it = iter(per)
        flat = [next(it) for _ in range(12)]
        it.close()
        win = self._pipe(window_size=2, prefetch_depth=1)  # ring of 2
        wit = iter(win)
        windows = [next(wit) for _ in range(6)]
        wit.close()
        for w_idx, window in enumerate(windows):
            for j in range(2):
                for key, ref in flat[2 * w_idx + j].items():
                    np.testing.assert_array_equal(
                        np.asarray(window[key])[j], ref,
                        err_msg=f"window {w_idx} row {j} {key} rewritten",
                    )

    def test_staging_rotates_buffers(self):
        from raft_tpu.data.pipeline import _WindowStaging

        staging = _WindowStaging(slots=2)
        batches = _batches(6, b=1, hw=(32, 32))
        w0 = staging.stack(batches[0:2])
        w1 = staging.stack(batches[2:4])
        # different underlying buffers: w0 is still intact after w1
        assert w0["image1"] is not w1["image1"]
        np.testing.assert_array_equal(w0["image1"][0], batches[0]["image1"])
        # ring of 2: the third stack reuses (overwrites) w0's buffers
        w2 = staging.stack(batches[4:6])
        assert w2["image1"] is w0["image1"]
        np.testing.assert_array_equal(w2["image1"][1], batches[5]["image1"])

    def test_batch_transfer_is_one_device_put(self, monkeypatch):
        """Satellite: the whole batch tree moves in ONE jax.device_put
        call (a tree of shardings), not one call per leaf — windowed and
        per-step alike."""
        from raft_tpu.parallel import make_mesh

        calls = []
        orig = jax.device_put

        def counting(x, *a, **kw):
            calls.append(x)
            return orig(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", counting)
        pipe = self._pipe(mesh=make_mesh(space=1))
        batch = {  # batch divisible by the 8-way data axis
            "image1": np.zeros((8, 32, 32, 3), np.float32),
            "flow": np.zeros((8, 32, 32, 2), np.float32),
            "valid": np.ones((8, 32, 32), np.float32),
        }
        out = pipe._to_device(batch)
        assert len(calls) == 1 and isinstance(calls[0], dict)
        assert set(out) == set(batch)
        calls.clear()
        wpipe = self._pipe(mesh=make_mesh(space=1), window_size=2)
        window = {k: np.stack([v, v]) for k, v in batch.items()}
        wout = wpipe._to_device(window, window=True)
        assert len(calls) == 1 and isinstance(calls[0], dict)
        assert np.asarray(wout["image1"]).shape == (2, 8, 32, 32, 3)

    def test_invalid_window_size(self):
        with pytest.raises(ValueError, match="window_size"):
            self._pipe(window_size=0)


# ---------------------------------------------------------------------------
# Trainer integration (tentpole part 3)
# ---------------------------------------------------------------------------


def _trainer(monkeypatch, **kw):
    from tests.test_faults import TrainerDS, _tiny_raft_small

    from raft_tpu.models import zoo
    from raft_tpu.train.trainer import TrainConfig, Trainer

    monkeypatch.setitem(zoo.CONFIGS, "raft_small", _tiny_raft_small())
    defaults = dict(
        arch="raft_small", num_steps=8, global_batch_size=2,
        num_flow_updates=2, crop_size=(128, 128), log_every=4,
        data_mesh=False,
    )
    defaults.update(kw)
    config = TrainConfig(**defaults)
    return Trainer(config, TrainerDS(n=50)), config


@pytest.mark.chaos
class TestTrainerWindow:
    def test_run_parity_with_per_step(self, monkeypatch):
        """A windowed run logs the same boundaries with the same scalars
        (up to scan-fusion float noise) and lands on the same step.

        Measured on this installation (8 AdamW steps at the default LR):
        window-vs-loop boundary losses differ by 4e-4 (step 4) and 3e-3
        (step 8) relative, a ONE-ulp nudge of the first batch by 4e-5 /
        5e-4 — same trajectory, fp32 noise amplified ~10x by the scan's
        reassociation, well inside the 5e-2 below. The 5.5% step-4 gap
        this test once showed under a loaded xdist run was not float
        noise: the staging ring rewrote a zero-copy window the step had
        not read yet (see
        ``TestWindowPipeline::test_windows_outlive_the_staging_ring``)."""
        runs = {}
        for k in (1, 2):
            tr, _ = _trainer(monkeypatch, window_size=k)
            scalars = []
            state = tr.run(log_fn=lambda s, m: scalars.append((s, dict(m))))
            runs[k] = (state, scalars)
        s1, sc1 = runs[1]
        s2, sc2 = runs[2]
        assert int(s1.step) == int(s2.step) == 8
        assert [s for s, _ in sc1] == [s for s, _ in sc2] == [4, 8]
        for (_, m1), (_, m2) in zip(sc1, sc2):
            np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=0.05)
            np.testing.assert_allclose(m1["epe"], m2["epe"], rtol=0.05)
        _tree_allclose(s1.params, s2.params, rtol=0.1, atol=3e-3)

    def test_skip_accounting_parity_under_faults(self, monkeypatch):
        """One injection plan drives both loops (patch_batches splits the
        window host-side): skip counters and boundary train/skipped are
        bitwise-equal, mid-window (idx 1) and boundary (idx 4) faults
        alike."""
        out = {}
        for k in (1, 2):
            tr, _ = _trainer(
                monkeypatch, window_size=k, num_steps=8,
                numerics_policy="skip", skip_budget=3,
            )
            inj = FaultInjector()
            inj.on("step.nan_grads", when=(1, 4),
                   action=FaultInjector.nan_grads)
            scalars = []
            with inj.patch_batches(tr):
                state = tr.run(
                    log_fn=lambda s, m: scalars.append((s, dict(m)))
                )
            assert inj.counts["step.nan_grads"] == 8  # per STEP, not window
            out[k] = (state, dict(scalars))
        s1, sc1 = out[1]
        s2, sc2 = out[2]
        assert int(s1.skipped_steps) == int(s2.skipped_steps) == 2
        assert int(s1.good_steps) == int(s2.good_steps) == 6
        # injected call indices 1 and 4 are steps 2 and 5: one skip per
        # log window, surfaced at the window's boundary in BOTH loops
        assert sc1[4]["train/skipped"] == sc2[4]["train/skipped"] == 1.0
        assert sc1[8]["train/skipped"] == sc2[8]["train/skipped"] == 1.0

    def test_rollback_escalation_parity(self, monkeypatch, tmp_path):
        """A persistently diverging window breaches the budget at the same
        boundary, rolls back to the same known-good step with the same
        perturbed seed, windowed or not — and the windowed run re-enters
        cleanly at the (window-aligned) restored step."""
        from raft_tpu.train.stability import perturb_seed

        trails = {}
        for k in (1, 2):
            tr, config = _trainer(
                monkeypatch, window_size=k, num_steps=16, log_every=4,
                seed=3, checkpoint_dir=str(tmp_path / f"ckpt{k}"),
                checkpoint_every=4, numerics_policy="skip", skip_budget=2,
                max_rollbacks=2, rollback_lr_scale=0.5,
            )
            inj = FaultInjector()
            inj.on("step.nan_grads", when=lambda i, ctx: 8 <= i < 12,
                   action=FaultInjector.nan_grads)
            with inj.patch_batches(tr):
                state = tr.run(log_fn=lambda *_: None)
            tr.manager.wait()
            tr.manager.close()
            assert int(state.step) == 16
            trails[k] = [
                (a.at_step, a.to_step, a.window_skips, a.seed, a.lr_scale)
                for a in tr.stability.rollbacks
            ]
        assert trails[1] == trails[2]  # escalation bitwise-equal
        assert trails[2] == [(12, 8, 4, perturb_seed(3, 1), 0.5)]

    def test_alignment_validation(self, monkeypatch):
        for bad in (
            dict(log_every=5, window_size=2),
            dict(num_steps=10, window_size=4),
            dict(eval_every=6, window_size=4, log_every=4),
        ):
            with pytest.raises(ValueError, match="window_size|window"):
                _trainer(monkeypatch, **bad)
        with pytest.raises(ValueError, match="window_size"):
            _trainer(monkeypatch, window_size=0)

    def test_misaligned_resume_raises(self, monkeypatch):
        tr, _ = _trainer(monkeypatch, window_size=2, num_steps=8)
        tr.state = tr.state.replace(step=jnp.asarray(3, jnp.int32))
        with pytest.raises(ValueError, match="not a multiple"):
            tr.run(log_fn=lambda *_: None)

    @pytest.mark.slow
    def test_window_divergence_exhausts_rollbacks(self, monkeypatch, tmp_path):
        """Fault ladder end-to-end under windows: every window diverges,
        rollbacks exhaust, DivergenceError carries the trail."""
        from raft_tpu.train.stability import DivergenceError

        tr, _ = _trainer(
            monkeypatch, window_size=2, num_steps=24, log_every=4,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=4,
            numerics_policy="skip", skip_budget=2, max_rollbacks=2,
            rollback_lr_scale=0.5,
        )
        inj = FaultInjector()
        inj.on("step.nan_grads", when=lambda i, ctx: i >= 6,
               action=FaultInjector.nan_grads)
        with inj.patch_batches(tr):
            with pytest.raises(DivergenceError) as ei:
                tr.run(log_fn=lambda *_: None)
        tr.manager.wait()
        tr.manager.close()
        assert len(ei.value.attempts) == 2
        assert ei.value.attempts[1].lr_scale == 0.25


# ---------------------------------------------------------------------------
# Host-sync tripwire (tentpole part 4)
# ---------------------------------------------------------------------------


class TestHostSyncTripwire:
    def test_counts_every_leak(self):
        a = jnp.asarray([1.0, 2.0])
        with HostSyncTripwire() as tw:
            _ = jnp.sum(a) * 2  # pure device work: free
            assert tw.total == 0
            float(jnp.sum(a))
            int(jnp.asarray(3))
            bool(jnp.asarray(True))
            np.asarray(a)
            jax.device_get(a)
            jax.block_until_ready(a)
            snap = tw.snapshot()
        assert snap["__float__"] == 1
        assert snap["device_get"] == 1
        assert snap["block_until_ready"] == 1
        assert snap["__array__"] >= 1
        with pytest.raises(HostSyncError, match="host sync"):
            tw.assert_none()
        # patches restored
        assert float(jnp.asarray(1.5)) == 1.5

    def test_pause_and_arm_scoping(self):
        a = jnp.asarray(2.0)
        with HostSyncTripwire() as tw:
            with tw.pause():
                float(a)
            tw.assert_none()
            tw.disarm()
            float(a)
            tw.assert_none()
            tw.arm()
            float(a)
            assert tw.total == 1

    def test_zero_syncs_inside_window_loop(self):
        """The distilled hot loop at k=4: dispatch windows, retain device
        metrics — zero host syncs until the boundary fetch."""
        from raft_tpu.train import make_window_step

        model, tx, state = _tiny_model_and_tx()
        win = make_window_step(
            model, tx, window_size=4, donate=False, **GUARD_KW
        )
        windows = [_stack(_batches(4, seed=s)) for s in (0, 1)]
        # compile outside the guarded region (jit tracing/lowering may
        # legitimately touch host-sync entry points once)
        jax.block_until_ready(win(state, jax.device_put(windows[0]))[0].params)
        retained = []
        with HostSyncTripwire() as tw:
            for w in windows:
                state, metrics = win(state, jax.device_put(w))
                retained.append(metrics)
            tw.assert_none("the training window hot loop")
            with tw.pause():
                host = jax.device_get(retained)  # the one boundary fetch
        assert len(host) == 2 and host[0]["loss"].shape == (4,)

    @pytest.mark.chaos
    def test_trainer_hot_loop_zero_syncs(self, monkeypatch):
        """Whole-trainer guarantee: between the first window dispatch and
        each log boundary's single fetch, the windowed trainer never
        syncs (k=2, two boundaries, fault counters and all)."""
        from raft_tpu.train.trainer import Trainer

        tr, _ = _trainer(monkeypatch, window_size=2, num_steps=8)
        tw = HostSyncTripwire(armed=False)
        orig_window_fn = tr.window_fn

        def arming(state, batch):
            out = orig_window_fn(state, batch)
            tw.arm()  # count from the first dispatch's return ...
            return out

        tr.window_fn = arming
        orig_hw = Trainer._host_window

        def disarming(self, w):
            tw.disarm()  # ... to the boundary fetch
            return orig_hw(self, w)

        monkeypatch.setattr(Trainer, "_host_window", disarming)
        with tw:
            state = tr.run(log_fn=lambda *_: None)
        assert int(state.step) == 8
        tw.assert_none("the windowed trainer hot loop")


# ---------------------------------------------------------------------------
# train_bench smoke (the A/B joins the bench trajectory)
# ---------------------------------------------------------------------------


class TestTrainBenchSmoke:
    def test_tiny_bench_emits_report(self, capsys):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "script_train_bench",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts",
                "train_bench.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.main(
            ["--tiny", "--steps", "8", "--window-sizes", "1,4"]
        )
        by_k = {r["window_size"]: r for r in report["results"]}
        assert by_k[4]["dispatches_per_step"] == 0.25
        assert by_k[1]["dispatches_per_step"] == 1.0
        # the tripwire-verified acceptance property: ZERO host syncs
        # inside windows, for the fused path especially
        assert by_k[4]["host_syncs_in_window"] == 0
        assert by_k[1]["host_syncs_in_window"] == 0
        assert by_k[4]["finite"] and by_k[1]["finite"]
        # steps/s comparable on a short noisy CPU run; the full-length
        # A/B (scripts/train_bench.py --tiny) shows the >= win
        assert by_k[4]["steps_per_s"] > 0.5 * by_k[1]["steps_per_s"]
        out = capsys.readouterr().out
        assert '"metric": "train_steps_per_s"' in out
        assert '"metric": "train_host_syncs_per_step"' in out
        assert '"metric": "train_dispatches_per_step"' in out
        assert '"metric": "train_bench_report"' in out
