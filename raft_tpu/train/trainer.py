"""The training driver: config, loop, checkpoints, logging, eval.

Ties together the pieces the reference never had (SURVEY.md §0): input
pipeline -> sharded jit step -> metric logging -> Orbax checkpoint/resume.
Stage presets encode the RAFT C -> T -> S/K/H curriculum (paper §4 /
torchvision recipe); each stage is one ``TrainConfig``.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from raft_tpu.data.augment import AugmentConfig, FlowAugmentor
from raft_tpu.data.pipeline import TrainPipeline
from raft_tpu.models.zoo import CONFIGS, build_raft, init_variables
from raft_tpu.train.optim import make_optimizer, one_cycle_lr
from raft_tpu.train.state import TrainState

__all__ = ["TrainConfig", "STAGES", "Trainer"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    arch: str = "raft_large"
    stage: str = "chairs"
    num_steps: int = 100_000
    global_batch_size: int = 8
    learning_rate: float = 4e-4
    weight_decay: float = 1e-4
    clip_norm: float = 1.0
    num_flow_updates: int = 12
    gamma: float = 0.8
    max_flow: float = 400.0
    crop_size: Tuple[int, int] = (368, 496)
    seed: int = 0
    # infra
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 5_000
    log_every: int = 100
    log_dir: Optional[str] = None  # durable scalars (JSONL + TensorBoard)
    profile_port: Optional[int] = None  # jax.profiler.start_server opt-in
    remat: bool = False
    # selective-remat policy under remat=True (models.raft.REMAT_POLICIES)
    remat_policy: Optional[str] = None
    corr_impl: str = "dense"
    # storage dtype for the correlation pyramid (None | 'bfloat16'): half
    # the volume's bytes. Gradients are the VJP of the XLA formulation
    # either way. Its effect on training throughput is not measured on
    # this chip (the training cell is parked, PERF.md §7).
    corr_dtype: Optional[str] = None
    # conv/activation compute dtype (None=fp32 | 'bfloat16'): half the
    # activations' bytes in the backward graph; throughput not measured
    # on this chip either. Params, norm statistics, flow arithmetic, and
    # the loss stay fp32 — the checkpoint tree and EPE-critical paths are
    # unaffected.
    compute_dtype: Optional[str] = None
    data_mesh: bool = True  # shard over all devices' `data` axis
    # Fused multi-step dispatch (docs/perf_notes.md, training-throughput
    # section): window_size=k > 1 lax.scans k train steps per device
    # dispatch over a stacked batch window, with metrics accumulated on
    # device — the host touches the device once per WINDOW (dispatch) and
    # once per LOG BOUNDARY (one stacked metrics fetch), eliminating the
    # per-step Python dispatch + per-step metric retention that dominate
    # trainer overhead once the step itself is fast. Semantics are those
    # of the per-step loop, step for step (skip-guard counters and
    # escalation bitwise-identical; float trajectories equal up to XLA
    # scan-vs-straight-line fusion noise, ~1e-5 relative). window_size=1
    # is exactly today's per-step behavior. log_every, checkpoint_every
    # and eval_every must be multiples of window_size (boundaries are
    # window-aligned); preemption is honored at boundaries as before, so
    # a preemption costs at most one window of recompute.
    window_size: int = 1
    # In-loop validation (the north star's C->T->S/K/H schedule is driven
    # by EPE on a held-out split — the reference's acceptance protocol,
    # validate_sintel.py:164-206 — so the trainer must see it, not train
    # blind). 0 disables; otherwise every `eval_every` steps process 0
    # runs the protocol-exact validate() on host-fetched weights, logs
    # eval/* scalars, and exports the best-EPE weights to
    # `<checkpoint_dir>/best.msgpack`.
    eval_every: int = 0
    eval_num_flow_updates: int = 32
    # Padding/metric protocol for in-loop eval ('sintel' = split vertical
    # pad + unmasked EPE, 'downstream' = bottom-only pad). None infers
    # from the dataset: Sintel type -> 'sintel', everything else ->
    # 'downstream' (matching what scripts/validate.py gives the same
    # data; sparse GT additionally gets the masked-EPE path).
    eval_mode: Optional[str] = None
    # NaN/inf watchdog (SURVEY.md §5.2): adds an on-device nonfinite-grad
    # counter to every step and raises NumericsError (with a per-leaf
    # report + checkify re-run instructions) at the log boundary it trips.
    check_numerics: bool = False
    # --- fault tolerance (docs/failure_model.md) ---
    # Data-pipeline fault policy: 'skip' quarantines samples that fail to
    # load (transient OSErrors retried with backoff first; bounded by
    # data_bad_sample_budget distinct bad samples) and refills the batch;
    # 'raise' propagates after the transient retries (fail-fast).
    # data/skipped + data/retries counters surface at the log boundary.
    data_fault_policy: str = "skip"
    data_bad_sample_budget: int = 64
    data_max_retries: int = 2
    # In-loop eval failures (OOM, one bad val sample): 'skip' logs an
    # eval/failed scalar and keeps training; 'raise' kills the run.
    eval_fault_policy: str = "skip"
    # Stall watchdog: seconds a step dispatch / data fetch / device sync /
    # checkpoint wait may block before all-thread stacks are dumped and
    # StallError is raised (utils.faults.Watchdog). None disables. Stacks
    # go to <log_dir>/stall_stacks.log when log_dir is set, else stderr.
    watchdog_timeout: Optional[float] = None
    # --- divergence resilience (docs/failure_model.md, model-fault ladder)
    # 'raise': the pre-existing fail-fast behavior (check_numerics raises
    # NumericsError at the log boundary). 'skip': the in-step guard
    # (train/step.py) applies-or-skips the whole update on device — a
    # non-finite gradient burst or a grad-norm spike costs one step, not
    # the run; skips surface as the train/skipped counter at boundaries.
    numerics_policy: str = "raise"
    # Skip updates whose gradient global-norm exceeds spike_factor x the
    # EMA of applied-step grad norms (0 disables; only under 'skip'). The
    # EMA needs spike_warmup applied updates before the detector arms.
    spike_factor: float = 20.0
    spike_warmup: int = 20
    # More than skip_budget skipped steps inside one log window = the run
    # is persistently diverging: roll back to the last known-good
    # checkpoint, perturb the data-order seed, and optionally scale the LR
    # by rollback_lr_scale. After max_rollbacks breaches, raise
    # DivergenceError with the full attempt trail.
    skip_budget: int = 5
    max_rollbacks: int = 3
    rollback_lr_scale: float = 1.0
    # Eval-EPE regression tolerated before a checkpoint stops being tagged
    # known-good (fraction of the best EPE so far; only with eval_every).
    good_epe_slack: float = 0.2

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


# Stage presets: (dataset mix, crop, lr, steps, batch, iters) following the
# RAFT schedule. Dataset construction is a callable(root_paths) so dataset
# roots stay out of the config.
STAGES: Dict[str, Dict] = {
    "chairs": dict(
        crop_size=(368, 496), learning_rate=4e-4, num_steps=100_000,
        global_batch_size=8, num_flow_updates=12, sparse=False,
        min_scale=-0.1, max_scale=1.0,
    ),
    "things": dict(
        crop_size=(400, 720), learning_rate=1.25e-4, num_steps=100_000,
        global_batch_size=6, num_flow_updates=12, sparse=False,
        min_scale=-0.4, max_scale=0.8,
    ),
    "sintel": dict(
        crop_size=(368, 768), learning_rate=1.25e-4, num_steps=100_000,
        global_batch_size=6, num_flow_updates=12, sparse=False,
        min_scale=-0.2, max_scale=0.6,
    ),
    "kitti": dict(
        crop_size=(288, 960), learning_rate=1e-4, num_steps=50_000,
        global_batch_size=6, num_flow_updates=12, sparse=True,
        min_scale=-0.2, max_scale=0.4,
    ),
}


class Trainer:
    """Owns model/state/pipeline; ``run`` executes the loop.

    Single-host and multi-chip: the step is mesh-sharded when more than one
    device is visible (or ``config.data_mesh``); multi-host works through
    the pipeline's process sharding + ``jax.distributed`` initialization
    done by the caller.
    """

    @staticmethod
    def model_config(config: TrainConfig):
        """Resolve the TrainConfig's model knobs into a RAFTConfig.

        ``compute_dtype`` must change ONLY conv/activation compute (its
        documented contract): the zoo resolves ``corr_dtype=None`` as
        "follow compute_dtype", so when the caller sets compute_dtype
        without an explicit corr_dtype the correlation storage is pinned
        to fp32 here (the zoo maps 'float32' back to no-cast)."""
        model_cfg = CONFIGS[config.arch].replace(
            remat=config.remat, remat_policy=config.remat_policy,
            corr_impl=config.corr_impl, corr_dtype=config.corr_dtype,
        )
        if config.compute_dtype is not None:
            model_cfg = model_cfg.replace(compute_dtype=config.compute_dtype)
            if config.corr_dtype is None:
                model_cfg = model_cfg.replace(corr_dtype="float32")
        return model_cfg

    def __init__(self, config: TrainConfig, dataset, *, init_from=None,
                 eval_dataset=None, eval_fn=None):
        if config.compute_dtype not in (None, "float32", "bfloat16"):
            # fail here with the legal values, not as a KeyError deep in
            # the zoo's dtype table
            raise ValueError(
                f"compute_dtype must be None, 'float32' or 'bfloat16', "
                f"got {config.compute_dtype!r}"
            )
        if config.data_fault_policy not in ("skip", "raise"):
            raise ValueError(
                f"data_fault_policy must be 'skip' or 'raise', "
                f"got {config.data_fault_policy!r}"
            )
        if config.eval_fault_policy not in ("skip", "raise"):
            raise ValueError(
                f"eval_fault_policy must be 'skip' or 'raise', "
                f"got {config.eval_fault_policy!r}"
            )
        if config.numerics_policy not in ("raise", "skip"):
            raise ValueError(
                f"numerics_policy must be 'raise' or 'skip', "
                f"got {config.numerics_policy!r}"
            )
        if config.window_size < 1:
            raise ValueError(
                f"window_size must be >= 1, got {config.window_size}"
            )
        if config.window_size > 1:
            # Boundaries (log, checkpoint, eval, preemption) happen only at
            # whole-window steps: a misaligned interval would silently
            # shift every boundary, so fail loudly at construction.
            k = config.window_size
            for name, every in (
                ("log_every", config.log_every),
                ("checkpoint_every",
                 config.checkpoint_every if config.checkpoint_dir else 0),
                ("eval_every", config.eval_every),
                ("num_steps", config.num_steps),
            ):
                if every and every % k:
                    raise ValueError(
                        f"{name}={every} is not a multiple of "
                        f"window_size={k}; boundaries are window-aligned "
                        f"(docs/perf_notes.md, training-throughput section)"
                    )
        self.config = config
        if config.profile_port and jax.process_index() == 0:
            # exposes the live TPU profile to TensorBoard / Perfetto capture
            # (`jax.profiler.trace` via tensorboard-plugin-profile or
            # `jax.profiler.collect_profile`), SURVEY.md §5.1
            jax.profiler.start_server(config.profile_port)
        self.model = build_raft(self.model_config(config))
        self.lr_schedule = one_cycle_lr(config.learning_rate, config.num_steps)
        self.tx = make_optimizer(
            self.lr_schedule,
            weight_decay=config.weight_decay,
            clip_norm=config.clip_norm,
        )

        # Observability spine (ISSUE 10): the trainer registers into the
        # same three pillars as the serving tier — per-window traces
        # (data wait / dispatch / metric fetch / checkpoint / eval
        # spans), a metrics registry of phase histograms, and a flight
        # recorder that the stability ladder and the stall watchdog dump
        # through when they fire.
        from raft_tpu.obs import FlightRecorder, MetricsRegistry, Tracer

        self.metrics = MetricsRegistry("train")
        self.recorder = FlightRecorder(proc="trainer")
        self.tracer = Tracer(
            1.0, capacity=64, prefix="trn",
            on_finish=self.recorder.add_trace,
        )
        self._phase_hist = {
            name: self.metrics.histogram(f"{name}_ms")
            for name in (
                "data_wait", "dispatch", "metric_fetch", "checkpoint",
                "eval",
            )
        }
        self._obs_counters = self.metrics.counter_group(
            "counters", ("windows", "boundaries", "checkpoints", "evals")
        )

        # Divergence-escalation bookkeeping (train/stability.py): the
        # monitor exists only under numerics_policy='skip'; its policy
        # constructor validates the knobs either way so a bad flag fails
        # at Trainer construction, not at the first breach.
        from raft_tpu.train.stability import StabilityMonitor, StabilityPolicy

        stability_policy = StabilityPolicy(
            skip_budget=config.skip_budget,
            max_rollbacks=config.max_rollbacks,
            rollback_lr_scale=config.rollback_lr_scale,
        )
        self.stability = (
            StabilityMonitor(
                stability_policy, base_seed=config.seed,
                recorder=self.recorder,
            )
            if config.numerics_policy == "skip"
            else None
        )
        self._lr_scale = 1.0
        self._eval_ok = True
        self._pending_good: list = []

        variables = init_from or init_variables(self.model)
        self.state = TrainState.create(variables, self.tx)

        self.mesh = None
        if config.data_mesh and len(jax.devices()) > 1:
            from raft_tpu.parallel import make_mesh, shard_state

            n_dev = len(jax.devices())
            if config.global_batch_size % n_dev != 0:
                raise ValueError(
                    f"global_batch_size={config.global_batch_size} is not "
                    f"divisible by the {n_dev} visible devices on the data "
                    f"axis; set global_batch_size to a multiple of {n_dev} "
                    f"(e.g. {-(-config.global_batch_size // n_dev) * n_dev}) "
                    "or pass data_mesh=False for single-device training"
                )
            self.mesh = make_mesh(space=1)
            self.state = shard_state(self.state, self.mesh)
        self.step_fn = self._make_step_fn()
        self.window_fn = self._make_window_fn()

        self.manager = None
        if config.checkpoint_dir:
            from raft_tpu.checkpoint import CheckpointManager

            self.manager = CheckpointManager(
                os.path.abspath(config.checkpoint_dir),
                max_to_keep=3,
                save_interval_steps=config.checkpoint_every,
            )
            restored = self.manager.restore(self.state)
            self._resumed = restored is not None
            if restored is not None:
                self.state = restored
                if jax.process_index() == 0:
                    print(f"resumed from step {int(self.state.step)}")
        else:
            self._resumed = False

        self.watchdog = None  # built per-run when watchdog_timeout is set
        self.eval_fn = eval_fn
        # always present: a Trainer with a custom eval_fn (or no eval at
        # all) must not raise AttributeError on later eval_model access;
        # the default-eval branch below overrides it with the fp32 twin
        self.eval_model = self.model
        if self.eval_fn is None and eval_dataset is not None:
            from functools import partial

            from raft_tpu.eval.validate import validate

            # In-loop eval must match the fp32 published protocol even
            # when TRAINING runs reduced precision (bf16 convs and/or
            # bf16 correlation storage): eval through an all-fp32 twin of
            # the model. The variable tree is identical (those knobs cast
            # activations/storage, never params), so the trained
            # variables apply directly — and the eval/* scalars plus the
            # best-EPE export stay comparable with what
            # scripts/validate.py reports on the same weights. The twin
            # keeps the trained corr_impl: fused-at-fp32 is
            # output-identical to the dense reference path
            # (oracle-tested), only faster.
            eval_model = self.model
            if (config.compute_dtype not in (None, "float32")
                    or config.corr_dtype not in (None, "float32")):
                eval_model = build_raft(
                    self.model_config(config).replace(
                        compute_dtype="float32", corr_dtype="float32"
                    )
                )
            self.eval_model = eval_model

            # One jit with variables as a TRACED argument, cached across
            # evals — validate()'s own default bakes the weights in as
            # constants and would recompile the full model every boundary.
            jitted_apply = jax.jit(
                partial(
                    eval_model.apply,
                    train=False,
                    num_flow_updates=config.eval_num_flow_updates,
                    emit_all=False,
                )
            )
            # KITTI/HD1K-style sparse GT needs the masked-EPE, bottom-pad
            # protocol; Sintel's dense GT the all-pixel, split-pad one.
            # Keyed on the dataset TYPE, not density: a dense non-Sintel
            # eval set (Chairs/Things) gets the same 'downstream' pad
            # protocol scripts/validate.py gives it.
            eval_mode = config.eval_mode
            if eval_mode is None:
                from raft_tpu.data.datasets import Sintel

                def _all_sintel(ds) -> bool:
                    # see through the mix wrappers: a Concat/Repeat of
                    # pure Sintel keeps the Sintel protocol
                    if isinstance(ds, Sintel):
                        return True
                    if hasattr(ds, "parts"):  # ConcatDataset
                        return bool(ds.parts) and all(
                            _all_sintel(p) for p in ds.parts
                        )
                    if hasattr(ds, "base"):  # RepeatDataset
                        return _all_sintel(ds.base)
                    return False

                eval_mode = (
                    "sintel" if _all_sintel(eval_dataset) else "downstream"
                )
            elif eval_mode not in ("sintel", "downstream"):
                raise ValueError(
                    f"eval_mode must be None, 'sintel' or 'downstream', "
                    f"got {config.eval_mode!r}"
                )

            def default_eval(variables):
                # protocol-exact EPE on the held-out split; no fps chain
                # (in-loop eval wants the metric, not a throughput bench).
                # One device_put up front: the per-pair lambda must not
                # re-transfer the host weight tree on every sample.
                dev_vars = jax.device_put(variables)
                return validate(
                    eval_model,
                    variables,
                    eval_dataset,
                    num_flow_updates=config.eval_num_flow_updates,
                    mode=eval_mode,
                    fps_pairs=0,
                    apply_fn=lambda im1, im2: jitted_apply(dev_vars, im1, im2),
                )

            self.eval_fn = default_eval
        if config.eval_every and self.eval_fn is None:
            raise ValueError(
                "eval_every is set but neither eval_dataset nor eval_fn "
                "was passed to Trainer"
            )
        self.best_epe = float("inf")
        if config.checkpoint_dir and self._resumed:
            # resuming must not let a worse eval overwrite the best export.
            # Gated on an ACTUAL resume: a stale best.json in a reused dir
            # (fresh run, checkpoints deleted) must not suppress the fresh
            # run's best export.
            best_json = os.path.join(
                os.path.abspath(config.checkpoint_dir), "best.json"
            )
            if os.path.exists(best_json):
                import json

                try:
                    with open(best_json) as f:
                        self.best_epe = float(json.load(f)["epe"])
                except (ValueError, KeyError, TypeError, OSError):
                    pass

        stage = STAGES.get(config.stage, {})
        self._augmentor = FlowAugmentor(
            AugmentConfig(
                crop_size=config.crop_size,
                sparse=stage.get("sparse", False),
                min_scale=stage.get("min_scale", -0.2),
                max_scale=stage.get("max_scale", 0.5),
            )
        )
        self._dataset = dataset
        self.pipeline = self._build_pipeline(
            seed=config.seed, start_step=int(self.state.step)
        )

    def _step_kw(self):
        config = self.config
        return dict(
            num_flow_updates=config.num_flow_updates,
            gamma=config.gamma,
            max_flow=config.max_flow,
            check_numerics=config.check_numerics,
            numerics_policy=config.numerics_policy,
            spike_factor=config.spike_factor,
            spike_warmup=config.spike_warmup,
        )

    def _make_step_fn(self):
        """(Re-)jit the train step for the current optimizer ``self.tx``.

        Called at construction and again after a rollback that scaled the
        LR (the schedule is baked into the compiled step, so an LR change
        means a re-jit — acceptable for an event that happens at most
        ``max_rollbacks`` times per run)."""
        if self.mesh is not None:
            from raft_tpu.parallel import make_sharded_train_step

            return make_sharded_train_step(
                self.model, self.tx, self.mesh, **self._step_kw()
            )
        from raft_tpu.train.step import make_train_step

        return make_train_step(self.model, self.tx, **self._step_kw())

    def _make_window_fn(self):
        """Jit the fused ``window_size``-step dispatch (None when k=1).

        jit is lazy, so at ``window_size=1`` nothing window-shaped ever
        compiles and the per-step path is byte-for-byte today's behavior.
        Re-built alongside ``step_fn`` after a rollback re-jit."""
        if self.config.window_size <= 1:
            return None
        if self.mesh is not None:
            from raft_tpu.parallel import make_sharded_window_step

            return make_sharded_window_step(
                self.model, self.tx, self.mesh,
                window_size=self.config.window_size, **self._step_kw()
            )
        from raft_tpu.train.step import make_window_step

        return make_window_step(
            self.model, self.tx,
            window_size=self.config.window_size, **self._step_kw()
        )

    def _build_pipeline(self, *, seed: int, start_step: int) -> TrainPipeline:
        """Pipeline state is just ``(seed, step)``: rollback recovery
        re-instantiates it with a perturbed seed at the restored step."""
        config = self.config
        from raft_tpu.utils.faults import DataFaultPolicy

        return TrainPipeline(
            self._dataset,
            config.global_batch_size,
            augmentor=self._augmentor,
            seed=seed,
            mesh=self.mesh,
            start_step=start_step,
            fault_policy=DataFaultPolicy(
                mode=config.data_fault_policy,
                max_bad_samples=config.data_bad_sample_budget,
                max_retries=config.data_max_retries,
            ),
            window_size=config.window_size,
        )

    def _host_window(self, window) -> list:
        """Fetch a metric window to host: ONE transfer, columnar convert.

        ``window`` is a list of ``(n_steps, metrics)`` pairs — per-step
        dicts from the per-step path (``n=1``) or stacked ``(k, ...)``
        trees from the fused window dispatch. The whole list goes through
        a single ``jax.device_get`` (the old code fetched once per step),
        and scalar conversion is one ``np.asarray`` per metric key over
        the flattened window (the old code called ``float(...)`` per
        element). ``"_"``-prefixed metrics are diagnostic vectors (e.g.
        per-leaf nonfinite counts), not scalars: they stay arrays.
        Returns one host dict per STEP, in step order.
        """
        if not window:
            return []
        host = jax.device_get([m for _, m in window])
        steps: list = []
        for (n, _), m in zip(window, host):
            if n == 1:
                steps.append(m)
            else:
                steps.extend(
                    {key: v[i] for key, v in m.items()} for i in range(n)
                )
        keys = list(steps[0])
        cols = {
            key: (
                [np.asarray(s[key]) for s in steps]
                if key.startswith("_")
                else np.asarray([s[key] for s in steps], np.float64)
            )
            for key in keys
        }
        return [
            {
                key: (cols[key][i] if key.startswith("_") else float(cols[key][i]))
                for key in keys
            }
            for i in range(len(steps))
        ]

    def _check_window(self, step: int, window) -> None:
        """Raise NumericsError if any step in the window saw nonfinite
        grads or a nonfinite loss (``check_numerics`` watchdog).

        The message names the exact failing step AND the first offending
        gradient leaves (from the per-leaf count vector the guarded step
        carries in its metrics; the path walk over the param tree happens
        host-side, on failure only) so a raise-mode death is diagnosable
        from the log alone."""
        import math

        from raft_tpu.utils.debug import (
            NumericsError, format_report, leaf_paths, nonfinite_report,
        )

        for i, m in enumerate(window):
            bad_grads = m.get("nonfinite_grads", 0.0) > 0
            bad_loss = not math.isfinite(m.get("loss", 0.0))
            if bad_grads or bad_loss:
                first_bad = step - len(window) + i + 1
                # grads mirror the param tree, so its key paths name them
                counts = m.get("_nonfinite_leaves")
                grad_leaves = "(no per-leaf data)"
                if counts is not None:
                    names = leaf_paths(self.state.params)
                    offenders = [
                        f"{n}: {int(c)} nonfinite"
                        for n, c in zip(names, np.asarray(counts).tolist())
                        if c
                    ]
                    grad_leaves = (
                        "; ".join(offenders[:5])
                        + (f"; ... {len(offenders) - 5} more leaves"
                           if len(offenders) > 5 else "")
                    ) or "(all gradient leaves finite)"
                report = nonfinite_report(self.state.params)
                raise NumericsError(
                    f"nonfinite numerics at step {first_bad} "
                    f"(loss={m.get('loss')}, "
                    f"nonfinite_grads={m.get('nonfinite_grads')}); "
                    f"offending gradient leaves: {grad_leaves}; "
                    f"param tree after the poisoned update:\n"
                    f"{format_report(report)}\n"
                    "To localize the producing op, re-run the failing "
                    "(state, batch) through "
                    "raft_tpu.utils.debug.localize_nans(step_body, ...). "
                    "To skip bad steps instead of dying, set "
                    "numerics_policy='skip'.",
                    report,
                )

    def _rollback(self, at_step: int, window_skips: int, guard,
                  log_fn, logger) -> None:
        """Persistent-divergence recovery (train/stability.py ladder).

        Restores the last known-good checkpoint, perturbs the data-order
        seed (pipeline state is ``(seed, step)`` — the restored step range
        replays with DIFFERENT batches), and scales the LR down when
        ``rollback_lr_scale < 1`` (re-jits the step: the schedule is baked
        into the compiled program). Raises :class:`DivergenceError` when
        the rollback budget is spent or there is nothing to restore.

        Armed as a watchdog ``rollback`` section: a hung restore (wedged
        storage mid-recovery) dumps stacks and raises ``StallError``
        instead of wedging the recovery path itself.
        """
        mon = self.stability
        mon.check_escalation(at_step, window_skips)
        if self.manager is None:
            mon.fail(at_step, window_skips,
                     "no checkpoint_dir configured: nothing to roll back to")
        new_seed = mon.next_seed()
        lr_scale = mon.next_lr_scale()
        with guard("rollback", scale=5.0):
            self.manager.wait()  # queued async saves must land first
            restored = self.manager.restore_known_good(
                self.state, before=at_step
            )
            if restored is None:
                mon.fail(at_step, window_skips,
                         "no retained checkpoint to roll back to")
            self.state = restored
            # the trajectory past the restore point is abandoned: drop its
            # checkpoints so the replayed steps' saves never collide with
            # retained diverged ones
            to_step = int(jax.device_get(restored.step))
            for s in sorted(self.manager.all_steps(), reverse=True):
                if s > to_step:
                    self.manager.delete(s)
            if self.config.rollback_lr_scale != 1.0:
                self._lr_scale = lr_scale
                base = self.lr_schedule
                scaled = lambda count, s=lr_scale: base(count) * s
                self.tx = make_optimizer(
                    scaled,
                    weight_decay=self.config.weight_decay,
                    clip_norm=self.config.clip_norm,
                )
                self.step_fn = self._make_step_fn()
                self.window_fn = self._make_window_fn()
            self.pipeline = self._build_pipeline(
                seed=new_seed, start_step=int(self.state.step)
            )
        attempt = mon.record_rollback(
            at_step, int(self.state.step), window_skips,
            seed=new_seed, lr_scale=lr_scale,
        )
        self._pending_good = []
        self._eval_ok = True
        if jax.process_index() == 0:
            print(f"stability: rollback {len(mon.rollbacks)}"
                  f"/{mon.policy.max_rollbacks} — {attempt.describe()}")
            scalars = {"stability/rollback_to": float(attempt.to_step)}
            log_fn(at_step, scalars)
            if logger is not None:
                logger.log(at_step, scalars)

    def _run_eval(self, step: int, log_fn, logger) -> None:
        """In-loop validation (SURVEY.md §5.5 + the acceptance protocol).

        The weights are ``device_get`` of the (replicated) training state,
        so the eval computation itself contains NO cross-host collectives:
        every process fetches (params are addressable everywhere — cheap),
        but only process 0 computes, logs ``eval/*`` scalars, and exports
        the best-EPE weights. Peers proceed straight into the next step;
        process 0 joins its collectives after eval — skew, not deadlock.
        """
        host_vars = jax.device_get(self.state.variables())
        if jax.process_index() != 0:
            return
        try:
            self._eval_and_export(step, host_vars, log_fn, logger)
        except Exception as e:
            # An in-loop eval failure (OOM, one bad val sample, a full disk
            # during the best-export) must not kill hours of training: log
            # it as a scalar and keep going (eval_fault_policy='skip').
            if self.config.eval_fault_policy == "raise":
                raise
            print(
                f"eval at step {step} failed "
                f"({type(e).__name__}: {e}); continuing (eval_fault_policy='skip')"
            )
            failed = {"eval/failed": 1.0}
            log_fn(step, failed)
            if logger is not None:
                logger.log(step, failed)

    def _eval_and_export(self, step: int, host_vars, log_fn, logger) -> None:
        metrics = self.eval_fn(host_vars)
        scalars = {
            f"eval/{k}": float(v)
            for k, v in metrics.items()
            if np.isfinite(float(v))
        }
        log_fn(step, scalars)
        if logger is not None:
            logger.log(step, scalars)
        epe = metrics.get("epe")
        if epe is None or not np.isfinite(float(epe)):
            self._eval_ok = epe is None  # nonfinite EPE = regressed
            return
        # Known-good gate (train/stability.py): a checkpoint is only a
        # rollback target while the latest eval EPE stays within
        # good_epe_slack of the best seen — a silently-degrading model
        # should not be what rollback restores.
        self._eval_ok = (
            self.best_epe == float("inf")
            or float(epe) <= self.best_epe * (1.0 + self.config.good_epe_slack)
        )
        if float(epe) < self.best_epe:
            self.best_epe = float(epe)
            if self.config.checkpoint_dir:
                import json

                from raft_tpu.checkpoint import save_variables

                d = os.path.abspath(self.config.checkpoint_dir)
                os.makedirs(d, exist_ok=True)
                # atomic replace, weights before metadata: a kill mid-write
                # can never leave a truncated best.msgpack that an intact
                # best.json then permanently shields from re-export
                tmp = os.path.join(d, ".best.msgpack.tmp")
                save_variables(host_vars, tmp)
                os.replace(tmp, os.path.join(d, "best.msgpack"))
                tmp_j = os.path.join(d, ".best.json.tmp")
                with open(tmp_j, "w") as f:
                    json.dump({"step": step, "epe": self.best_epe}, f)
                os.replace(tmp_j, os.path.join(d, "best.json"))

    def _install_preemption_handler(self):
        """SIGTERM/SIGINT -> finish the in-flight step, checkpoint, exit
        cleanly (SURVEY.md §5.3: the TPU-pod failure model is
        restart-the-slice, so preemption safety = always having a fresh
        checkpoint to resume from; Orbax manager.restore picks it up on
        the next launch). Only active when checkpointing is configured.
        Returns a restore() callable for run()'s finally block — the
        handlers must not outlive the loop (they would permanently swallow
        Ctrl+C for the rest of the process)."""
        import signal

        self._preempted = False
        saved = {}

        def _handler(signum, _frame):
            # flag only — the loop breaks at the next safe boundary, so
            # the checkpoint is of a consistent post-step state
            self._preempted = True

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                saved[sig] = signal.signal(sig, _handler)
            except ValueError:
                # non-main thread (tests, notebook executors): polling
                # self._preempted still works for direct injection
                pass

        def restore():
            for sig, old in saved.items():
                signal.signal(sig, old)

        return restore

    def _preemption_agreed(self, at_boundary: bool) -> bool:
        """Whether to take the preemption exit at this step.

        Single-host: act immediately on the local flag. Multi-host: the
        checkpoint save and the train step both contain cross-host
        collectives, so every process must take the exit at the SAME step
        — hosts agree via an allgather of their local flags, executed only
        at log boundaries (deterministic points every host reaches), never
        on a host-local condition."""
        if jax.process_count() == 1:
            return self._preempted
        if not at_boundary:
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([self._preempted], dtype=np.int32)
        )
        return bool(np.asarray(flags).max())

    def run(self, log_fn=None) -> TrainState:
        cfg = self.config
        log_fn = log_fn or (lambda step, m: print(
            f"step {step}: " + " ".join(f"{k}={v:.4f}" for k, v in m.items())
        ))
        logger = None
        if cfg.log_dir and jax.process_index() == 0:
            from raft_tpu.obs import logger_sink
            from raft_tpu.utils.logging import MetricLogger

            logger = MetricLogger(cfg.log_dir)
            # postmortem bundles (watchdog trip, divergence death)
            # persist through the logger's structured events file
            self.recorder.add_sink(logger_sink(logger))
        start = int(self.state.step)
        # Fused multi-step dispatch: with window_size=k > 1 every loop
        # iteration advances k steps through ONE device dispatch
        # (window_fn lax.scans the per-step body over the pipeline's
        # stacked batch window) and metrics stay on device as one (k, ...)
        # stacked tree until the log boundary's single fetch. Boundaries
        # are window-aligned (validated at construction), so the loop
        # below is the per-step loop with a stride — including rollback,
        # which restores a (window-aligned) checkpoint step and re-enters
        # at a window start. Checked before any handlers install so a
        # misaligned resume cannot leak signal-handler state.
        wsize = cfg.window_size if self.window_fn is not None else 1
        if wsize > 1 and start % wsize:
            raise ValueError(
                f"resumed at step {start}, which is not a multiple of "
                f"window_size={wsize} (a checkpoint from a differently "
                f"windowed run?); resume with window_size=1 or a divisor "
                f"of {start} to realign"
            )
        t0 = time.perf_counter()
        window: list = []
        data_iter = iter(self.pipeline)
        restore_handlers = lambda: None
        if self.manager is not None:
            restore_handlers = self._install_preemption_handler()
        # Stall watchdog (docs/failure_model.md): armed around every
        # blocking host-side region below. Guarding is two attribute
        # writes per region — no device syncs on the hot path.
        from contextlib import nullcontext

        self.watchdog = None
        if cfg.watchdog_timeout:
            from raft_tpu.utils.faults import Watchdog

            dump = (
                os.path.join(cfg.log_dir, "stall_stacks.log")
                if cfg.log_dir
                else None
            )
            self.watchdog = Watchdog(
                cfg.watchdog_timeout, dump_path=dump,
                recorder=self.recorder,
            )

        def guard(name, scale=1.0):
            if self.watchdog is None:
                return nullcontext()
            return self.watchdog.section(name, scale=scale)

        try:
            step = start
            stretch_next = True  # first step jit-compiles; also post-rollback
            while step < cfg.num_steps:
                at_boundary = step == start or step % cfg.log_every == 0
                if self.manager is not None and self._preemption_agreed(at_boundary):
                    with guard("checkpoint/preempt"):
                        jax.block_until_ready(self.state.params)
                        if self.manager.latest_step() != step:
                            # force=True does NOT overwrite in Orbax: skip when
                            # this exact step is already on disk (resume + an
                            # immediate second preemption)
                            self.manager.save(step, self.state, force=True)
                        self.manager.wait()
                    if jax.process_index() == 0:
                        print(f"preempted: checkpointed step {step}, exiting")
                    return self.state
                # the first step jit-compiles and the first fetch warms the
                # prefetch pipeline: legitimately slow ONCE, so the deadline
                # is stretched there instead of loosening the steady state
                # (same after a rollback: new pipeline, maybe a re-jit).
                # Steady-state deadlines scale with the window: one guarded
                # dispatch now covers wsize steps of device work.
                first = stretch_next
                stretch_next = False
                scale = (20.0 if first else 1.0) * wsize
                # one observability trace per dispatch window: the same
                # span machinery the serve path uses, wrapping the
                # trainer's blocking host-side phases (ISSUE 10)
                wtrace = self.tracer.start("train_window", rid=step)
                t_a = time.monotonic()
                with guard("data/next", scale=scale):
                    batch = next(data_iter)
                t_b = time.monotonic()
                with guard("train/step", scale=scale):
                    from raft_tpu.obs import profile

                    with profile.annotate("train/window_dispatch"):
                        fn = (
                            self.window_fn
                            if self.window_fn is not None
                            else self.step_fn
                        )
                        self.state, metrics = fn(self.state, batch)
                t_c = time.monotonic()
                if wtrace is not None:
                    wtrace.add_span("data_wait", t_a, t_b)
                    wtrace.add_span("dispatch", t_b, t_c, steps=wsize)
                self._phase_hist["data_wait"].observe((t_b - t_a) * 1e3)
                self._phase_hist["dispatch"].observe((t_c - t_b) * 1e3)
                self._obs_counters["windows"] += 1
                window.append((wsize, metrics))
                at_log = (step + wsize) % cfg.log_every == 0
                at_ckpt = (
                    self.manager is not None
                    and (step + wsize) % cfg.checkpoint_every == 0
                )
                hwin = None
                if at_log or (at_ckpt and cfg.check_numerics):
                    t_mf = time.monotonic()
                    with guard("train/device_sync"):
                        hwin = self._host_window(window)
                        # keep the (count, metrics) shape invariant: a
                        # check_numerics-only sync between log boundaries
                        # must leave the list appendable and re-fetchable
                        window = [(1, m) for m in hwin]
                    if wtrace is not None:
                        wtrace.add_span("metric_fetch", t_mf)
                    self._phase_hist["metric_fetch"].observe(
                        (time.monotonic() - t_mf) * 1e3
                    )
                    self._obs_counters["boundaries"] += 1
                    if cfg.check_numerics and cfg.numerics_policy == "raise":
                        # never persist a NaN-poisoned state as "latest":
                        # check before the save below (one device sync per
                        # boundary, off the hot path). Under 'skip' the
                        # guard already rejected the bad updates — nothing
                        # poisoned exists to protect the checkpoint from.
                        self._check_window(step + wsize, hwin)
                if self.manager is not None:
                    t_ck = time.monotonic()
                    with guard("checkpoint/save"):
                        if self.manager.save(step + wsize, self.state):
                            # tagged known-good once the covering window
                            # closes finite (below)
                            self._pending_good.append(step + wsize)
                            self._obs_counters["checkpoints"] += 1
                    if wtrace is not None:
                        wtrace.add_span("checkpoint", t_ck)
                    self._phase_hist["checkpoint"].observe(
                        (time.monotonic() - t_ck) * 1e3
                    )
                if at_log:
                    # skipped steps carry the bad batch's NaN loss/grads in
                    # their METRICS (the state never saw them): keep them
                    # out of the window means so one skipped step doesn't
                    # turn every boundary scalar into NaN
                    applied = [
                        m for m in hwin if not m.get("skipped", 0.0)
                    ] or hwin
                    mean = {
                        k: float(np.mean([m[k] for m in applied]))
                        for k in hwin[0]
                        if not k.startswith("_")
                    }
                    dt = time.perf_counter() - t0
                    mean["pairs_per_s"] = (
                        len(hwin) * cfg.global_batch_size / max(dt, 1e-9)
                    )
                    mean["lr"] = float(self.lr_schedule(step)) * self._lr_scale
                    # host-side fault counters (data/skipped, data/retries):
                    # free to read, and the only way a quarantined sample
                    # becomes visible without grepping worker logs
                    if self.pipeline.fault_policy is not None:
                        mean.update(
                            {k: float(v) for k, v in self.pipeline.counters.items()}
                        )
                    # divergence-guard accounting: skipped-update COUNT for
                    # this window (the mean is per-step; the budget is per
                    # window) plus the escalation state
                    window_skips = int(
                        round(sum(m.get("skipped", 0.0) for m in hwin))
                    )
                    breached = False
                    if self.stability is not None:
                        mean["train/skipped"] = float(window_skips)
                        mean["stability/rollbacks"] = float(
                            len(self.stability.rollbacks)
                        )
                        breached = self.stability.breached(window_skips)
                    import math

                    # finiteness gate over APPLIED steps only: a skipped
                    # step's NaN loss never touched the state, so it must
                    # not block tagging the (protected) checkpoint
                    window_finite = all(
                        math.isfinite(m.get("loss", 0.0)) for m in applied
                    )
                    if self._pending_good:
                        # known-good tagging: the window around the save
                        # closed with finite losses, no budget breach, and
                        # no regressed eval -> a legitimate rollback target
                        if (
                            window_finite
                            and not breached
                            and self._eval_ok
                            and jax.process_index() == 0
                        ):
                            for s in self._pending_good:
                                self.manager.tag_good(
                                    s, {"loss": mean.get("loss")}
                                )
                        self._pending_good = []
                    if jax.process_index() == 0:
                        log_fn(step + wsize, mean)
                        if logger is not None:
                            logger.log(step + wsize, mean)
                    window = []
                    t0 = time.perf_counter()
                    if breached:
                        # budgeted-skip rung exhausted: roll back to the
                        # last known-good checkpoint with a perturbed data
                        # order (may raise DivergenceError instead)
                        self._rollback(step + wsize, window_skips, guard,
                                       log_fn, logger)
                        if wtrace is not None:
                            wtrace.finish(
                                ok=True, step=step + wsize, rollback=True
                            )
                        if hasattr(data_iter, "close"):
                            data_iter.close()
                        data_iter = iter(self.pipeline)
                        step = int(self.state.step)
                        stretch_next = True
                        t0 = time.perf_counter()
                        continue
                if cfg.eval_every and (step + wsize) % cfg.eval_every == 0:
                    t_eval = time.perf_counter()
                    t_ev = time.monotonic()
                    # eval walks the whole held-out split (+ first-call jit)
                    with guard("eval", scale=20.0):
                        self._run_eval(step + wsize, log_fn, logger)
                    if wtrace is not None:
                        wtrace.add_span("eval", t_ev)
                    self._phase_hist["eval"].observe(
                        (time.monotonic() - t_ev) * 1e3
                    )
                    self._obs_counters["evals"] += 1
                    # eval is not training time: keep it out of the next
                    # window's pairs_per_s
                    t0 += time.perf_counter() - t_eval
                if wtrace is not None:
                    wtrace.finish(ok=True, step=step + wsize)
                step += wsize
        finally:
            restore_handlers()
            if self.watchdog is not None:
                # closed but kept: stall_count/last_stall stay inspectable
                self.watchdog.close()
            if logger is not None:
                logger.close()
        if self.manager is not None:
            if cfg.check_numerics and cfg.numerics_policy == "raise" and window:
                # the tail window (loop ended between boundaries) must be
                # checked before the final force save persists the state
                self._check_window(cfg.num_steps, self._host_window(window))
            if self.manager.latest_step() != cfg.num_steps:
                self.manager.save(cfg.num_steps, self.state, force=True)
            self.manager.wait()
        return self.state
