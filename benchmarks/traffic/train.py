"""Train driver: ``Trainer.run`` with ``data/pipeline.py`` feeding it from an
in-memory synthetic dataset (augmentation running).

Set-up builds ONE ``Trainer`` (the compiled step and its state), drives it
from the seed through its first steps — through ``Trainer.run`` and the
pipeline, exactly as the window does — keeps what the comparison needs
(each step's loss, the optimizer's first moment after step 1, the
parameters after step 3, and the batches as the step was given them),
runs a few more steps to find the rate, and hands the same object to the
window: one more ``Trainer.run`` call of as many steps as fill
``--seconds`` at that rate. The rate reported is all the window's pairs
over all its time.

Cell file keys: ``image_hw``, ``crop``, ``iters``, ``batch``,
``dataset_size``, ``compare_steps``, ``warm_steps``, ``train``
(TrainConfig overrides incl. ``learning_rate``, ``weight_decay``,
``clip_norm``, ``log_every``), ``schedule_steps``, ``limits``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import inputs, weights
from benchmarks.reference import compare as cmp, raft as ref
from benchmarks.traffic.serve_closed import check_arch

ADAM_B1 = 0.9  # optax.adamw's default, which train/optim.py keeps


class RecordingPipeline:
    """Passes the trainer's pipeline through and keeps the first ``keep``
    batches as the step received them (host arrays)."""

    def __init__(self, inner, keep: int):
        self._inner, self._keep = inner, keep
        self.batches = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        for batch in self._inner:
            if len(self.batches) < self._keep:
                self.batches.append({k: np.array(v) for k, v in batch.items()})
            yield batch


def first_moment(opt_state):
    """Adam's ``mu`` tree, wherever the optimizer chain keeps it."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def make_trainer(ctx, variables, dataset, **overrides):
    from raft_tpu.models import zoo
    from raft_tpu.train.trainer import TrainConfig, Trainer

    cell, config = ctx.cell, ctx.config
    check_arch(config, zoo.CONFIGS[config["program_arch"]])
    prec = config["precision"]["train"]
    kw = dict(
        arch=config["program_arch"], crop_size=tuple(cell["crop"]),
        global_batch_size=cell["batch"], num_flow_updates=cell["iters"],
        num_steps=cell["schedule_steps"], seed=ctx.seed, remat=True,
        remat_policy=prec["remat_policy"], corr_impl=prec["corr_impl"],
        corr_dtype=prec["corr_dtype"], data_mesh=False,
    )
    if prec["compute_dtype"] != "float32":
        kw["compute_dtype"] = prec["compute_dtype"]
    kw.update(cell["train"])
    kw.update(overrides)
    return Trainer(TrainConfig(**kw), dataset, init_from=variables)


def run_to(trainer, step: int, log_every: int, losses=None, memory=None):
    """``Trainer.run`` up to absolute step ``step``. ``memory`` is sampled
    whenever the trainer logs (every ``log_every`` steps)."""
    trainer.config = trainer.config.replace(num_steps=step, log_every=log_every)

    def log_fn(_step, metrics):
        if losses is not None:
            losses.append(metrics["loss"])
        if memory is not None:
            memory.sample()

    trainer.run(log_fn=log_fn)


def setup(ctx, **overrides):
    import jax

    cell, config = ctx.cell, ctx.config
    if ctx.trace:
        from raft_tpu.obs import profile

        profile.enable()
    variables = weights.make_variables(
        ref.param_shapes(config["arch"]), ctx.seed,
        config["assumed"]["flow_head_scale"])
    host_vars = jax.device_get(variables)
    dataset = inputs.SyntheticFlowDataset(
        ctx.seed, cell["dataset_size"], cell["image_hw"])
    trainer = make_trainer(ctx, variables, dataset, **overrides)
    n = cell["compare_steps"]
    trainer.pipeline = RecordingPipeline(trainer.pipeline, n)

    losses = []
    run_to(trainer, 1, 1, losses)
    mu = jax.device_get(first_moment(trainer.state.opt_state))
    grad_norms = {k: v / (1.0 - ADAM_B1) for k, v in cmp.leaf_norms(mu).items()}
    run_to(trainer, n, 1, losses)
    params = jax.device_get(trainer.state.params)
    change = cmp.leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - b, params, host_vars["params"]))
    program = {"losses": [float(x) for x in losses], "grad_norms": grad_norms,
               "change_norms": change}
    batches = trainer.pipeline.batches
    for b in batches:
        if b["image1"].shape[0] > 1 and np.array_equal(b["image1"][0], b["image1"][1]):
            raise RuntimeError("the pipeline fed identical rows")

    log_every = cell["train"].get("log_every", 100)
    run_to(trainer, n + 2, log_every)            # restart stall, untimed
    t0 = time.monotonic()
    run_to(trainer, n + 2 + cell["warm_steps"], log_every)
    jax.block_until_ready(trainer.state.params)
    steps_per_s = cell["warm_steps"] / (time.monotonic() - t0)
    ctx.log(phase="train_setup", losses=program["losses"], steps_per_s=steps_per_s)
    return {"trainer": trainer, "host_vars": host_vars, "program": program,
            "batches": batches, "steps_per_s": steps_per_s}


def window(ctx, state, seconds):
    import jax

    trainer, cell = state["trainer"], ctx.cell
    start = int(trainer.state.step)
    steps = max(1, int(round(state["steps_per_s"] * seconds)))
    with ctx.window_region():
        t0 = time.monotonic()
        run_to(trainer, start + steps, cell["train"].get("log_every", 100),
               memory=ctx.memory)
        jax.block_until_ready(trainer.state.params)
        t1 = time.monotonic()
    done = int(trainer.state.step) - start
    elapsed = t1 - t0
    rate = done * cell["batch"] / elapsed
    spans = [s for s in trainer.tracer.snapshot() if s.get("t_start", 0) >= t0]
    return {
        "metrics": {"train_pairs_per_s": rate},
        "attempted": steps, "failed": steps - done,
        "window_s": elapsed, "t0": t0, "t1": t1, "counters": {"steps": done},
        "spans": spans, "rates": {"train_pairs_per_s": rate},
        "notes": {"steps": done, "elapsed_s": elapsed,
                  "steps_per_s_at_setup": state["steps_per_s"]},
    }


def release(ctx, state):
    import gc

    del state["trainer"]
    gc.collect()


def reference_kwargs(cell, precision):
    """How the plain reference follows this cell's steps."""
    t = cell["train"]
    return dict(
        iters=cell["iters"],
        schedule={"max_lr": t["learning_rate"], "total_steps": cell["schedule_steps"]},
        optimizer={"weight_decay": t["weight_decay"], "clip": t["clip_norm"]},
        precision=precision,
    )


def compare(ctx, state, window_result):
    cell, config = ctx.cell, ctx.config
    reference = cmp.train_reference(
        config["arch"], state["host_vars"], state["batches"],
        **reference_kwargs(cell, config["precision"]["train"]["reference"]))
    stats = cmp.train_stats(state["program"], reference)
    ctx.log(phase="compare", program_losses=state["program"]["losses"],
            reference_losses=reference["losses"], **stats)
    out = {"failed": (float(window_result["failed"]), 0.0)}
    for name, limit in cell["limits"].items():
        out[name] = (stats[name], limit)
    return out
