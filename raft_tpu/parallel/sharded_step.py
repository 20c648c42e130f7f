"""Mesh-sharded training: the DP(+spatial) compilation of the train step.

One code path for 1..N chips: the same pure step function from
``raft_tpu.train.step`` is jitted with explicit in/out shardings — state
replicated, batch sharded ``(data, space)`` — and XLA's SPMD partitioner
emits the psum gradient all-reduce over ICI and the conv halo exchanges.
This replaces the reference's (absent) NCCL layer with compiler-scheduled
collectives (SURVEY.md §5.8).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import optax
from jax.sharding import Mesh

from raft_tpu.parallel.mesh import (
    batch_sharding, replicated, traced_under, window_batch_sharding,
)
from raft_tpu.train.state import TrainState

__all__ = [
    "make_sharded_train_step",
    "make_sharded_window_step",
    "shard_state",
]


def make_sharded_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    num_flow_updates: int = 12,
    gamma: float = 0.8,
    max_flow: float = 400.0,
    donate: bool = True,
    check_numerics: bool = False,
    numerics_policy: str = "raise",
    spike_factor: float = 0.0,
    ema_decay: float = 0.99,
    spike_warmup: int = 20,
) -> Callable[[TrainState, Dict], Tuple[TrainState, Dict]]:
    """Jit the train step over ``mesh``: replicated state, sharded batch.

    The divergence-guard knobs (``numerics_policy='skip'`` etc.) compose
    unchanged: the skip decision is a replicated scalar computed from
    all-reduced gradients, so every device selects the same branch."""
    from raft_tpu.train.step import make_train_step_fn

    step_fn = make_train_step_fn(
        model, tx, num_flow_updates=num_flow_updates, gamma=gamma,
        max_flow=max_flow, check_numerics=check_numerics,
        numerics_policy=numerics_policy, spike_factor=spike_factor,
        ema_decay=ema_decay, spike_warmup=spike_warmup,
    )

    rep = replicated(mesh)
    bsh = batch_sharding(mesh)
    return jax.jit(
        traced_under(mesh, step_fn),
        in_shardings=(rep, bsh),
        out_shardings=(rep, rep),
        donate_argnums=(0,) if donate else (),
    )


def make_sharded_window_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    window_size: int,
    num_flow_updates: int = 12,
    gamma: float = 0.8,
    max_flow: float = 400.0,
    donate: bool = True,
    check_numerics: bool = False,
    numerics_policy: str = "raise",
    spike_factor: float = 0.0,
    ema_decay: float = 0.99,
    spike_warmup: int = 20,
) -> Callable[[TrainState, Dict], Tuple[TrainState, Dict]]:
    """Jit the fused ``window_size``-step scan over ``mesh``.

    The window's leading (scan) axis stays unsharded — every device runs
    every step — while batch/height shard as in the per-step program, so
    the per-step collectives (gradient all-reduce, conv halos) are emitted
    INSIDE the scan body and the host still dispatches once per window.
    Skip-guard semantics compose exactly as in
    :func:`make_sharded_train_step`: the skip decision is a replicated
    scalar, so every device selects the same branch at every scanned step.
    """
    from raft_tpu.train.step import make_window_step_fn

    fn = make_window_step_fn(
        model, tx, window_size=window_size,
        num_flow_updates=num_flow_updates, gamma=gamma, max_flow=max_flow,
        check_numerics=check_numerics, numerics_policy=numerics_policy,
        spike_factor=spike_factor, ema_decay=ema_decay,
        spike_warmup=spike_warmup,
    )
    rep = replicated(mesh)
    return jax.jit(
        traced_under(mesh, fn),
        in_shardings=(rep, window_batch_sharding(mesh)),
        out_shardings=(rep, rep),
        donate_argnums=(0,) if donate else (),
    )


def shard_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Replicate the training state over every device of the mesh."""
    return jax.device_put(state, replicated(mesh))
