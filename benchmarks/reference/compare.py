"""The numbers ``correct`` rests on: what the timed path produced, held
against the plain reference (``reference/raft.py``). Nothing here imports
the program; it gets arrays (inputs as the program was given them, outputs
as it returned them) and the benchmark's own weights.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import raft as ref


def _hashable(arch):
    return tuple(sorted((k, _freeze(v)) for k, v in arch.items()))


def _freeze(v):
    return tuple(_freeze(x) for x in v) if isinstance(v, (list, tuple)) else v


def _thaw(frozen):
    return {k: v for k, v in frozen}


# -- serving ----------------------------------------------------------------------

def preprocess(image, bucket):
    """Raw [0, 255] ``(H, W, 3)`` -> [-1, 1], replicate-padded bottom/right
    to the bucket, with a batch dim: the service's input contract."""
    x = np.asarray(image, np.float32) / 255.0 * 2.0 - 1.0
    h, w = x.shape[:2]
    x = np.pad(x, ((0, bucket[0] - h), (0, bucket[1] - w), (0, 0)), mode="edge")
    return x[None]


@functools.lru_cache(maxsize=8)
def _forward_fn(frozen_arch, iters, precision):
    arch = _thaw(frozen_arch)
    return jax.jit(lambda v, a, b: ref.forward(
        arch, v, a, b, iters=iters, precision=precision))


def reference_flow(arch, variables, pair, *, bucket, iters, precision="fp32"):
    a, b = (preprocess(im, bucket) for im in pair)
    h, w = np.asarray(pair[0]).shape[:2]
    fn = _forward_fn(_hashable(arch), int(iters), precision)
    return np.asarray(fn(variables, a, b))[0, :h, :w]


def flow_stats(got, want) -> Dict[str, float]:
    """How far ``got`` lies from the reference field ``want``: the mean
    and the 99th percentile of the endpoint error, in px. Absolute, since
    a relative error's denominator (the field's size) swings 4x over
    seeds (PERF.md, PR 25)."""
    epe = np.sqrt(((np.asarray(got, np.float64) - want) ** 2).sum(-1))
    return {
        "flow_epe_mean_px": float(epe.mean()),
        "flow_epe_p99_px": float(np.percentile(epe, 99)),
        "finite": float(np.isfinite(np.asarray(got)).all()),
    }


def serve_stats(arch, variables, pairs, served: Sequence[np.ndarray], *,
                bucket, iters, precision="fp32") -> Dict[str, float]:
    """Each statistic's worst reading over the sampled pairs; NaN where
    any served value is not finite."""
    rows: List[Dict[str, float]] = []
    for pair, flow in zip(pairs, served):
        want = reference_flow(arch, variables, pair, bucket=bucket,
                              iters=iters, precision=precision)
        rows.append(flow_stats(flow, want))
    finite = all(r["finite"] for r in rows)
    return {k: max(r[k] for r in rows) if finite else float("nan")
            for k in rows[0] if k != "finite"}


# -- training ---------------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.sqrt(np.sum(np.square(
        np.asarray(x, np.float64))))) for p, x in flat}


@functools.lru_cache(maxsize=4)
def _grad_fn(frozen_arch, iters, precision):
    arch = _thaw(frozen_arch)
    return jax.jit(lambda v, b: ref.loss_and_grads(
        arch, v, b, iters=iters, precision=precision))


def train_reference(arch, variables, batches, *, iters, schedule,
                    precision="fp32", optimizer=None) -> Dict[str, object]:
    """Follow the first ``len(batches)`` steps: each step's loss, the
    first gradient as the optimizer gets it (clipped), per-leaf norms,
    and the per-leaf norm of the parameters' change over all of them."""
    optimizer = optimizer or {}
    grad = _grad_fn(_hashable(arch), int(iters), precision)
    params0 = variables["params"]
    params, state = params0, ref.adamw_init(params0)
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        batch = {k: jnp.asarray(v, jnp.float32) for k, v in batch.items()}
        loss, grads = grad({**variables, "params": params}, batch)
        lr = ref.one_cycle_lr(step, **schedule)
        params, state, clipped = jax.jit(
            functools.partial(ref.adamw_update, lr=lr, **optimizer)
        )(params, grads, state)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(clipped)
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   leaves=None):
    """The widest gap between the program's norm and the reference's over
    leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    keys = list(want) if leaves is None else list(leaves)
    floor = float(np.median([want[k] for k in keys]))
    worst, where = 0.0, None
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], floor)
        if not gap <= worst:  # NaN wins
            worst, where = gap, k
    return float(worst), where


def train_stats(program, reference) -> Dict[str, float]:
    """``program`` / ``reference``: ``losses``, ``grad_norms``,
    ``change_norms``. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam
    and are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_gap_leaf"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    g = reference["grad_norms"]
    med = float(np.median(list(g.values())))
    moved = [k for k in g if g[k] >= 1e-3 * med]
    out["change_norm_gap"], out["change_norm_gap_leaf"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], moved)
    out["leaves_left_out"] = float(len(g) - len(moved))
    return out
