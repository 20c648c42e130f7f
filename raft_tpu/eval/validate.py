"""Sintel/KITTI validation loop: the reference's acceptance protocol, TPU-first.

Protocol parity with ``scripts/validate_sintel.py:164-206`` (the published
README numbers): normalize to [-1, 1], replicate-pad to %8, 32 flow updates,
EPE of the final prediction, FPS excluding the first (compile) call.

TPU-first deltas:
  * final-only forward (``emit_all=False``) — no N-way prediction stack;
  * background-thread prefetch pipelines host I/O with device compute (the
    reference loads synchronously between device calls, SURVEY.md §3.3);
  * per-resolution jit cache — Sintel is constant-resolution so exactly one
    compilation happens;
  * chained FPS: dispatch is asynchronous and per-call host overhead is
    not the device's, so throughput is measured by chaining K pairs through
    ONE compiled ``lax.scan`` program and fetching a single scalar (the
    ``block_until_ready`` at the end of the timed work) — the same doctrine
    as ``bench.py``.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, Iterable, Optional

import jax.numpy as jnp

import jax
import numpy as np

from raft_tpu.data.datasets import FlowDataset, Sintel
from raft_tpu.eval.padder import InputPadder
from raft_tpu.utils.prefetch import prefetch

__all__ = ["validate", "validate_sintel", "chained_pairs_per_s", "prefetch"]


def chained_pairs_per_s(
    model,
    variables,
    images1,
    images2,
    *,
    num_flow_updates: int = 32,
) -> float:
    """Chained throughput: N pairs in one compiled program, one fetch.

    All pairs run inside a single ``lax.scan``; one scalar (consumed by the
    scan carry so no step can be elided) is fetched to host afterwards. The
    device-to-host transfer cannot complete before the compute does, and the
    one dispatch + one fetch is paid once, amortized over N pairs.
    """

    def one_pair(carry, pair):
        im1, im2 = pair
        flow = model.apply(
            variables,
            im1[None],
            im2[None],
            train=False,
            num_flow_updates=num_flow_updates,
            emit_all=False,
        )
        return carry + flow.mean(), flow[0, 0, 0, 0]

    @jax.jit
    def run(pairs):
        return jax.lax.scan(one_pair, jnp.float32(0), pairs)

    pairs = (jnp.asarray(images1), jnp.asarray(images2))
    jax.block_until_ready(pairs)
    np.asarray(run(pairs)[0])  # compile + warm up
    t0 = time.perf_counter()
    np.asarray(run(pairs)[0])  # host fetch forces completion of every pair
    return pairs[0].shape[0] / (time.perf_counter() - t0)


def _prepare(sample, mode: str):
    im1 = sample["image1"].astype(np.float32) / 255.0 * 2.0 - 1.0
    im2 = sample["image2"].astype(np.float32) / 255.0 * 2.0 - 1.0
    padder = InputPadder(im1.shape, mode=mode)
    im1, im2 = padder.pad(im1, im2)
    out = {
        "image1": im1[None],
        "image2": im2[None],
        "flow": sample.get("flow"),
        "valid": sample.get("valid"),
    }
    return out, padder


def validate(
    model,
    variables,
    dataset: FlowDataset,
    *,
    num_flow_updates: int = 32,
    mode: str = "sintel",
    use_valid_mask: Optional[bool] = None,
    fps_pairs: int = 64,
    progress: bool = False,
    apply_fn=None,
) -> Dict[str, float]:
    """Run the reference validation protocol over ``dataset``.

    Returns ``{"epe", "1px", "3px", "5px", "fps"}`` (pixel-weighted like the
    reference: EPE list is per-pixel concatenated, i.e. the mean over all
    pixels of all pairs).

    ``use_valid_mask``: whether EPE is restricted to the dataset's validity
    mask. Defaults to ``mode != "sintel"`` — the reference protocol averages
    over ALL pixels for Sintel's dense GT (``validate_sintel.py:187-196``),
    while sparse-GT datasets (KITTI) must mask. ``fps_pairs``: how many
    same-shaped pairs to chain for the throughput measurement (0 disables;
    fps is then NaN, never a per-call wall-clock guess). The default of 64
    follows ``bench.py``'s chain-length doctrine: the chain's one-time
    dispatch + fetch cost leaks 1/N of itself into the per-pair figure, so
    a short chain under-reports the rate; shorter chains are only used
    when the dataset has fewer same-shaped pairs.

    ``apply_fn``: optional pre-built ``(image1, image2) -> flow`` override.
    The default bakes ``variables`` into a fresh ``jax.jit`` closure, which
    is right for one-shot validation but recompiles on every call — in-loop
    eval (Trainer) passes a cached jit that takes variables as a traced
    argument so the multi-minute model compile is paid once per run.
    """
    if use_valid_mask is None:
        use_valid_mask = mode != "sintel"
    if apply_fn is None:
        apply_fn = jax.jit(
            partial(
                model.apply,
                variables,
                train=False,
                num_flow_updates=num_flow_updates,
                emit_all=False,
            )
        )

    epes = []
    mags = []
    fps_batch = []
    it: Iterable = range(len(dataset))
    if progress:
        try:
            from tqdm import tqdm

            it = tqdm(it, total=len(dataset))
        except ImportError:
            pass

    stream = prefetch((_prepare(dataset[i], mode) for i in it), depth=2)
    for batch, padder in stream:
        flow = apply_fn(batch["image1"], batch["image2"])

        if len(fps_batch) < fps_pairs and (
            not fps_batch
            or batch["image1"][0].shape == fps_batch[0][0].shape
        ):
            fps_batch.append((batch["image1"][0], batch["image2"][0]))

        flow = padder.unpad(np.asarray(flow))[0]
        gt = batch["flow"]
        if gt is None:
            continue
        epe = np.linalg.norm(flow - gt, axis=-1)
        mag = np.linalg.norm(gt, axis=-1)
        valid = batch["valid"]
        if use_valid_mask and valid is not None:
            epe = epe[valid]
            mag = mag[valid]
        epes.append(epe.reshape(-1))
        mags.append(mag.reshape(-1))

    # No ground truth anywhere (test split) -> NaN metrics, never a
    # fabricated perfect score.
    epe_all = np.concatenate(epes) if epes else np.full(1, np.nan)
    mag_all = np.concatenate(mags) if mags else np.full(1, np.nan)
    fps = float("nan")
    if len(fps_batch) >= 2:
        fps = chained_pairs_per_s(
            model,
            variables,
            np.stack([p[0] for p in fps_batch]),
            np.stack([p[1] for p in fps_batch]),
            num_flow_updates=num_flow_updates,
        )
    # KITTI Fl-all: fraction of (valid) pixels that are outliers, i.e.
    # EPE > 3 px AND EPE > 5% of the GT magnitude (the KITTI-2015 metric;
    # harmless extra information on dense-GT datasets). No GT -> NaN, same
    # rule as above — the comparison chain would otherwise yield a
    # fabricated perfect 0.0.
    f1 = (
        np.mean((epe_all > 3.0) & (epe_all > 0.05 * mag_all))
        if epes
        else float("nan")
    )
    return {
        "epe": float(np.mean(epe_all)),
        "1px": float(np.mean(epe_all < 1.0)),
        "3px": float(np.mean(epe_all < 3.0)),
        "5px": float(np.mean(epe_all < 5.0)),
        "f1": float(f1),
        "fps": float(fps),
    }


def validate_sintel(
    model,
    variables,
    root: str,
    *,
    num_flow_updates: int = 32,
    dstypes=("clean", "final"),
    progress: bool = True,
) -> Dict[str, Dict[str, float]]:
    """Full Sintel-train validation (both passes), reference protocol."""
    results = {}
    for dstype in dstypes:
        ds = Sintel(root, split="training", dstype=dstype)
        results[dstype] = validate(
            model,
            variables,
            ds,
            num_flow_updates=num_flow_updates,
            mode="sintel",
            progress=progress,
        )
    return results
