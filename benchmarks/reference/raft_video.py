"""The plain reference for video: RAFT over a clip the way upstream runs it
(princeton-vl/RAFT ``evaluate.py::create_sintel_submission(model, iters=32,
warm_start=True)``; RAFT, Teed & Deng, arXiv:2003.12039, Table 1 "Ours
(warm-start)"): the clip is walked frame by frame, pair ``(t, t+1)`` is a
whole forward pass, and with ``warm_start`` the 1/8-grid flow of pair
``t-1`` goes through ``forward_interpolate`` and starts pair ``t``
(``coords1 = coords0 + flow_init``).

Plain ``jax.numpy``, fp32, ``Precision.HIGHEST``; nothing of the program.
The layers are ``reference/raft.py``'s own (``encoder``, ``corr_pyramid``,
``corr_lookup``, ``_update``, ``_upsample``); what this file adds is the
start from ``init_flow``, the interpolation and the loop over a clip.

Departures from upstream, all cited from memory (no copy on disk; the
configuration file lists them under ``assumed``):

* ``forward_interpolate`` searches the nearest kept point by brute force
  (every distance, squared Euclidean, fp32, ``argmin``: ties go to the
  lowest source index) where upstream calls
  ``scipy.interpolate.griddata(..., method='nearest')`` (a KD-tree over
  float64 points, whose choice among equidistant points is the tree's);
* frames are padded bottom/right to a multiple of 8 by edge replication
  (the service's input contract, as ``raft_large``'s file says) where
  upstream's ``InputPadder('sintel')`` pads both sides of the height;
* each pair's two frames go through the feature encoder together and the
  first through the context encoder, as upstream's ``RAFT.forward`` does
  — a frame is encoded twice over a clip, and instance norm (per frame)
  and batch norm (running statistics) make that the same numbers as
  encoding it once.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import raft as ref


def forward_interpolate(flow):
    """``(h8, w8, 2)`` flow of pair ``t-1`` -> the start of pair ``t``:
    every cell ``p`` lands at ``p + flow[p]``; points strictly inside
    ``(0, w8) x (0, h8)`` are kept; each grid cell takes the flow of the
    nearest kept point; zeros when no point is kept."""
    flow = jnp.asarray(flow, jnp.float32)
    h, w = flow.shape[:2]
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    x0, y0 = xs.reshape(-1), ys.reshape(-1)
    x1 = x0 + flow[..., 0].reshape(-1)
    y1 = y0 + flow[..., 1].reshape(-1)
    valid = (x1 > 0) & (x1 < w) & (y1 > 0) & (y1 < h)
    dx = x0[:, None] - x1[None, :]
    dy = y0[:, None] - y1[None, :]
    dist = jnp.where(valid[None, :], dx * dx + dy * dy, jnp.inf)
    nearest = jnp.argmin(dist, axis=1)
    out = flow.reshape(-1, 2)[nearest].reshape(h, w, 2)
    return jnp.where(valid.any(), out, jnp.zeros_like(out))


def forward_pair(arch: Dict[str, Any], variables, image1, image2, *,
                 iters: int, init_flow=None, precision: str = "fp32"):
    """One pair, started from ``coords0 + init_flow`` (``(B, H/8, W/8, 2)``;
    None or zeros: the cold start). Returns ``(flow, flow8)``: the
    ``(B, H, W, 2)`` flow and the 1/8-grid ``coords1 - coords0`` it was
    upsampled from, which is what the next pair's warm start takes."""
    ops = ref._Ops(precision)
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    b = image1.shape[0]
    fmaps = ref.encoder(
        ops, jnp.concatenate([image1, image2], 0), params["feature_encoder"],
        stats.get("feature_encoder"), kind=arch["feature_encoder_block"],
        norm=arch["feature_encoder_norm"], train=False,
    )
    fmap1, fmap2 = fmaps[:b], fmaps[b:]
    ctx = ref.encoder(
        ops, image1, params["context_encoder"], stats.get("context_encoder"),
        kind=arch["context_encoder_block"], norm=arch["context_encoder_norm"],
        train=False,
    )
    hid = arch["gru_hidden"]
    hidden, context = jnp.tanh(ctx[..., :hid]), jax.nn.relu(ctx[..., hid:])
    pyramid = ref.corr_pyramid(ops, fmap1, fmap2, arch["corr_levels"])

    h8, w8 = fmap1.shape[1], fmap1.shape[2]
    xs, ys = jnp.meshgrid(jnp.arange(w8, dtype=jnp.float32),
                          jnp.arange(h8, dtype=jnp.float32), indexing="xy")
    coords0 = jnp.broadcast_to(jnp.stack([xs, ys], -1)[None], (b, h8, w8, 2))
    coords1 = coords0 if init_flow is None else coords0 + init_flow

    def step(carry, _):
        coords1, hidden = carry
        feats = ref.corr_lookup(pyramid, coords1, arch["corr_radius"])
        hidden, delta = ref._update(ops, arch, params["update_block"], hidden,
                                    context, feats, coords1 - coords0)
        return (coords1 + delta, hidden), None

    (coords1, hidden), _ = jax.lax.scan(step, (coords1, hidden), None,
                                        length=iters)
    flow8 = coords1 - coords0
    return ref._upsample(ops, arch, params, flow8, hidden), flow8


@functools.lru_cache(maxsize=8)
def _pair_fn(frozen_arch, iters, precision):
    arch = dict(frozen_arch)
    return jax.jit(lambda v, a, b, init: forward_pair(
        arch, v, a, b, iters=iters, init_flow=init, precision=precision))


_interp_fn = jax.jit(forward_interpolate)


def forward_step(arch: Dict[str, Any], variables, frame1, frame2, *,
                 iters: int, prev_flow8=None, precision: str = "fp32"):
    """One link of upstream's loop: the pair ``(frame1, frame2)``
    (``(1, H, W, 3)`` in [-1, 1]) started from ``forward_interpolate`` of
    the previous pair's 1/8-grid flow ``prev_flow8`` (``(h8, w8, 2)``;
    None: a clip's first pair, cold). Returns ``(flow, flow8)`` with the
    batch dim dropped."""
    from benchmarks.reference.compare import _hashable

    fn = _pair_fn(_hashable(arch), int(iters), precision)
    h8, w8 = frame1.shape[1] // 8, frame1.shape[2] // 8
    if prev_flow8 is None:
        init = jnp.zeros((1, h8, w8, 2), jnp.float32)
    else:
        init = _interp_fn(jnp.asarray(prev_flow8, jnp.float32))[None]
    flow, flow8 = fn(variables, frame1, frame2, init)
    return np.asarray(flow[0]), flow8[0]


def forward_clip(arch: Dict[str, Any], variables, frames, *, iters: int,
                 warm_start: bool, precision: str = "fp32") -> List[np.ndarray]:
    """Upstream's loop: ``frames`` is ``(N, H, W, 3)`` in [-1, 1] with H and
    W multiples of 8; returns the N-1 flows ``(H, W, 2)``, pair ``t`` warm-
    started from ``forward_interpolate`` of pair ``t-1``'s 1/8-grid flow
    when ``warm_start``."""
    frames = np.asarray(frames, np.float32)
    flows, prev8 = [], None
    for t in range(frames.shape[0] - 1):
        flow, flow8 = forward_step(
            arch, variables, frames[t:t + 1], frames[t + 1:t + 2],
            iters=iters, prev_flow8=prev8 if warm_start else None,
            precision=precision)
        prev8 = flow8
        flows.append(flow)
    return flows
