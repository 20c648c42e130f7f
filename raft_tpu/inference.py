"""High-level inference API: the framework owns the input contract.

The reference pushes [-1, 1] normalization and %8 replicate-padding onto
every caller (``examples/demo.py:7-10``, ``scripts/validate_sintel.py:
177-183`` there) — SURVEY.md §7.3 lists that split ownership as a hard
part. :class:`FlowEstimator` owns it end to end: raw [0, 255] images in
(uint8 or float, batched or single), final flow out at the input
resolution, with a per-shape jit cache so constant-resolution streams
compile exactly once. The raw ``model.apply`` contract stays available
for parity testing.
"""

from __future__ import annotations

import threading
import warnings
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from raft_tpu.eval.padder import InputPadder

__all__ = ["FlowEstimator", "FlowStream"]


class FlowEstimator:
    """Raw image pairs -> optical flow, with the full input contract owned.

    Args:
        model: a built RAFT module.
        variables: its variable tree (``{'params': ...[, 'batch_stats']}``).
        num_flow_updates: refinement iterations (32 = the published
            protocol; 12 is the common fast setting).
        pad_mode: ``'sintel'`` splits the vertical pad top/bottom (the
            Sintel eval protocol), ``'downstream'`` pads bottom-only
            (KITTI and general use).

    Example::

        model, variables = raft_large(pretrained=True)
        estimate = FlowEstimator(model, variables)
        flow = estimate(image1, image2)   # (H, W, 2) float32, pixels
    """

    def __init__(
        self,
        model,
        variables,
        *,
        num_flow_updates: int = 32,
        pad_mode: str = "sintel",
    ):
        self.model = model
        self.variables = variables
        self.num_flow_updates = num_flow_updates
        self.pad_mode = pad_mode
        # weights live on device once; apply_fn takes them as a traced arg
        # so the per-shape cache below never rebakes them as constants.
        # num_flow_updates is a static arg so per-call overrides compile
        # one program per distinct value, exactly like shapes do.
        self._dev_vars = jax.device_put(variables)
        self._apply = jax.jit(
            partial(model.apply, train=False, emit_all=False),
            static_argnames=("num_flow_updates",),
        )
        # the class is advertised for streams and the serve engine calls it
        # from worker threads: cache bookkeeping is lock-guarded
        self._cache_lock = threading.Lock()
        self._cache_info: Dict[Tuple[int, ...], int] = {}
        # stream-mode applies (encode-once feature caching), built lazily so
        # pairwise-only users never pay for them
        self._encode_apply = None
        self._iterate_apply = None

    def cache_info(self) -> Dict[Tuple[int, ...], int]:
        """Per-padded-shape call counts (a snapshot; thread-safe)."""
        with self._cache_lock:
            return dict(self._cache_info)

    @classmethod
    def from_preset(
        cls,
        preset: str = "throughput",
        *,
        arch: str = "raft_large",
        pretrained: bool = True,
        checkpoint: Optional[str] = None,
        **kw,
    ) -> "FlowEstimator":
        """Build an estimator at a named deployment precision preset.

        The presets (``'quality'`` / ``'throughput'``) are
        the golden-EPE-gated precision configs of
        :meth:`raft_tpu.serve.ServeConfig.preset` — ``'throughput'``
        (bf16 convs + bf16 correlation storage, the fastest validated
        config) is the default. Precision knobs change activation and
        storage casts only, so pretrained fp32 checkpoints load
        unchanged. Extra ``**kw`` goes to :class:`FlowEstimator`.
        """
        from raft_tpu.models.zoo import raft_for_serving
        from raft_tpu.serve.config import ServeConfig

        model, variables = raft_for_serving(
            ServeConfig.preset(preset), arch=arch,
            pretrained=pretrained, checkpoint=checkpoint,
        )
        return cls(model, variables, **kw)

    @staticmethod
    def _normalize(img: np.ndarray) -> np.ndarray:
        """[0, 255] uint8/float -> [-1, 1] float32 (the model contract)."""
        img = np.asarray(img)
        if img.ndim == 3:
            img = img[None]
        if img.ndim != 4 or img.shape[-1] != 3:
            raise ValueError(
                f"expected (H, W, 3) or (B, H, W, 3) RGB images, got "
                f"{img.shape}"
            )
        if img.dtype.kind == "f" and not np.isfinite(img).all():
            # NaN/Inf pixels would sail through normalization and silently
            # poison the correlation volume (every cost row touching the bad
            # pixel goes nonfinite) — reject at the API edge instead. Checked
            # before the range heuristic below: np.max is NaN-poisoned, so
            # the heuristic cannot be trusted on nonfinite input.
            raise ValueError(
                "nonfinite pixel values (NaN/Inf) in input image: rejected "
                "at the API edge — they would poison the correlation volume "
                "downstream"
            )
        if img.dtype.kind == "f" and img.size and float(np.max(img)) <= 1.5:
            # catch callers migrating from the raw model.apply contract:
            # feeding already-normalized [-1,1] floats through /255 would
            # silently collapse the pair to ~-1 everywhere. Negative values
            # prove pre-normalization; an all-positive low-max image could
            # legitimately be a near-black [0, 255] frame, so that case
            # only warns (it may also be a [0, 1]-normalized input).
            if float(np.min(img)) < 0.0:
                raise ValueError(
                    "images look already normalized (float with negative "
                    "values and max <= 1.5); FlowEstimator expects raw "
                    "[0, 255] values — use model.apply directly for "
                    "pre-normalized inputs"
                )
            warnings.warn(
                "float image with max <= 1.5: treating as raw [0, 255] "
                "(a near-black frame). If this input is [0, 1]-normalized, "
                "rescale to [0, 255] or use model.apply directly.",
                stacklevel=3,
            )
        return img.astype(np.float32) / 255.0 * 2.0 - 1.0

    def _validate_iters(self, n: Optional[int]) -> int:
        """Resolve a per-call ``num_flow_updates`` override against the
        configured maximum (the instance's ``num_flow_updates``)."""
        if n is None:
            return self.num_flow_updates
        if int(n) != n or not (1 <= int(n) <= self.num_flow_updates):
            raise ValueError(
                f"num_flow_updates must be an int in "
                f"[1, {self.num_flow_updates}] (the configured maximum), "
                f"got {n!r}"
            )
        return int(n)

    def __call__(
        self, image1, image2, *, num_flow_updates: Optional[int] = None
    ) -> np.ndarray:
        """Compute flow from ``image1`` to ``image2``.

        Accepts ``(H, W, 3)`` or ``(B, H, W, 3)`` images in [0, 255]
        (uint8 or float). Returns flow at the input resolution:
        ``(H, W, 2)`` for single pairs, ``(B, H, W, 2)`` batched.
        ``num_flow_updates`` overrides the instance default per call
        (RAFT is anytime — fewer iterations trade accuracy for latency),
        validated against the configured maximum.
        """
        iters = self._validate_iters(num_flow_updates)
        single = np.asarray(image1).ndim == 3
        im1 = self._normalize(image1)
        im2 = self._normalize(image2)
        if im1.shape != im2.shape:
            raise ValueError(
                f"image shapes differ: {im1.shape} vs {im2.shape}"
            )
        padder = InputPadder(im1.shape, mode=self.pad_mode)
        p1, p2 = padder.pad(im1, im2)
        with self._cache_lock:
            self._cache_info[p1.shape] = self._cache_info.get(p1.shape, 0) + 1
        flow = self._apply(self._dev_vars, p1, p2, num_flow_updates=iters)
        flow = padder.unpad(np.asarray(flow))
        return flow[0] if single else flow

    # -- stream mode (shared-frame feature cache) --------------------------

    def _stream_applies(self):
        """Jitted encode/iterate applies for stream mode (built once)."""
        with self._cache_lock:
            if self._encode_apply is None:
                self._encode_apply = jax.jit(
                    partial(self.model.apply, train=False, method="encode_frame")
                )
                self._iterate_apply = jax.jit(
                    partial(
                        self.model.apply,
                        train=False,
                        emit_all=False,
                        num_flow_updates=self.num_flow_updates,
                        method="iterate",
                    )
                )
            return self._encode_apply, self._iterate_apply

    def open_stream(self) -> "FlowStream":
        """Start a video-stream session with encode-once feature caching.

        Consecutive pairs of a stream share a frame; pairwise ``__call__``
        re-encodes it every time. A :class:`FlowStream` encodes each frame
        once and reuses frame t's feature and context maps as pair
        (t, t+1)'s first-frame inputs — roughly half the encoder FLOPs —
        while producing flow numerically equivalent to the pairwise path
        (per-sample normalization; see ``RAFT.encode_frame``).
        """
        return FlowStream(self)


class FlowStream:
    """One video-stream session over a :class:`FlowEstimator`.

    Feed frames in order; each call returns the flow from the *previous*
    frame to this one, or ``None`` for the first frame (nothing to pair
    with yet). All frames of a stream must share one resolution. Not
    thread-safe — one stream, one caller thread (open several streams for
    concurrency; the cached state is per-stream).
    """

    def __init__(self, estimator: FlowEstimator):
        self._est = estimator
        self._encode, self._iterate = estimator._stream_applies()
        self._shape: Optional[Tuple[int, ...]] = None
        self._padder: Optional[InputPadder] = None
        self._fmap = None      # previous frame's feature map (device)
        self._ctx = None       # previous frame's raw context output (device)

    def reset(self) -> None:
        """Drop the cached frame: the next frame primes a fresh pair."""
        self._fmap = None
        self._ctx = None

    def __call__(self, frame) -> Optional[np.ndarray]:
        """Advance the stream by one frame; flow(prev -> frame) or None."""
        est = self._est
        img = est._normalize(frame)
        if self._shape is None:
            self._shape = img.shape
            self._padder = InputPadder(img.shape, mode=est.pad_mode)
        elif img.shape != self._shape:
            raise ValueError(
                f"stream frames must share one resolution; stream is "
                f"{self._shape}, got {img.shape} (open a new stream)"
            )
        p = self._padder.pad(img)
        with est._cache_lock:
            est._cache_info[p.shape] = est._cache_info.get(p.shape, 0) + 1
        fmap, ctx = self._encode(est._dev_vars, p)
        prev_fmap, prev_ctx = self._fmap, self._ctx
        self._fmap, self._ctx = fmap, ctx
        if prev_fmap is None:
            return None
        flow = self._iterate(est._dev_vars, prev_fmap, fmap, prev_ctx)
        flow = self._padder.unpad(np.asarray(flow))
        return flow[0] if np.asarray(frame).ndim == 3 else flow
