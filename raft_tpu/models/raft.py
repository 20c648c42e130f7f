"""The RAFT orchestrator: encode, correlate, iteratively refine.

Structure (reference behavior contract: ``jax_raft/model.py:513-605``):
  1. Feature-encode both frames in one batch-stacked pass (2x arithmetic
     intensity on the conv stack).
  2. Build the correlation pyramid once.
  3. Context-encode frame 1; split into GRU hidden-state init (tanh) and
     context features (relu).
  4. Refine iteratively under ``nn.scan`` — one fused XLA while-loop on TPU.

TPU-first additions over the reference:
  * ``emit_all=False`` runs the recurrence carry-only and upsamples once at
    the end — inference skips N-1 convex upsamples and never materializes the
    ``(N, B, H, W, 2)`` prediction stack (the reference always does;
    ``jax_raft/model.py:595-605``).
  * The apply surface is split into ``encode_frame`` (per-frame feature +
    context encode) and ``iterate`` (pyramid + scan + upsample), with
    ``__call__`` composing them — stream callers (``FlowEstimator`` streams,
    the serve engine's sessions) cache frame t's encode and pay only the
    refinement for pair (t, t+1), roughly halving encoder FLOPs on video.
  * The refinement itself is further split for iteration-level continuous
    batching (the serve engine's resident iteration pool):
    ``begin_refinement`` turns encoded inputs into a per-request recurrent
    *state* pytree (pyramid, coords, hidden, context — every leaf with a
    leading batch/slot dim), ``iterate_step`` advances that state by
    exactly ONE GRU refinement, and ``finalize_flow`` runs the final
    convex upsample. ``begin_pair`` composes the pairwise encode with
    ``begin_refinement``. Together they decompose ``iterate`` exactly
    (same scanned body, same upsample tail), so a pool that admits and
    retires requests between single-iteration dispatches serves flow
    numerically equivalent to the whole-batch scan.
  * ``remat=True`` rematerializes each refinement step in the backward pass,
    trading FLOPs for activation memory during training. ``remat_policy``
    makes the trade selective (``jax.checkpoint`` policies): ``'dots'``
    saves every dot/matmul result, ``'dots_no_batch'`` only those without
    batch dims, ``'corr'`` saves exactly the per-iteration correlation
    features (the step's most expensive recompute — pyramid gather +
    projection) and recomputes the cheap elementwise/conv tail.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.ops.sampling import coords_grid
from raft_tpu.models.corr import LazyCorrFeatures
from raft_tpu.ops.upsample import upsample_flow

__all__ = ["RAFT", "REMAT_POLICIES"]

# Named jax.checkpoint policies for selective rematerialization of the scan
# body. Values are thunks so the table stays importable if a policy moves
# between jax versions.
REMAT_POLICIES = {
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": lambda: (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    ),
    "corr": lambda: jax.checkpoint_policies.save_only_these_names(
        "corr_features"
    ),
}


def _refinement_step(mdl: "RAFT", carry, _, *, coords0, context, pyramid, train, emit_all):
    """One refinement iteration; scanned over via ``nn.scan``."""
    coords1, hidden = carry
    # Gradient-truncation point: flow targets do not backprop through the
    # accumulated coordinates (per the RAFT paper).
    coords1 = jax.lax.stop_gradient(coords1)

    # Deferred lookup: the motion encoder triggers it via its convcorr1
    # projection so lookup+projection can fuse into one kernel (the
    # default dense block computes the identical relu(taps @ W + b)).
    corr_features = LazyCorrFeatures(mdl.corr_block, pyramid, coords1)
    flow = coords1 - coords0
    hidden, delta_flow = mdl.update_block(
        hidden, context, corr_features, flow, train=train
    )
    coords1 = coords1 + delta_flow

    if not emit_all:
        return (coords1, hidden), None

    up_mask = None
    if mdl.mask_predictor is not None:
        up_mask = mdl.mask_predictor(hidden, train=train)
    upsampled = upsample_flow(coords1 - coords0, up_mask)
    return (coords1, hidden), upsampled


class RAFT(nn.Module):
    """RAFT optical-flow estimator (Teed & Deng, arXiv:2003.12039).

    Component contract (duck-typed, as in the reference docstring
    ``jax_raft/model.py:513-548``): ``feature_encoder`` / ``context_encoder``
    downsample 8x; ``corr_block`` exposes ``build_pyramid`` /
    ``index_pyramid`` / ``out_channels`` (and ``resident_pyramid`` to be
    held in the serve pool: ``begin_refinement``); ``update_block`` exposes
    ``hidden_state_size``; ``mask_predictor`` (optional) outputs 8*8*9
    channels.
    """

    feature_encoder: nn.Module
    context_encoder: nn.Module
    corr_block: Any
    update_block: nn.Module
    mask_predictor: Optional[nn.Module] = None
    remat: bool = False
    remat_policy: Optional[str] = None

    @nn.compact
    def __call__(
        self,
        image1,
        image2,
        train: bool = False,
        num_flow_updates: int = 12,
        emit_all: bool = True,
    ):
        """Compute flow from ``image1`` to ``image2``.

        Args:
            image1, image2: ``(B, H, W, 3)`` images normalized to [-1, 1],
                H and W divisible by 8.
            train: training mode (BatchNorm batch statistics).
            num_flow_updates: refinement iterations (static).
            emit_all: if True, return all per-iteration full-res flows stacked
                as ``(N, B, H, W, 2)`` (training needs every prediction for
                the sequence loss); if False, return only the final flow
                ``(B, H, W, 2)`` without materializing the stack.
        """
        b, h, w, _ = image1.shape
        if image2.shape != image1.shape:
            raise ValueError("input images must have identical shapes")
        if h % 8 or w % 8:
            raise ValueError("input H and W must be divisible by 8")

        fmaps = self.feature_encoder(
            jnp.concatenate([image1, image2], axis=0), train=train
        )
        fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        if fmap1.shape[1:3] != (h // 8, w // 8):
            raise ValueError("feature encoder must downsample exactly 8x")

        context_out = self.context_encoder(image1, train=train)
        if context_out.shape[1:3] != (h // 8, w // 8):
            raise ValueError("context encoder must downsample exactly 8x")

        return self.iterate(
            fmap1,
            fmap2,
            context_out,
            train=train,
            num_flow_updates=num_flow_updates,
            emit_all=emit_all,
        )

    def encode_frame(self, image, train: bool = False):
        """Encode ONE frame batch: ``(B, H, W, 3)`` -> (feature map, raw
        context output), both at /8 resolution.

        This is the stream-cache unit: a video stream encodes each frame
        once and reuses frame t's outputs as pair (t, t+1)'s first-frame
        inputs (feature map -> ``fmap1``, context output -> GRU init +
        context features), instead of re-encoding it inside the pairwise
        ``__call__``. Per-sample normalization (InstanceNorm, or BatchNorm
        with ``train=False`` running stats) makes single-frame encoding
        numerically equivalent to the batch-stacked pairwise pass.
        """
        b, h, w, _ = image.shape
        if h % 8 or w % 8:
            raise ValueError("input H and W must be divisible by 8")
        fmap = self.feature_encoder(image, train=train)
        if fmap.shape[1:3] != (h // 8, w // 8):
            raise ValueError("feature encoder must downsample exactly 8x")
        context_out = self.context_encoder(image, train=train)
        if context_out.shape[1:3] != (h // 8, w // 8):
            raise ValueError("context encoder must downsample exactly 8x")
        return fmap, context_out

    def iterate(
        self,
        fmap1,
        fmap2,
        context_out,
        train: bool = False,
        num_flow_updates: int = 12,
        emit_all: bool = True,
    ):
        """The post-encode tail: correlation pyramid + iterative refinement.

        Takes pre-encoded inputs (``encode_frame`` outputs, or the stacked
        encode of ``__call__``) so callers holding cached frame features —
        the serve engine's stream sessions, :class:`FlowEstimator` streams —
        pay only the refinement FLOPs for reused frames. ``context_out`` is
        the *raw* context-encoder output (the tanh/relu split happens here).
        """
        b = fmap1.shape[0]
        h8, w8 = fmap1.shape[1], fmap1.shape[2]
        if fmap2.shape != fmap1.shape:
            raise ValueError("feature maps must have identical shapes")
        if context_out.shape[1:3] != (h8, w8):
            raise ValueError("context output must match the feature grid")

        pyramid = self.corr_block.build_pyramid(fmap1, fmap2)

        hidden_size = self.update_block.hidden_state_size
        if context_out.shape[-1] <= hidden_size:
            raise ValueError(
                f"context encoder outputs {context_out.shape[-1]} channels; "
                f"needs > hidden_state_size={hidden_size}"
            )
        hidden, context = jnp.split(context_out, [hidden_size], axis=-1)
        hidden = jnp.tanh(hidden)
        context = nn.relu(context)

        coords0 = coords_grid(b, h8, w8)
        coords1 = coords_grid(b, h8, w8)

        body = partial(
            _refinement_step,
            coords0=coords0,
            context=context,
            pyramid=pyramid,
            train=train,
            emit_all=emit_all,
        )
        if self.remat_policy is not None and not self.remat:
            raise ValueError(
                "remat_policy is set but remat=False — the policy would be "
                "silently ignored; enable remat or drop the policy"
            )
        if self.remat:
            policy = None
            if self.remat_policy is not None:
                if self.remat_policy not in REMAT_POLICIES:
                    raise ValueError(
                        f"unknown remat_policy {self.remat_policy!r}; "
                        f"choose from {sorted(REMAT_POLICIES)}"
                    )
                policy = REMAT_POLICIES[self.remat_policy]()
            body = nn.remat(body, prevent_cse=False, policy=policy)
        scan = nn.scan(
            body,
            variable_broadcast="params",
            split_rngs={"params": False},
            length=num_flow_updates,
        )
        (coords1, hidden), flows = scan(self, (coords1, hidden), None)

        if emit_all:
            return flows

        up_mask = None
        if self.mask_predictor is not None:
            up_mask = self.mask_predictor(hidden, train=train)
        return upsample_flow(coords1 - coords0, up_mask)

    # -- iteration-level entry points (the serve engine's resident pool) ---

    def begin_pair(self, image1, image2, init_flow=None, train: bool = False):
        """Pairwise admission for the iteration pool: encode both frames
        (batch-stacked, exactly as ``__call__`` does) and initialize the
        refinement state. Returns the ``begin_refinement`` state pytree.
        ``init_flow`` (optional, ``(B, H/8, W/8, 2)``) warm-starts the
        refinement — see :meth:`begin_refinement`.
        """
        b, h, w, _ = image1.shape
        if image2.shape != image1.shape:
            raise ValueError("input images must have identical shapes")
        if h % 8 or w % 8:
            raise ValueError("input H and W must be divisible by 8")
        fmaps = self.feature_encoder(
            jnp.concatenate([image1, image2], axis=0), train=train
        )
        fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        if fmap1.shape[1:3] != (h // 8, w // 8):
            raise ValueError("feature encoder must downsample exactly 8x")
        context_out = self.context_encoder(image1, train=train)
        if context_out.shape[1:3] != (h // 8, w // 8):
            raise ValueError("context encoder must downsample exactly 8x")
        return self.begin_refinement(
            fmap1, fmap2, context_out, init_flow=init_flow, train=train
        )

    def begin_refinement(self, fmap1, fmap2, context_out, init_flow=None,
                         train: bool = False):
        """Initialize per-request refinement state from encoded inputs.

        The head of :meth:`iterate` (pyramid build + context split + GRU
        init), returned as a state pytree instead of being consumed by a
        scan, so a resident iteration pool can hold many requests'
        recurrent state stacked along the leading dim and advance them one
        :meth:`iterate_step` at a time. Every leaf carries the batch as
        its leading dim — the correlation pyramid levels are reshaped from
        the ``(B*Q, hl, wl, 1)`` lookup layout to ``(B, Q, hl, wl, 1)``
        (``Q = h/8 * w/8``) so slot-granular insert/gather is a plain
        leading-axis index. ``iterate_step`` restores the lookup layout.

        ``init_flow`` (optional, ``(B, H/8, W/8, 2)``, (x, y) pixel units
        at the 1/8 grid) warm-starts the refinement: ``coords1`` is seeded
        at ``coords0 + init_flow`` instead of the zero-flow identity —
        RAFT's video-mode trick (Teed & Deng 2020) of initializing pair
        (t, t+1) from the forward-interpolated flow of (t-1, t), which puts the
        recurrence near its fixed point so far fewer iterations reach the
        same answer. Zeros (or ``None``) reproduce the cold start exactly.
        """
        b = fmap1.shape[0]
        h8, w8 = fmap1.shape[1], fmap1.shape[2]
        if fmap2.shape != fmap1.shape:
            raise ValueError("feature maps must have identical shapes")
        if context_out.shape[1:3] != (h8, w8):
            raise ValueError("context output must match the feature grid")

        # held across every iterate_step, so in the shapes the block wants
        # it held in (or refused, where a block's pyramid cannot be held
        # by slot): the block's answer, whatever its format. Every leaf
        # is q-major
        pyramid = jax.tree.map(
            lambda lvl: lvl.reshape((b, h8 * w8) + lvl.shape[1:]),
            self.corr_block.resident_pyramid(
                self.corr_block.build_pyramid(fmap1, fmap2)
            ),
        )

        hidden_size = self.update_block.hidden_state_size
        if context_out.shape[-1] <= hidden_size:
            raise ValueError(
                f"context encoder outputs {context_out.shape[-1]} channels; "
                f"needs > hidden_state_size={hidden_size}"
            )
        hidden, context = jnp.split(context_out, [hidden_size], axis=-1)
        coords1 = coords_grid(b, h8, w8)
        if init_flow is not None:
            if init_flow.shape != (b, h8, w8, 2):
                raise ValueError(
                    f"init_flow must be (B, H/8, W/8, 2) = "
                    f"{(b, h8, w8, 2)}, got {init_flow.shape}"
                )
            coords1 = coords1 + init_flow
        return {
            "pyramid": pyramid,
            "coords1": coords1,
            "hidden": jnp.tanh(hidden),
            "context": nn.relu(context),
        }

    def iterate_step(self, state, train: bool = False):
        """Advance refinement state by exactly ONE GRU iteration.

        The single-iteration dispatch unit of the serve engine's resident
        pool: one compiled program per (bucket, pool capacity) advances
        every slot by one step, so requests with different iteration
        targets can join and leave between dispatches. Runs the SAME
        scanned body as :meth:`iterate` (``_refinement_step``), so N calls
        reproduce an N-step scan. Returns the updated state (pyramid and
        context pass through unchanged — callers may donate ``coords1`` /
        ``hidden`` buffers).
        """
        coords1 = state["coords1"]
        b, h8, w8, _ = coords1.shape
        pyramid = jax.tree.map(
            lambda lvl: lvl.reshape(
                (lvl.shape[0] * lvl.shape[1],) + lvl.shape[2:]
            ),
            state["pyramid"],
        )
        body = partial(
            _refinement_step,
            coords0=coords_grid(b, h8, w8),
            context=state["context"],
            pyramid=pyramid,
            train=train,
            emit_all=False,
        )
        (coords1, hidden), _ = body(self, (coords1, state["hidden"]), None)
        return {
            "pyramid": state["pyramid"],
            "coords1": coords1,
            "hidden": hidden,
            "context": state["context"],
        }

    def finalize_flow(self, coords1, hidden, train: bool = False):
        """The final-upsample tail of :meth:`iterate`, standalone.

        Takes the recurrent carry of however many :meth:`iterate_step`
        calls a request actually ran (the pool's per-request iteration
        target, a deadline-driven early exit, or a degradation target) and
        produces the full-resolution flow — anytime semantics made a
        first-class entry point.
        """
        b, h8, w8, _ = coords1.shape
        coords0 = coords_grid(b, h8, w8)
        up_mask = None
        if self.mask_predictor is not None:
            up_mask = self.mask_predictor(hidden, train=train)
        return upsample_flow(coords1 - coords0, up_mask)
