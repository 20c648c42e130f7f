"""Model zoo: named RAFT configurations, assembly, and pretrained weights.

Two-level configuration scheme (kept from the reference, SURVEY.md §5.6):
a flat dataclass of hyperparameters per named config, plus component
injection — any of the five components can be passed pre-built to
``build_raft`` for research use. Hyperparameter values reproduce
torchvision's raft_large / raft_small (reference
``jax_raft/model.py:694-767``).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

from raft_tpu.models.corr import CorrBlock
from raft_tpu.models.encoders import FeatureEncoder
from raft_tpu.models.layers import BottleneckBlock, ResidualBlock
from raft_tpu.models.raft import RAFT
from raft_tpu.models.update import (
    FlowHead,
    MaskPredictor,
    MotionEncoder,
    RecurrentBlock,
    UpdateBlock,
)

__all__ = ["RAFTConfig", "RAFT_LARGE", "RAFT_SMALL", "build_raft", "init_variables", "raft_large", "raft_small", "raft_for_serving"]

_BASE_URL = "https://github.com/alebeck/jax-raft/releases/download/checkpoints/"
PRETRAINED_URLS = {
    "raft_large": _BASE_URL + "raft_large_C_T_SKHT_V2-ff5fadd5.msgpack",
    "raft_small": _BASE_URL + "raft_small_C_T_V2-01064c6d.msgpack",
}

_BLOCKS = {"residual": ResidualBlock, "bottleneck": BottleneckBlock}

# Pretrained-fetch retry knobs (module-level so tests can shrink the
# backoff): 3 attempts, capped exponential backoff with jitter.
_FETCH_ATTEMPTS = 3
_FETCH_BASE_DELAY = 0.5


@dataclasses.dataclass(frozen=True)
class RAFTConfig:
    """Flat hyperparameter set fully describing a RAFT variant."""

    name: str
    # Encoders
    feature_encoder_widths: Tuple[int, int, int, int, int]
    feature_encoder_block: str  # 'residual' | 'bottleneck'
    feature_encoder_norm: Optional[str]  # 'batch' | 'instance' | None
    context_encoder_widths: Tuple[int, int, int, int, int]
    context_encoder_block: str
    context_encoder_norm: Optional[str]
    # Correlation
    corr_levels: int
    corr_radius: int
    # Motion encoder
    motion_corr_widths: Tuple[int, ...]
    motion_flow_widths: Tuple[int, int]
    motion_out_channels: int
    # Recurrent block
    gru_hidden: int
    gru_kernels: Tuple[Tuple[int, int], ...]
    gru_pads: Tuple[Tuple[int, int], ...]
    # Flow head
    flow_head_hidden: int
    # Mask predictor
    use_mask_predictor: bool
    mask_predictor_hidden: int = 256
    # Three correlation engines, each parameter-free (the checkpoint tree
    # never depends on this):
    #   'dense'    the pooled volume pyramid and the separable XLA lookup
    #              (models/corr.py): reference semantics, the tests'
    #              oracle, the fused block's backward pass, the CPU;
    #   'fused'    the same pyramid, looked up and projected by the Pallas
    #              kernel (kernels/lookup_xtap.py): what every benchmark
    #              cell runs on the chip;
    #   'onthefly' no volume at all, correlation rows recomputed a query
    #              chunk at a time (models/corr_otf.py): what fits where a
    #              volume does not (46% of fused's rate in 23% of the
    #              memory at 1088x1920; builder's chip run, PR 30).
    corr_impl: str = "dense"
    # Computation dtype for the conv stacks ('float32' | 'bfloat16').
    # Parameters, norm statistics, correlation accumulation, flow/coordinate
    # arithmetic, and the convex-upsample softmax always stay fp32, so the
    # checkpoint tree and EPE-critical paths are unaffected. The serving
    # presets differ in it ('quality' fp32, 'throughput' bf16, which the
    # cells run); the ledger has no cell at fp32 convs to compare.
    compute_dtype: str = "float32"
    # Storage dtype for the correlation pyramid + lookup intermediates
    # ('float32' | 'bfloat16'), independently of the conv compute dtype
    # (None = follow compute_dtype). The pooled volume is the largest
    # array a pair holds and every flow update reads it; 'bfloat16' halves
    # it while the volume matmul still accumulates fp32.
    corr_dtype: Optional[str] = None
    # TPU options (no effect on the parameter tree)
    remat: bool = False
    # Selective-remat policy for the scan body (None = recompute everything;
    # 'dots' | 'dots_no_batch' | 'corr' — see models.raft.REMAT_POLICIES)
    remat_policy: Optional[str] = None
    axis_name: Optional[str] = None
    # Compute the encoders' 7x7/2 RGB stems via 2x2 space-to-depth (same
    # parameters and sums, MXU-shaped contraction; layers._S2DConv7x2)
    s2d_stem: bool = False

    def replace(self, **kw) -> "RAFTConfig":
        return dataclasses.replace(self, **kw)


RAFT_LARGE = RAFTConfig(
    name="raft_large",
    feature_encoder_widths=(64, 64, 96, 128, 256),
    feature_encoder_block="residual",
    feature_encoder_norm="instance",
    context_encoder_widths=(64, 64, 96, 128, 256),
    context_encoder_block="residual",
    context_encoder_norm="batch",
    corr_levels=4,
    corr_radius=4,
    motion_corr_widths=(256, 192),
    motion_flow_widths=(128, 64),
    motion_out_channels=128,
    gru_hidden=128,
    gru_kernels=((1, 5), (5, 1)),
    gru_pads=((0, 2), (2, 0)),
    flow_head_hidden=256,
    use_mask_predictor=True,
)

RAFT_SMALL = RAFTConfig(
    name="raft_small",
    feature_encoder_widths=(32, 32, 64, 96, 128),
    feature_encoder_block="bottleneck",
    feature_encoder_norm="instance",
    context_encoder_widths=(32, 32, 64, 96, 160),
    context_encoder_block="bottleneck",
    context_encoder_norm=None,
    corr_levels=4,
    corr_radius=3,
    motion_corr_widths=(96,),
    motion_flow_widths=(64, 32),
    motion_out_channels=82,
    gru_hidden=96,
    gru_kernels=((3, 3),),
    gru_pads=((1, 1),),
    flow_head_hidden=128,
    use_mask_predictor=False,
)

CONFIGS = {"raft_large": RAFT_LARGE, "raft_small": RAFT_SMALL}


def build_raft(
    config: RAFTConfig,
    *,
    feature_encoder: Optional[Any] = None,
    context_encoder: Optional[Any] = None,
    corr_block: Optional[Any] = None,
    update_block: Optional[Any] = None,
    mask_predictor: Optional[Any] = None,
) -> RAFT:
    """Assemble a RAFT module from a config, with per-component injection."""
    dtype = _DTYPES[config.compute_dtype]
    if dtype == jnp.float32:
        dtype = None  # Flax default: no casting at all
    corr_dtype = (
        _DTYPES[config.corr_dtype] if config.corr_dtype is not None else dtype
    )
    if corr_dtype == jnp.float32:
        corr_dtype = None
    if feature_encoder is None:
        feature_encoder = FeatureEncoder(
            block=_BLOCKS[config.feature_encoder_block],
            widths=config.feature_encoder_widths,
            norm=config.feature_encoder_norm,
            axis_name=config.axis_name,
            dtype=dtype,
            s2d_stem=config.s2d_stem,
        )
    if context_encoder is None:
        context_encoder = FeatureEncoder(
            block=_BLOCKS[config.context_encoder_block],
            widths=config.context_encoder_widths,
            norm=config.context_encoder_norm,
            axis_name=config.axis_name,
            dtype=dtype,
            s2d_stem=config.s2d_stem,
        )
    if corr_block is None:
        if config.corr_impl == "onthefly":
            from raft_tpu.models.corr_otf import OnTheFlyCorrBlock

            corr_block = OnTheFlyCorrBlock(
                num_levels=config.corr_levels, radius=config.corr_radius
            )
        elif config.corr_impl == "fused":
            from raft_tpu.kernels import FusedLookupCorrBlock

            corr_block = FusedLookupCorrBlock(
                num_levels=config.corr_levels,
                radius=config.corr_radius,
                dtype=corr_dtype,
            )
        elif config.corr_impl == "dense":
            corr_block = CorrBlock(
                num_levels=config.corr_levels,
                radius=config.corr_radius,
                dtype=corr_dtype,
            )
        else:
            raise ValueError(
                f"unknown corr_impl {config.corr_impl!r}: it is one of "
                f"'dense', 'fused', 'onthefly'"
            )
    if update_block is None:
        update_block = UpdateBlock(
            motion_encoder=MotionEncoder(
                corr_widths=config.motion_corr_widths,
                flow_widths=config.motion_flow_widths,
                out_channels=config.motion_out_channels,
                dtype=dtype,
            ),
            recurrent_block=RecurrentBlock(
                hidden=config.gru_hidden,
                kernels=config.gru_kernels,
                pads=config.gru_pads,
                dtype=dtype,
            ),
            flow_head=FlowHead(hidden=config.flow_head_hidden, dtype=dtype),
        )
    if mask_predictor is None and config.use_mask_predictor:
        mask_predictor = MaskPredictor(
            hidden=config.mask_predictor_hidden, dtype=dtype
        )

    return RAFT(
        feature_encoder=feature_encoder,
        context_encoder=context_encoder,
        corr_block=corr_block,
        update_block=update_block,
        mask_predictor=mask_predictor,
        remat=config.remat,
        remat_policy=config.remat_policy,
    )


def init_variables(
    model: RAFT, rng: Optional[jax.Array] = None, image_size: Optional[int] = None
):
    """Initialize a variable tree (``params`` [+ ``batch_stats``]).

    Uses the minimum legal input for the model's correlation pyramid (128 px
    for 4 levels; reference ``jax_raft/model.py:681-682``) and a single
    refinement step — the scan broadcasts parameters, so the tree is
    independent of ``num_flow_updates``.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if image_size is None:
        min_fmap = getattr(model.corr_block, "min_fmap_size", lambda: 16)()
        image_size = 8 * min_fmap
    sample = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    return model.init(rng, sample, sample, train=True, num_flow_updates=1)


def _check_digest(path: str, name: Optional[str] = None) -> None:
    """Verify the sha256 prefix embedded in ``name-XXXXXXXX.msgpack``.

    Catches truncated downloads and stale/corrupt cache files with an
    actionable error instead of a cryptic msgpack failure downstream.
    ``name`` overrides the digest-carrying filename when ``path`` is a
    temp file (the atomic-download staging name has a ``.tmp.PID``
    suffix the digest pattern would never match).
    """
    import hashlib
    import re

    m = re.search(
        r"-([0-9a-f]{8})\.msgpack$", name or os.path.basename(path)
    )
    if not m:
        return  # user-supplied file without an embedded digest
    with open(path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    if not digest.startswith(m.group(1)):
        # The upstream release may have named the msgpack after the source
        # .pth's hash, so a mismatch is suspicious but not proof of
        # corruption — warn with the actionable remedy instead of failing.
        import warnings

        warnings.warn(
            f"{path}: sha256 {digest[:8]} does not match the filename digest "
            f"{m.group(1)}; if loading fails, delete this file and retry"
        )


def _load_pretrained(variables, arch: str, checkpoint: Optional[str]):
    """Restore pretrained weights from a local path, cache, or release URL."""
    from flax.serialization import from_bytes

    if checkpoint is None:
        url = PRETRAINED_URLS[arch]
        cache_dir = os.environ.get(
            "RAFT_TPU_CACHE", os.path.expanduser("~/.cache/raft_tpu")
        )
        cached = os.path.join(cache_dir, os.path.basename(url))
        if os.path.exists(cached):
            _check_digest(cached)
            checkpoint = cached
        else:
            import urllib.request

            os.makedirs(cache_dir, exist_ok=True)

            def _fetch() -> bytes:
                with urllib.request.urlopen(url, timeout=30) as resp:
                    return resp.read()

            from raft_tpu.utils.faults import retry_transient

            try:
                # Transient network flakes (URLError/TimeoutError are
                # OSError subclasses, as are 5xx HTTPErrors via URLError)
                # get capped exponential backoff with jitter before the
                # actionable failure below.
                data = retry_transient(
                    _fetch,
                    attempts=_FETCH_ATTEMPTS,
                    base_delay=_FETCH_BASE_DELAY,
                    max_delay=4.0,
                    transient=(OSError, TimeoutError),
                    on_retry=lambda i, e: print(
                        f"pretrained fetch attempt {i + 1} failed "
                        f"({type(e).__name__}: {e}); retrying"
                    ),
                )
            except Exception as e:
                raise RuntimeError(
                    f"could not download pretrained weights from {url}; "
                    f"place the msgpack file at {cached} or pass checkpoint="
                ) from e
            # Atomic publish: an interrupted/racing download must never leave
            # a truncated file at the final cache path.
            tmp = cached + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(data)
            _check_digest(tmp, name=os.path.basename(cached))
            os.replace(tmp, cached)
            checkpoint = cached
    with open(checkpoint, "rb") as f:
        return from_bytes(variables, f.read())


def _make(arch: str, pretrained: bool, checkpoint: Optional[str], **overrides):
    config = CONFIGS[arch]
    cfg_fields = {f.name for f in dataclasses.fields(RAFTConfig)}
    cfg_kw = {k: overrides.pop(k) for k in list(overrides) if k in cfg_fields}
    if cfg_kw:
        config = config.replace(**cfg_kw)
    model = build_raft(config, **overrides)
    variables = init_variables(model)
    if pretrained or checkpoint is not None:
        variables = _load_pretrained(variables, arch, checkpoint)
    return model, variables


def raft_large(*, pretrained: bool = False, checkpoint: Optional[str] = None, **overrides):
    """RAFT large: (model, variables). API-compatible with the reference."""
    return _make("raft_large", pretrained, checkpoint, **overrides)


def raft_small(*, pretrained: bool = False, checkpoint: Optional[str] = None, **overrides):
    """RAFT small: (model, variables). API-compatible with the reference."""
    return _make("raft_small", pretrained, checkpoint, **overrides)


def raft_for_serving(
    serve_config,
    *,
    arch: str = "raft_large",
    pretrained: bool = False,
    checkpoint: Optional[str] = None,
    **overrides,
):
    """Build (model, variables) matching a serving config's precision.

    The deployment glue between :meth:`raft_tpu.serve.ServeConfig.preset`
    and the model zoo: the config's ``compute_dtype`` / ``corr_dtype`` /
    ``corr_impl`` fields become :class:`RAFTConfig` overrides (precision
    knobs change activation/storage casts only, never the parameter
    tree — pretrained fp32 checkpoints load unchanged), so the engine,
    its iteration pool, and the warmup-artifact fingerprint all see one
    consistent precision::

        cfg = ServeConfig.preset("throughput", warmup=True)
        model, variables = raft_for_serving(cfg, pretrained=True)
        engine = ServeEngine(model, variables, cfg)

    Explicit ``**overrides`` win over the config's precision fields.
    """
    if arch not in CONFIGS:
        raise ValueError(f"unknown arch {arch!r}; choose from {sorted(CONFIGS)}")
    kw = dict(serve_config.model_overrides())
    kw.update(overrides)
    return _make(arch, pretrained, checkpoint, **kw)
