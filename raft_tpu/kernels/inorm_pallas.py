"""Pallas TPU kernel: streaming instance normalization (+ optional relu).

RAFT's feature encoder applies parameter-free InstanceNorm at up to
220x512 resolution (reference ``jax_raft/model.py:120-184``), five times
per image pair at full stem/stage1 resolution.

**Measured result: this kernel LOSES to XLA and is deliberately NOT wired
into the model.** Same-session interleaved A/B on the real chip at
(2, 220, 512, 64) fp32, 128 scan-chained iterations: XLA's fused
reduce+normalize 0.74 ms vs this kernel 1.75 ms. A copy-only Pallas kernel
with the identical grid already costs ~1.5-1.9 ms at this shape, i.e. the
Pallas DMA pipeline streams these 64-lane blocks at roughly half XLA's
fused-loop bandwidth, and folding W*C into full 128-lane rows does not
recover it. The round-1 motivation ("XLA runs the reduction ~20x over the
HBM floor") turned out to be a cross-session measurement artifact — per-call host
overhead varies enough between processes to fake a 2x gap on a sub-ms op;
only same-program, same-session comparisons are trustworthy (see
``docs/perf_notes.md``).

**That 0.74 ms was the norm alone, a program of its own; inside
``pool_begin_pair`` the same expression was not the same code.** There
the TPU compiler computes the two frames' 64-channel convs with the width
split into the batch, and the norm over ``(B, H, W, C)`` made it leave
that split and come back: per half-resolution norm four relayout copies
of the fp32 activation and two materialised broadcasts: 2.37 GB written
in a program of 17.5 ms a pair at 440x1024, 15.7 GB in one of 132.6 ms
at 1088x1920 (PERF.md, PR 31). Since PR 31 the feature encoder runs fewer
than 8 frames a device on a depth axis of batch-1 convs (``layers.frames_conv``)
and the statistics leave the conv's own fusion as ``(B, C)``: the program
takes 8.9 and 64.1 ms a pair, no kernel needed. A Pallas pass would read and write the unsplit layout,
i.e. bring those copies back.

Kept as a tested negative result: the two-phase streaming-stats pattern
(grid = (B, 2, H-tiles); TPU grids are sequential, so for each image every
phase-0 accumulate step runs before any phase-1 normalize step, with fp32
(1, C) sum / sum-of-squares scratch carried across steps) is the right
shape for a fused norm and documents what was tried.

Statistics use ``E[x^2] - E[x]^2`` in fp32 — the same ``use_fast_variance``
formula as ``nn.InstanceNorm`` (the parity oracle in tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["instance_norm_relu", "instance_norm_pallas"]


def _kernel(x_ref, o_ref, sum_ref, sq_ref, *, n: float, eps: float, relu: bool):
    ph = pl.program_id(1)
    hi = pl.program_id(2)

    @pl.when(ph == 0)
    def _accumulate():
        x = x_ref[0].astype(jnp.float32)  # (th, W, C)

        @pl.when(hi == 0)
        def _reset():
            sum_ref[...] = jnp.zeros_like(sum_ref)
            sq_ref[...] = jnp.zeros_like(sq_ref)

        sum_ref[...] += jnp.sum(x, axis=(0, 1))[None]
        sq_ref[...] += jnp.sum(x * x, axis=(0, 1))[None]

    @pl.when(ph == 1)
    def _normalize():
        x = x_ref[0].astype(jnp.float32)
        mean = sum_ref[...] * (1.0 / n)  # (1, C)
        var = sq_ref[...] * (1.0 / n) - mean * mean
        scale = jax.lax.rsqrt(var + eps)
        y = (x - mean[None]) * scale[None]
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0] = y.astype(o_ref.dtype)


def instance_norm_pallas(
    x: jax.Array,
    *,
    eps: float = 1e-5,
    relu: bool = False,
    row_tile: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """Parameter-free instance norm over the spatial dims of ``(B,H,W,C)``.

    Matches ``nn.InstanceNorm(epsilon=eps, use_bias=False, use_scale=False)``
    (fast-variance formula, fp32 statistics); optionally fuses the trailing
    relu of ``ConvNormAct``. Output dtype == input dtype.
    """
    b, h, w, c = x.shape
    th = h
    for d in range(min(row_tile, h), 0, -1):
        if h % d == 0:
            th = d
            break
    kernel = functools.partial(
        _kernel, n=float(h) * float(w), eps=eps, relu=relu
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(b, 2, h // th),
        in_specs=[
            pl.BlockSpec((1, th, w, c), lambda bi, ph, hi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, w, c), lambda bi, ph, hi: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(x)


def instance_norm_relu(x: jax.Array, *, eps: float = 1e-5, relu: bool = False):
    """Instance norm (+ optional relu) via the canonical jnp formula
    (``layers.instance_norm``) — on every backend. The Pallas kernel above
    measured 2.4x SLOWER than XLA's fused lowering of exactly this formula
    (module docstring), so nothing dispatches to it; it stays importable
    for its tests and any future re-measurement."""
    from raft_tpu.models.layers import instance_norm

    return instance_norm(x, eps=eps, relu=relu)
