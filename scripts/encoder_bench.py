#!/usr/bin/env python
"""Microbench: encoder internals on the real chip (scan chains, one fetch).

The r2 profile put the two encoders at ~33 ms/pair at 440x1024 — an order of
magnitude over the conv roofline (~150 GFLOP -> ~3 ms fp32). bf16 moved the
headline < 2%, so the time is NOT MXU passes. This script times the encoder
piecewise (conv1 / norm / res stages / full) to locate the hog.

Run: python scripts/encoder_bench.py [--dtype bfloat16]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

H, W = 440, 1024


def timed(fn, x, label, iters=64):
    @jax.jit
    def run(v):
        def body(c, _):
            out = fn(c)
            # feed a scalar back so iterations chain
            return c * (1.0 + 0.0 * out), out
        c, outs = jax.lax.scan(body, v, None, length=iters)
        return jnp.float32(outs[-1]) + jnp.float32(c.mean() * 0)

    np.asarray(run(x))
    t0 = time.perf_counter()
    np.asarray(run(x))
    dt = (time.perf_counter() - t0) / iters
    print(f"{label:>34}: {dt*1e3:8.3f} ms", flush=True)
    return dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else None

    import flax.linen as nn
    from raft_tpu.models.layers import ConvNormAct, ResidualBlock, conv
    from raft_tpu.models.encoders import FeatureEncoder

    k = jax.random.PRNGKey(0)
    # batch 2: the model concatenates both images through the feature encoder
    x = jax.random.uniform(k, (2, H, W, 3), jnp.float32, -1, 1)
    jax.block_until_ready(x)

    # full feature encoder
    enc = FeatureEncoder(
        block=ResidualBlock,
        widths=(64, 64, 96, 128, 256),
        norm="instance",
        dtype=dtype,
    )
    v = enc.init(k, x, train=False)
    timed(lambda a: jnp.float32(enc.apply(v, a, train=False).mean()), x,
          f"feature encoder b2 ({args.dtype})")

    # stage 0: 7x7/2 conv + instance norm + relu
    s0 = ConvNormAct(64, kernel=7, stride=2, norm="instance", dtype=dtype)
    v0 = s0.init(k, x, train=False)
    timed(lambda a: jnp.float32(s0.apply(v0, a, train=False).mean()), x,
          "conv7x7/2 + inorm + relu")

    # the same conv without norm
    c0 = conv(64, kernel=7, stride=2, dtype=dtype)
    vc = c0.init(k, x)
    timed(lambda a: jnp.float32(c0.apply(vc, a).mean()), x, "conv7x7/2 only")

    # instance norm alone at 220x512x64
    y = jax.random.uniform(k, (2, H // 2, W // 2, 64), jnp.float32)
    jax.block_until_ready(y)
    inorm = nn.InstanceNorm(epsilon=1e-5, use_bias=False, use_scale=False)
    vi = inorm.init(k, y)
    timed(lambda a: jnp.float32(inorm.apply(vi, a).mean()), y,
          "instance norm @220x512x64")

    # one residual block at 220x512x64 (layer1 has two of these, x2 images)
    rb = ResidualBlock(64, norm="instance", stride=1, dtype=dtype)
    vr = rb.init(k, y, train=False)
    timed(lambda a: jnp.float32(rb.apply(vr, a, train=False).mean()), y,
          "res block 64ch @220x512")

    # plain 3x3 conv 64->64 at 220x512
    c3 = conv(64, kernel=3, stride=1, dtype=dtype)
    v3 = c3.init(k, y)
    timed(lambda a: jnp.float32(c3.apply(v3, a).mean()), y,
          "conv3x3 64ch @220x512")


if __name__ == "__main__":
    main()
