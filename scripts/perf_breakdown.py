#!/usr/bin/env python
"""Attribute per-pair time among RAFT stages on the real chip.

Strategy (one chain, one fetch, like bench.py): each measurement chains N pairs
through one compiled scan and fetches one scalar. Components are isolated by
benching nested prefixes of the pipeline, so stage cost = difference of
successive prefixes:

  encoders            = A
  + corr pyramid      = B  -> pyramid  = B - A
  + K x lookup        = C  -> lookup   = (C - B) / K per iteration
  + K x update block  = D  -> update   = (D - C) / K
  + K x upsample      = E  -> upsample = (E - D) / K   [full model]

Run: python scripts/perf_breakdown.py [--arch raft_large] [--iters 32]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

H, W = 440, 1024


def timed(fn, pairs, n_pairs):
    @jax.jit
    def run(ps):
        def body(carry, pair):
            out = fn(pair)
            return carry + out, 0.0

        total, _ = jax.lax.scan(body, jnp.float32(0), ps)
        return total

    np.asarray(run(pairs))  # compile + warm
    t0 = time.perf_counter()
    np.asarray(run(pairs))
    return (time.perf_counter() - t0) / n_pairs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="raft_large")
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--pairs", type=int, default=8)
    ap.add_argument("--dtype", default=None)
    args = ap.parse_args()

    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.models.zoo import CONFIGS
    from raft_tpu.ops import coords_grid as make_coords_grid

    cfg = CONFIGS[args.arch]
    if args.dtype:
        cfg = cfg.replace(compute_dtype=args.dtype)
    model = build_raft(cfg)
    variables = init_variables(model)
    K = args.iters

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    pairs = (
        jax.random.uniform(k1, (args.pairs, H, W, 3), jnp.float32, -1, 1),
        jax.random.uniform(k2, (args.pairs, H, W, 3), jnp.float32, -1, 1),
    )
    jax.block_until_ready(pairs)

    # Stage closures drive the production submodules directly (their params
    # live under the same names in the variable tree).
    params = variables["params"]
    stats = variables.get("batch_stats", {})

    def sub_vars(name):
        v = {"params": params[name]}
        if name in stats:
            v["batch_stats"] = stats[name]
        return v

    def encode(im1, im2):
        fmaps = model.feature_encoder.apply(
            sub_vars("feature_encoder"),
            jnp.concatenate([im1, im2], axis=0),
            train=False,
        )
        fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        ctx = model.context_encoder.apply(
            sub_vars("context_encoder"), im1, train=False
        )
        hs = model.update_block.hidden_state_size
        hidden, context = jnp.tanh(ctx[..., :hs]), jax.nn.relu(ctx[..., hs:])
        return fmap1, fmap2, hidden, context

    def encoders_only(pair):
        im1, im2 = pair
        fmap1, fmap2, hidden, context = encode(im1[None], im2[None])
        return fmap1.mean() + fmap2.mean() + hidden.mean() + context.mean()

    def plus_pyramid(pair):
        im1, im2 = pair
        fmap1, fmap2, hidden, context = encode(im1[None], im2[None])
        pyramid = model.corr_block.build_pyramid(fmap1, fmap2)
        return sum(p.mean() for p in pyramid) + hidden.mean()

    def plus_lookup(pair):
        im1, im2 = pair
        fmap1, fmap2, hidden, context = encode(im1[None], im2[None])
        pyramid = model.corr_block.build_pyramid(fmap1, fmap2)
        b, h, w, _ = fmap1.shape
        coords = make_coords_grid(b, h, w)

        def it(carry, _):
            c = carry
            feats = model.corr_block.index_pyramid(pyramid, c)
            # feed the output back so iterations can't be collapsed
            c = c + feats.mean(axis=-1, keepdims=True)[..., :2] * 1e-6
            return c, 0.0

        c, _ = jax.lax.scan(it, coords, None, length=K)
        return c.mean() + hidden.mean()

    def plus_update(pair):
        im1, im2 = pair
        fmap1, fmap2, hidden, context = encode(im1[None], im2[None])
        pyramid = model.corr_block.build_pyramid(fmap1, fmap2)
        b, h, w, _ = fmap1.shape
        coords0 = make_coords_grid(b, h, w)

        def it(carry, _):
            c, hid = carry
            feats = model.corr_block.index_pyramid(pyramid, c)
            hid, delta = model.update_block.apply(
                sub_vars("update_block"), hid, context, feats, c - coords0,
                train=False,
            )
            return (c + delta, hid), 0.0

        (c, hid), _ = jax.lax.scan(it, (coords0, hidden), None, length=K)
        return c.mean() + hid.mean()

    def full_model(pair):
        im1, im2 = pair
        flow = model.apply(
            variables,
            im1[None],
            im2[None],
            train=False,
            num_flow_updates=K,
            emit_all=False,
        )
        return flow.mean()

    rows = {}
    rows["encoders"] = timed(encoders_only, pairs, args.pairs)
    rows["+pyramid"] = timed(plus_pyramid, pairs, args.pairs)
    rows[f"+{K}x lookup"] = timed(plus_lookup, pairs, args.pairs)
    rows[f"+{K}x update"] = timed(plus_update, pairs, args.pairs)
    rows["full model"] = timed(full_model, pairs, args.pairs)

    print(f"\n== {args.arch} {H}x{W} {K} iters (ms/pair) ==")
    prev = 0.0
    for name, t in rows.items():
        print(f"{name:>14}: {t*1e3:8.2f} total  (+{(t-prev)*1e3:7.2f})")
        prev = t
    lookup = (rows[f"+{K}x lookup"] - rows["+pyramid"]) / K
    update = (rows[f"+{K}x update"] - rows[f"+{K}x lookup"]) / K
    tail = rows["full model"] - rows[f"+{K}x update"]
    print(f"\nper-iteration: lookup {lookup*1e3:.3f} ms, update {update*1e3:.3f} ms; "
          f"final mask+upsample {tail*1e3:.2f} ms")
    print(json.dumps({k: round(v * 1e3, 3) for k, v in rows.items()}))


if __name__ == "__main__":
    main()
