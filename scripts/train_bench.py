#!/usr/bin/env python
"""Training hot-loop A/B bench: per-step dispatch vs fused window dispatch.

Measures what the fused multi-step window (``TrainConfig.window_size``,
``train.step.make_window_step``) actually buys: steps/s, device
**dispatches per step** (1/k with a window of k), and **host syncs per
step** — counted by ``utils.tripwire.HostSyncTripwire``, split into syncs
*inside* windows (must be 0: the hot loop never touches the device) and
syncs at log boundaries (one stacked metrics fetch per boundary). Emits
BENCH-style JSON lines (the repo's bench trajectory format):

    {"metric": "train_steps_per_s", "value": ..., "config": {...}}

The loop driven here is the trainer's hot path distilled — stage a batch
window through the pipeline's rotating host buffers, one async
``device_put``, one dispatch, metrics retained on device until the
boundary fetch — without the checkpoint/eval machinery, so the A/B
isolates dispatch+sync overhead (exactly what dominates once the step
itself is fast; ISSUE 5 / perf_notes training-throughput section).

`--mesh-devices N` (ISSUE 8) additionally runs every window size
through the mesh-sharded step (`parallel.make_sharded_window_step`,
batches sharded over an N-way `data` axis) and emits `train_mesh_ab`
BENCH lines — the 1-vs-N A/B for the executed sharded training lane.

Run (TPU, real model):      python scripts/train_bench.py --arch raft_small
Run (CPU smoke, tiny net):  python scripts/train_bench.py --tiny --steps 16
A/B (the window win):       python scripts/train_bench.py --tiny \\
                                --window-sizes 1,4 --steps 16

Without ``--tiny`` the bench refuses to run off a TPU (``require_tpu``):
a CPU run times the CPU backend, not the system. Every line it prints
carries the ``device`` (platform, kind, count) it ran on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def tiny_config():
    """A CPU-sized RAFT for smoke runs (mirrors the test suite's tiny cfg)."""
    from raft_tpu.models import RAFT_SMALL

    return RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,),
        motion_flow_widths=(16, 8),
        motion_out_channels=20,
        gru_hidden=24,
        flow_head_hidden=16,
        corr_levels=2,
    )


def make_batches(n, batch_size, hw, seed=0):
    rng = np.random.default_rng(seed)
    b, (h, w) = batch_size, hw
    return [
        {
            "image1": rng.uniform(-1, 1, (b, h, w, 3)).astype(np.float32),
            "image2": rng.uniform(-1, 1, (b, h, w, 3)).astype(np.float32),
            "flow": rng.uniform(-5, 5, (b, h, w, 2)).astype(np.float32),
            "valid": np.ones((b, h, w), np.float32),
        }
        for _ in range(n)
    ]


def bench_one(model, variables, args, window_size, mesh_n=1):
    """steps/s + syncs/dispatches per step for one window size.

    ``mesh_n > 1`` runs the SAME loop through the mesh-sharded step
    (``parallel.make_sharded_{train,window}_step``) with batches sharded
    over an ``mesh_n``-way ``data`` axis — the 1-vs-N A/B for the
    end-to-end sharded training lane (ISSUE 8)."""
    import jax

    from raft_tpu.data.pipeline import _WindowStaging
    from raft_tpu.train import TrainState, make_optimizer
    from raft_tpu.train.step import make_train_step, make_window_step
    from raft_tpu.utils.tripwire import HostSyncTripwire

    k = window_size
    steps = args.steps
    if steps % k:
        raise SystemExit(f"--steps {steps} is not a multiple of window {k}")
    tx = make_optimizer(1e-4, weight_decay=1e-5)
    state = TrainState.create(variables, tx)
    step_kw = dict(num_flow_updates=args.iters, numerics_policy="skip")
    mesh = None
    if mesh_n > 1:
        from raft_tpu.parallel import (
            make_mesh, make_sharded_train_step, make_sharded_window_step,
            shard_state,
        )

        mesh = make_mesh(data=mesh_n, space=1,
                         devices=jax.devices()[:mesh_n])
        state = shard_state(state, mesh)
        if k == 1:
            fn = make_sharded_train_step(model, tx, mesh, donate=False,
                                         **step_kw)
        else:
            fn = make_sharded_window_step(model, tx, mesh, window_size=k,
                                          donate=False, **step_kw)
    elif k == 1:
        fn = make_train_step(model, tx, donate=False, **step_kw)
    else:
        fn = make_window_step(
            model, tx, window_size=k, donate=False, **step_kw
        )
    batches = make_batches(steps, args.batch_size, (args.hw, args.hw))
    staging = _WindowStaging(slots=2)

    def put_window(i, *sharding):
        buf = staging.stack(batches[i: i + k])
        out = jax.device_put(buf, *sharding)
        staging.transferred(buf, out)  # zero-copy on CPU: retires the slot
        return out

    def feed(i):
        # the pipeline's staging path: per-step feeds one host batch (jit
        # transfers per leaf); windows stage k batches into a rotating
        # buffer and enqueue ONE async device_put of the tree
        if mesh is not None:
            from raft_tpu.parallel import shard_batch, window_batch_sharding

            if k == 1:
                return shard_batch(batches[i], mesh)
            return put_window(i, window_batch_sharding(mesh))
        if k == 1:
            return batches[i]
        return put_window(i)

    # warmup: compile + first transfer, outside the timed region
    w_state, w_metrics = fn(state, feed(0))
    jax.block_until_ready(w_state.params)

    dispatches = steps // k
    retained = []
    tw_window = {}
    t0 = time.perf_counter()
    with HostSyncTripwire() as tw:
        for d in range(dispatches):
            state, metrics = fn(state, feed(d * k))
            retained.append(metrics)  # stays on device until the boundary
        tw_window = tw.snapshot()  # syncs INSIDE the loop: must be {}
        # the log boundary: one fetch of everything the loop retained
        host = jax.device_get(retained)
        jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    boundary_syncs = tw.total - sum(tw_window.values())

    losses = (
        [float(m["loss"]) for m in host]
        if k == 1
        else [float(x) for m in host for x in np.asarray(m["loss"])]
    )
    return {
        "window_size": k,
        "mesh_devices": mesh_n,
        "steps": steps,
        "steps_per_s": steps / max(dt, 1e-9),
        "dispatches_per_step": dispatches / steps,
        "host_syncs_in_window": sum(tw_window.values()),
        "host_syncs_in_window_per_step": sum(tw_window.values()) / steps,
        "host_syncs_per_step": tw.total / steps,
        "boundary_syncs": boundary_syncs,
        "final_loss": losses[-1],
        "finite": bool(np.isfinite(losses).all()),
    }


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--tiny", action="store_true",
                   help="CPU-sized model + synthetic data (smoke/A-B)")
    p.add_argument("--arch", default="raft_small",
                   choices=["raft_small", "raft_large"])
    p.add_argument("--random-init", action="store_true")
    p.add_argument("--steps", type=int, default=None,
                   help="train steps per configuration (multiple of every "
                        "--window-sizes entry); default 32 tiny / 64 full")
    p.add_argument("--window-sizes", default="1,4",
                   help="comma list to A/B; 1 = per-step baseline")
    p.add_argument("--batch-size", type=int, default=None,
                   help="default 1 tiny / 2 full")
    p.add_argument("--hw", type=int, default=None,
                   help="square crop edge for the synthetic batches; "
                        "default 64 tiny / 128 full (the tiny default "
                        "keeps the per-step device time small so the "
                        "dispatch-overhead A/B is measurable on CPU)")
    p.add_argument("--iters", type=int, default=None,
                   help="flow updates per step (12 = the training recipe); "
                        "default 1 tiny / 12 full")
    p.add_argument("--mesh-devices", type=int, default=1,
                   help="also run every window size through the "
                        "mesh-sharded step over an N-way data axis "
                        "(1-vs-N A/B; batch size must divide by N). On "
                        "CPU, virtual devices are provisioned "
                        "automatically (ISSUE 8)")
    args = p.parse_args(argv)
    args.steps = args.steps or (32 if args.tiny else 64)
    args.batch_size = args.batch_size or (
        max(1, args.mesh_devices) if args.tiny else 2
    )
    args.hw = args.hw or (64 if args.tiny else 128)
    args.iters = args.iters or (1 if args.tiny else 12)
    if args.mesh_devices > 1 and args.batch_size % args.mesh_devices:
        raise SystemExit(
            f"--batch-size {args.batch_size} is not divisible by "
            f"--mesh-devices {args.mesh_devices}; the data axis shards "
            f"the batch dim evenly"
        )

    if args.tiny and not os.environ.get("JAX_PLATFORMS"):
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.mesh_devices > 1:
        # must precede the first jax import: the --tiny CPU smoke
        # provisions a virtual mesh (a TPU host exposes its own chips; a
        # non---tiny run without one refuses below)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags and args.tiny:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh_devices}"
            ).strip()
    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.utils.runtime import (
        device_info, enable_persistent_cache, require_tpu,
    )

    # --tiny is the CPU counts/correctness smoke; anything else is a
    # measurement and needs the chip
    if args.tiny:
        device = device_info()
    else:
        device = require_tpu("train_bench.py")
        enable_persistent_cache()

    def emit(line):
        print(json.dumps(dict(line, device=device)))

    if args.tiny:
        from raft_tpu.models.corr import CorrBlock

        model = build_raft(
            tiny_config(), corr_block=CorrBlock(num_levels=2, radius=3)
        )
        variables = init_variables(model)
    else:
        from raft_tpu.models import zoo

        model, variables = {
            "raft_small": zoo.raft_small,
            "raft_large": zoo.raft_large,
        }[args.arch](pretrained=not args.random_init)

    sizes = [int(x) for x in args.window_sizes.split(",")]
    results = [bench_one(model, variables, args, k) for k in sizes]
    if args.mesh_devices > 1:
        # the 1-vs-N A/B: the same window sizes through the sharded step
        results += [
            bench_one(model, variables, args, k, mesh_n=args.mesh_devices)
            for k in sizes
        ]

    base = next((r for r in results if r["window_size"] == 1
                 and r["mesh_devices"] == 1), results[0])
    report = {
        "window_sizes": sizes,
        "mesh_devices": args.mesh_devices,
        "steps": args.steps,
        "batch_size": args.batch_size,
        "results": results,
        "baseline_steps_per_s": base["steps_per_s"],
        "best_speedup": max(
            r["steps_per_s"] / base["steps_per_s"] for r in results
        ),
    }
    if args.mesh_devices > 1:
        for k in sizes:
            one = next(r for r in results
                       if r["window_size"] == k and r["mesh_devices"] == 1)
            n = next(r for r in results
                     if r["window_size"] == k
                     and r["mesh_devices"] == args.mesh_devices)
            emit({
                "metric": "train_mesh_ab",
                "window_size": k,
                "mesh_devices": args.mesh_devices,
                "steps_per_s_1dev": round(one["steps_per_s"], 3),
                "steps_per_s_mesh": round(n["steps_per_s"], 3),
                "speedup": round(
                    n["steps_per_s"] / max(one["steps_per_s"], 1e-9), 3
                ),
                "pairs_per_s_mesh": round(
                    n["steps_per_s"] * args.batch_size, 3
                ),
            })
    cfg = {"tiny": args.tiny, "batch_size": args.batch_size,
           "hw": args.hw, "iters": args.iters}
    for r in results:
        c = dict(cfg, window_size=r["window_size"],
                 mesh_devices=r["mesh_devices"])
        emit({"metric": "train_steps_per_s",
              "value": round(r["steps_per_s"], 3),
              "unit": "steps/s", "config": c})
        emit({"metric": "train_host_syncs_per_step",
              "value": round(r["host_syncs_in_window_per_step"], 5),
              "unit": "syncs/step (inside windows)",
              "config": c})
        emit({"metric": "train_dispatches_per_step",
              "value": round(r["dispatches_per_step"], 5),
              "unit": "dispatches/step", "config": c})
    emit({"metric": "train_bench_report", "value": report})
    return report


if __name__ == "__main__":
    main()
