#!/usr/bin/env python3
"""The seed study behind a stream cell's limits: per seed, how far the
served pairs of sampled sessions lie from upstream's loop in the plain
fp32 reference, a link at a time (pass side), and how far the control does
— the same link, from the same start, computed in fp8, the nearest
precision below the bf16 the configuration states, in the program's place
(fail side). ``links`` names each compared pair: its index in its session
and whether it was warm-started. One JSON line a seed. ``seed_study.py``
reads pairs off ``serve_closed``'s state; a stream driver keeps sessions.

On the chip (the cell's own size, through ``ServeEngine``, one process):

    python3 benchmarks/tools/seed_study_video.py --workload raft_large_video.sintel_streams --seeds 12

Rehearsal on the CPU, the engine itself at a small size:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/seed_study_video.py --workload ... --tiny --seeds 1

Seeds are drawn as the check draws them: large whole numbers.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed-of-seeds", type=int, default=20261005)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--sessions", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmarks import inputs, loader, run as runmod
    from benchmarks.traffic import serve_streams as driver
    from raft_tpu.utils.runtime import enable_persistent_cache

    cell = loader.load_cell(args.workload)
    config = cell["config"]
    if args.tiny:
        cell.update(image_hw=[120, 152], bucket=[128, 160], iters=4, streams=3,
                    distinct_clips=2, clip_frames=[5, 6], ramp_s=0.5)
        cell["serve"] = dict(cell["serve"], pool_capacity=4, max_batch=2,
                             ladder=[4, 3, 2], stream_cache_size=4)
    cell["compare_sessions"] = args.sessions
    cell["limits"] = {}
    prec = config["precision"]["serve"]
    seeds = [int(s) for s in np.random.default_rng(args.seed_of_seeds).integers(
        1, 2**31 + 2**20, size=args.seeds)]
    cache_root = os.path.join(ROOT, ".bench_cache")
    cache_dir = enable_persistent_cache(os.path.join(cache_root, "jax"))
    for seed in seeds:
        ctx = runmod.Context(cell, seed, args.seconds, 0, cache_root)
        ctx.jax_cache_dir, ctx.peaks = cache_dir, None
        state = driver.setup(ctx)
        win = driver.window(ctx, state, args.seconds)
        driver.release(ctx, state)
        sample = driver.sample_sessions(
            state["sessions"], win["t0"], win["t1"], args.sessions,
            cell["compare_pairs"], inputs.seeded_rng(seed, 5))
        kw = dict(bucket=cell["bucket"], iters=cell["iters"],
                  warm_start=cell["serve"]["stream_warm_start"])
        program, ctrl = [], []
        for s, pairs in sample:
            link = lambda p, prec: driver.reference_link(
                config["arch"], state["host_vars"], state["clips"][s.clip], p,
                precision=prec, **kw)
            want = [link(p, prec["reference"]) for p in pairs]
            program.append([driver.cmp.flow_stats(p[2], w)
                            for p, w in zip(pairs, want)])
            # the control in the program's place, from the same start
            ctrl.append([driver.cmp.flow_stats(link(p, prec["control"]), w)
                         for p, w in zip(pairs, want)])
        print(json.dumps({
            "seed": seed, "workload": args.workload, "hw": cell["bucket"],
            "pairs_per_s": win["metrics"]["serve_pairs_per_s"],
            "failed": win["failed"],
            "links": [[(p[0] - s.start, p[3] is not None) for p in pairs]
                      for s, pairs in sample],
            "program": program, prec["control"]: ctrl}), flush=True)


if __name__ == "__main__":
    main()
