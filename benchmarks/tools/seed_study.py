#!/usr/bin/env python3
"""The seed study behind a serve cell's limits: per seed, how far the
served flow lies from the plain fp32 reference (pass side), and how far
the control does — the reference computed in fp8, the nearest precision
below the bf16 the configurations state (fail side). One JSON line a seed.

On the chip (the cell's own size, through ``ServeEngine``, one process):

    python3 benchmarks/tools/seed_study.py --workload raft_small.sintel_offline --seeds 12

Rehearsal on the CPU (``--standin``): no engine; the program's place is
taken by its own dense model at the stated precision, at ``--hw``:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/seed_study.py --workload ... --standin --hw 128 256

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
    ap.add_argument("--seed-of-seeds", type=int, default=20261001)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--standin", action="store_true")
    ap.add_argument("--hw", type=int, nargs=2, default=None)
    ap.add_argument("--controls", nargs="*", default=None,
                    help="reference precisions put in the program's place "
                         "(default: the configuration's own control)")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import inputs, loader, run as runmod, weights
    from benchmarks.reference import compare as cmp, raft as ref

    cell = loader.load_cell(args.workload)
    config = cell["config"]
    if args.hw:
        cell["image_hw"] = [args.hw[0] - 4, args.hw[1]]
        cell["bucket"] = list(args.hw)
    cell["compare_pairs"] = args.pairs
    cell["limits"] = {}
    if args.controls is None:
        args.controls = [config["precision"]["serve"]["control"]]
    seeds = [int(s) for s in np.random.default_rng(args.seed_of_seeds).integers(
        1, 2**31 + 2**20, size=args.seeds)]
    if not args.standin:
        from raft_tpu.utils.runtime import enable_persistent_cache

        cache_root = os.path.join(ROOT, ".bench_cache")
        cache_dir = enable_persistent_cache(os.path.join(cache_root, "jax"))
        driver = loader.driver(cell["driver"])
    for seed in seeds:
        kw = dict(bucket=cell["bucket"], iters=cell["iters"])
        if args.standin:
            from raft_tpu.models import build_raft, zoo

            variables = weights.make_variables(
                ref.param_shapes(config["arch"]), seed,
                config["assumed"]["flow_head_scale"])
            prec = config["precision"]["serve"]
            model = build_raft(zoo.CONFIGS[config["program_arch"]].replace(
                compute_dtype=prec["compute_dtype"], corr_dtype=prec["corr_dtype"]))
            apply = jax.jit(lambda v, a, b: model.apply(
                v, a, b, train=False, num_flow_updates=cell["iters"], emit_all=False))
            pairs = inputs.serve_pairs(seed, args.pairs, cell["image_hw"])
            h, w = cell["image_hw"]
            served = [np.asarray(apply(variables, *(cmp.preprocess(im, cell["bucket"])
                                                    for im in p)))[0, :h, :w] for p in pairs]
            host_vars = variables
        else:
            ctx = runmod.Context(cell, seed, args.seconds, 0, cache_root)
            ctx.jax_cache_dir, ctx.peaks = cache_dir, None
            state = driver.setup(ctx)
            driver.window(ctx, state, args.seconds)
            driver.release(ctx, state)
            idx = sorted(state["served"])[: args.pairs]
            pairs = [state["pairs"][i] for i in idx]
            served = [state["served"][i] for i in idx]
            host_vars = state["host_vars"]
        rows = {"program": [], **{c: [] for c in args.controls}}
        for pair, flow in zip(pairs, served):
            want = cmp.reference_flow(config["arch"], host_vars, pair, **kw)
            rows["program"].append(cmp.flow_stats(flow, want))
            for c in args.controls:
                got = cmp.reference_flow(config["arch"], host_vars, pair,
                                         precision=c, **kw)
                rows[c].append(cmp.flow_stats(got, want))
        print(json.dumps({"seed": seed, "workload": args.workload,
                          "hw": cell["bucket"], "standin": args.standin,
                          **rows}), flush=True)


if __name__ == "__main__":
    main()
