#!/usr/bin/env python3
"""The seed study behind the train cell's limits, on the chip at the cell's
own size, one process. Per seed one JSON line with the raw readings (each
side's losses and per-leaf norms, so any statistic can be worked out again
without the chip) and, through the harness's own ``judge`` with the cell's
limits, what ``correct`` would have said of each side:

* ``program``   — the program's first steps against the plain reference at
  the precision the configuration states (pass side);
* ``control``   — on the first ``--controls`` seeds, the program with the
  path the configuration names as its control switched on
  (``precision.train.control``: bf16 convolutions, the nearest precision
  below the stated fp32 storage) in the program's place;
* ``half_batch`` — on the first ``--faults`` seeds, half of the batch left
  out (the mean taken over the rest), planted in the reference put in the
  program's place. A state left unchanged reads 1 by the measure and needs
  no run.

    python3 benchmarks/tools/train_study.py --workload raft_large.sintel_train --parked --seeds 12
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
    ap.add_argument("--parked", action="store_true",
                    help="the cell is under benchmarks/parked/, not in BENCHMARK.json")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed-of-seeds", type=int, default=20261003)
    ap.add_argument("--controls", type=int, default=4)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--precisions", default=None,
                    help="comma-separated reference precisions (default: the "
                         "configuration's own)")
    ap.add_argument("--control-precisions", default="",
                    help="further reference precisions, on the control's seeds only")
    ap.add_argument("--out", default=None, help="raw readings, one line a seed")
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal sizes")
    args = ap.parse_args()

    import numpy as np

    from benchmarks import loader, run as runmod
    from benchmarks.reference import compare as cmp
    from benchmarks.traffic import train

    root = loader.ROOT
    if args.parked:
        import tempfile

        root = loader.unpark(args.workload, tempfile.mkdtemp(prefix="unparked_"))
    cell = loader.load_cell(args.workload, root=root)
    config = cell["config"]
    prec = config["precision"]["train"]
    cell["warm_steps"] = 1
    if args.tiny:
        cell.update(image_hw=[140, 200], crop=[128, 160], iters=3,
                    dataset_size=4, schedule_steps=1000)
        cache_dir = None
    else:
        from raft_tpu.utils.runtime import enable_persistent_cache

        cache_dir = enable_persistent_cache(os.path.join(ROOT, ".bench_cache", "jax"))
    seeds = [int(s) for s in np.random.default_rng(args.seed_of_seeds).integers(
        1, 2**31 + 2**20, size=args.seeds)]
    precisions = (args.precisions.split(",") if args.precisions
                  else [prec["reference"]])
    out = open(args.out, "w") if args.out else None

    def verdict(stats):
        compared, ok = runmod.judge(
            {k: (stats[k], limit) for k, limit in cell["limits"].items()})
        return {"correct": ok, "failed": [k for k, c in compared.items() if not c["ok"]],
                **{k: v for k, v in stats.items() if not k.endswith("_leaf")}}

    for i, seed in enumerate(seeds):
        ctx = runmod.Context(cell, seed, 1.0, 0, os.path.join(ROOT, ".bench_cache"))
        ctx.jax_cache_dir = cache_dir
        sides = {}
        state = train.setup(ctx)
        train.release(ctx, state)
        sides["program"] = state["program"]
        if i < args.controls:
            ctl = train.setup(ctx, **prec["control"]["program_overrides"])
            train.release(ctx, ctl)
            sides["control"] = ctl["program"]
        raw = {"seed": seed, "sides": sides, "reference": {}, "half_batch": {}}
        extra = [p for p in args.control_precisions.split(",") if p and i < args.controls]
        for p in precisions + extra:
            kw = train.reference_kwargs(cell, p)
            reference = cmp.train_reference(config["arch"], state["host_vars"],
                                            state["batches"], **kw)
            raw["reference"][p] = reference
            row = {"seed": seed, "reference": p}
            for name, side in sides.items():
                row[name] = verdict(cmp.train_stats(side, reference))
            if i < args.faults and p == precisions[0]:
                half = [{k: v[: max(1, v.shape[0] // 2)] for k, v in b.items()}
                        for b in state["batches"]]
                faulty = cmp.train_reference(config["arch"], state["host_vars"],
                                             half, **kw)
                raw["half_batch"][p] = faulty
                row["half_batch"] = verdict(cmp.train_stats(faulty, reference))
            print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(raw) + "\n")
            out.flush()


if __name__ == "__main__":
    main()
