#!/usr/bin/env python
"""Train RAFT on TPU (C -> T -> S/K/H schedule, one stage per invocation).

Examples:
    python scripts/train.py --stage chairs --data-root /data/FlyingChairs \\
        --checkpoint-dir ckpts/chairs
    python scripts/train.py --stage sintel --data-root /data \\
        --init-from ckpts/things/weights.msgpack --checkpoint-dir ckpts/sintel
"""

import argparse

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))



# RAFT-recipe sampling weights for the S/K/H fine-tune mix (integer repeats,
# matching the original RAFT `datasets.fetch_dataloader` 'C+T+K+S+H' stage):
# 100x Sintel-clean + 100x Sintel-final + 200x KITTI + 5x HD1K + 1x Things.
SKH_WEIGHTS = {"sintel_clean": 100, "sintel_final": 100, "kitti": 200, "hd1k": 5, "things": 1}


def _find_root(root, *names):
    import os

    for name in names:
        cand = os.path.join(root, name)
        if os.path.isdir(cand):
            return cand
    return None


def build_dataset(stage: str, root: str):
    from raft_tpu.data import (
        HD1K,
        ConcatDataset,
        FlyingChairs,
        FlyingThings3D,
        Kitti,
        RepeatDataset,
        Sintel,
    )

    if stage == "chairs":
        return FlyingChairs(root, split="train")
    if stage == "things":
        return FlyingThings3D(root)
    if stage == "kitti":
        return Kitti(root)
    if stage == "sintel":
        # The S/K/H mixed fine-tune. `root` is a directory containing the
        # per-dataset roots (Sintel/ required; FlyingThings3D/, KITTI/,
        # HD1K/ each join the mix when present, with the recipe weights).
        sintel_root = _find_root(root, "Sintel", "MPI-Sintel") or root
        parts = [
            RepeatDataset(Sintel(sintel_root, dstype="clean"), SKH_WEIGHTS["sintel_clean"]),
            RepeatDataset(Sintel(sintel_root, dstype="final"), SKH_WEIGHTS["sintel_final"]),
        ]
        things_root = _find_root(root, "FlyingThings3D", "flyingthings3d")
        if things_root:
            parts.append(FlyingThings3D(things_root, dstype="frames_cleanpass"))
        kitti_root = _find_root(root, "KITTI", "kitti", "KITTI-2015")
        if kitti_root:
            parts.append(RepeatDataset(Kitti(kitti_root), SKH_WEIGHTS["kitti"]))
        hd1k_root = _find_root(root, "HD1K", "hd1k")
        if hd1k_root:
            parts.append(RepeatDataset(HD1K(hd1k_root), SKH_WEIGHTS["hd1k"]))
        missing = [
            n for n, r in [("FlyingThings3D", things_root), ("KITTI", kitti_root), ("HD1K", hd1k_root)]
            if r is None
        ]
        if missing:
            print(f"S/K/H mix: {', '.join(missing)} not found under {root}; "
                  "training on the remaining datasets")
        return ConcatDataset(parts)
    raise ValueError(f"unknown stage {stage}")


def main():
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("--stage", required=True, choices=["chairs", "things", "sintel", "kitti"])
    p.add_argument("--data-root", required=True)
    p.add_argument("--arch", default="raft_large", choices=["raft_large", "raft_small"])
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--iters", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--log-dir", default=None,
                   help="write JSONL + TensorBoard scalars here")
    p.add_argument("--log-every", type=int, default=100)
    p.add_argument("--profile-port", type=int, default=None,
                   help="start jax.profiler server on this port")
    p.add_argument("--init-from", default=None, help=".msgpack weights to start from")
    p.add_argument("--corr-impl", default="dense", choices=["dense", "onthefly", "fused"])
    p.add_argument("--corr-dtype", default=None, choices=["bfloat16"],
                   help="bf16 correlation pyramid storage (+10%% measured "
                        "training throughput with --corr-impl fused; "
                        "since round 5 the fused kernel engages at ANY "
                        "crop width — 368x768 measured 17.3 vs 16.9 "
                        "pairs/s over the dense path, b=8 recommended "
                        "config)")
    p.add_argument("--compute-dtype", default=None, choices=["bfloat16"],
                   help="bf16 conv/activation compute (+15%% measured "
                        "training throughput — the backward's layout-copy "
                        "bucket halves; params/norm stats/flow/loss stay "
                        "fp32). Recommended single-chip training config: "
                        "--corr-impl fused --corr-dtype bfloat16 "
                        "--compute-dtype bfloat16 --remat --remat-policy "
                        "dots --batch-size 8 (17.3 pairs/s raft_large at "
                        "the 368x768 fine-tune crop)")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", default=None,
                   choices=["dots", "dots_no_batch", "corr"],
                   help="selective rematerialization under --remat: 'dots' "
                        "(save dot/matmul results — measured +34%% train "
                        "throughput on raft_large at the b=6 fine-tune "
                        "shape, recommended when it fits memory) / "
                        "'dots_no_batch' / 'corr' (save only the projected "
                        "correlation features)")
    p.add_argument("--window-size", type=int, default=1,
                   help="fuse this many train steps into one device "
                        "dispatch (lax.scan over a stacked batch window; "
                        "metrics accumulate on device and are fetched "
                        "once per log boundary). log/checkpoint/eval "
                        "intervals and --steps must be multiples of it; "
                        "1 = the per-step loop "
                        "(docs/perf_notes.md, training-throughput)")
    p.add_argument("--check-numerics", action="store_true",
                   help="per-step nonfinite-grad watchdog (raises with a "
                        "per-leaf report at the log boundary it trips)")
    p.add_argument("--export", default=None, help="write final weights msgpack here")
    p.add_argument("--eval-every", type=int, default=0,
                   help="run in-loop validation every N steps (logs eval/* "
                        "scalars, exports best-EPE weights to "
                        "<checkpoint-dir>/best.msgpack)")
    p.add_argument("--eval-root", default=None,
                   help="root of the held-out eval dataset (required with "
                        "--eval-every)")
    p.add_argument("--eval-dataset", default="sintel-clean",
                   choices=["sintel-clean", "sintel-final", "kitti"],
                   help="which held-out split --eval-root points at")
    p.add_argument("--eval-iters", type=int, default=32,
                   help="flow updates for in-loop eval (32 = the published "
                        "protocol)")
    p.add_argument("--data-fault-policy", default="skip",
                   choices=["skip", "raise"],
                   help="corrupt/unreadable samples: 'skip' quarantines "
                        "(bounded budget, transient retries with backoff) "
                        "and refills the batch; 'raise' fails fast "
                        "(docs/failure_model.md)")
    p.add_argument("--data-bad-sample-budget", type=int, default=64,
                   help="distinct quarantined samples allowed before the "
                        "run fails with BadSampleBudgetError")
    p.add_argument("--eval-fault-policy", default="skip",
                   choices=["skip", "raise"],
                   help="in-loop eval failures: 'skip' logs eval/failed "
                        "and keeps training; 'raise' kills the run")
    p.add_argument("--watchdog-timeout", type=float, default=None,
                   help="seconds a step/data-fetch/checkpoint wait may "
                        "block before all-thread stacks are dumped and "
                        "StallError raised (default: disabled)")
    p.add_argument("--numerics-policy", default="raise",
                   choices=["raise", "skip"],
                   help="model-level numeric faults: 'skip' arms the "
                        "on-device guard (a NaN-grad burst or grad-norm "
                        "spike skips that update — params/opt "
                        "state/batch_stats keep their old values — and "
                        "escalates to rollback-with-reseed past the skip "
                        "budget); 'raise' keeps the fail-fast "
                        "NumericsError behavior (docs/failure_model.md)")
    p.add_argument("--spike-factor", type=float, default=20.0,
                   help="skip updates whose grad global-norm exceeds this "
                        "multiple of the applied-step EMA (0 disables "
                        "spike detection; only with "
                        "--numerics-policy skip)")
    p.add_argument("--skip-budget", type=int, default=5,
                   help="skipped updates tolerated per log window before "
                        "rolling back to the last known-good checkpoint "
                        "with a perturbed data-order seed")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="divergence rollbacks before the run dies with "
                        "DivergenceError (full attempt trail in the "
                        "message)")
    p.add_argument("--rollback-lr-scale", type=float, default=1.0,
                   help="multiply the LR schedule by this per rollback "
                        "(e.g. 0.5 halves it; 1.0 keeps the schedule)")
    args = p.parse_args()
    if args.remat_policy and not args.remat:
        p.error("--remat-policy requires --remat")

    from raft_tpu.train.trainer import STAGES, TrainConfig, Trainer

    stage = STAGES[args.stage]
    config = TrainConfig(
        arch=args.arch,
        stage=args.stage,
        num_steps=args.steps or stage["num_steps"],
        global_batch_size=args.batch_size or stage["global_batch_size"],
        learning_rate=args.lr or stage["learning_rate"],
        num_flow_updates=args.iters or stage["num_flow_updates"],
        crop_size=stage["crop_size"],
        seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        log_dir=args.log_dir,
        log_every=args.log_every,
        profile_port=args.profile_port,
        corr_impl=args.corr_impl,
        corr_dtype=args.corr_dtype,
        compute_dtype=args.compute_dtype,
        remat=args.remat,
        remat_policy=args.remat_policy,
        window_size=args.window_size,
        check_numerics=args.check_numerics,
        eval_every=args.eval_every,
        eval_num_flow_updates=args.eval_iters,
        data_fault_policy=args.data_fault_policy,
        data_bad_sample_budget=args.data_bad_sample_budget,
        eval_fault_policy=args.eval_fault_policy,
        watchdog_timeout=args.watchdog_timeout,
        numerics_policy=args.numerics_policy,
        spike_factor=args.spike_factor,
        skip_budget=args.skip_budget,
        max_rollbacks=args.max_rollbacks,
        rollback_lr_scale=args.rollback_lr_scale,
    )

    eval_dataset = None
    if args.eval_every:
        if not args.eval_root:
            p.error("--eval-every requires --eval-root")
        from raft_tpu.data import Kitti, Sintel

        if args.eval_dataset == "kitti":
            eval_dataset = Kitti(args.eval_root)
        else:
            eval_dataset = Sintel(
                args.eval_root,
                split="training",
                dstype=args.eval_dataset.split("-")[1],
            )

    dataset = build_dataset(args.stage, args.data_root)
    if len(dataset) == 0:
        p.error(
            f"no samples found for stage {args.stage!r} under "
            f"{args.data_root!r} — check the layout (e.g. FlyingChairs "
            "expects <root>/data/NNNNN_{img1,img2}.ppm + _flow.flo)"
        )
    print(f"stage={args.stage} dataset={len(dataset)} pairs, {config}")

    # persistent compile cache (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache): a restarted run skips the multi-minute
    # train-step compile. Before anything compiles: JAX
    # decides once, at the first compile, whether the cache is in use
    from raft_tpu.utils.runtime import enable_persistent_cache

    enable_persistent_cache()
    init_from = None
    if args.init_from:
        from raft_tpu.checkpoint import load_variables
        from raft_tpu.models.zoo import CONFIGS, build_raft, init_variables

        template_model = build_raft(CONFIGS[args.arch])
        init_from = load_variables(init_variables(template_model), args.init_from)

    trainer = Trainer(config, dataset, init_from=init_from,
                      eval_dataset=eval_dataset)
    state = trainer.run()

    if args.export:
        import jax

        from raft_tpu.checkpoint import save_variables

        save_variables(jax.device_get(state.variables()), args.export)
        print(f"wrote {args.export}")


if __name__ == "__main__":
    main()
