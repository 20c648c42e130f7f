#!/usr/bin/env python
"""Validate RAFT on any supported dataset (the C->T->S/K/H stages each get
an acceptance check matching their training data).

Generalizes the reference's Sintel-only protocol
(``scripts/validate_sintel.py:164-206`` there) to KITTI-2015 (sparse GT:
masked EPE + Fl-all outlier rate, bottom-only padding), FlyingThings3D and
FlyingChairs (dense GT, bottom-only padding). ``scripts/validate_sintel.py``
remains the headline two-pass Sintel entry point.

Usage:
    python scripts/validate.py DATA_ROOT --dataset kitti
    python scripts/validate.py DATA_ROOT --dataset things --split TEST
    python scripts/validate.py DATA_ROOT --dataset sintel --dstype final
"""

import argparse

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


def build_dataset(args):
    from raft_tpu.data import FlyingChairs, FlyingThings3D, Kitti, Sintel

    if args.dataset == "sintel":
        return Sintel(args.root, split=args.split or "training", dstype=args.dstype)
    if args.dataset == "kitti":
        return Kitti(args.root, split=args.split or "training")
    if args.dataset == "things":
        return FlyingThings3D(
            args.root, split=args.split or "TEST", dstype=f"frames_{args.dstype}pass"
        )
    if args.dataset == "chairs":
        return FlyingChairs(args.root, split=args.split or "val")
    raise ValueError(args.dataset)


def main():
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("root", help="dataset root directory")
    p.add_argument("--dataset", default="sintel",
                   choices=["sintel", "kitti", "things", "chairs"])
    p.add_argument("--split", default=None,
                   help="dataset split (defaults: sintel/kitti 'training', "
                        "things 'TEST', chairs 'val')")
    p.add_argument("--dstype", default="clean", choices=["clean", "final"],
                   help="render pass (sintel/things)")
    p.add_argument("--arch", default="raft_large",
                   choices=["raft_small", "raft_large"])
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--pretrained", action="store_true", default=None)
    p.add_argument("--random-init", action="store_true",
                   help="random weights (layout/protocol smoke runs only — "
                        "metrics are meaningless)")
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--fps-pairs", type=int, default=64)
    p.add_argument("--corr-impl", default=None,
                   choices=["dense", "onthefly", "fused"],
                   help="correlation implementation (default: library "
                        "dense; 'fused' engages the Pallas deployment "
                        "kernel — since round 5 at ANY geometry incl. "
                        "KITTI's 1242-wide frames, measured 2.3x the "
                        "dense path there)")
    p.add_argument("--corr-dtype", default=None,
                   choices=["bfloat16"],
                   help="reduced-precision correlation storage (bfloat16 "
                        "is the deployment config; default exact fp32)")
    args = p.parse_args()

    from raft_tpu.eval import validate
    from raft_tpu.models import raft_large, raft_small

    factory = {"raft_small": raft_small, "raft_large": raft_large}[args.arch]
    overrides = {}
    if args.corr_impl:
        overrides["corr_impl"] = args.corr_impl
    if args.corr_dtype:
        overrides["corr_dtype"] = args.corr_dtype
    if args.random_init:
        model, variables = factory(pretrained=False, **overrides)
    else:
        pretrained = (
            args.pretrained if args.pretrained is not None
            else args.checkpoint is None
        )
        model, variables = factory(
            pretrained=pretrained, checkpoint=args.checkpoint, **overrides
        )

    dataset = build_dataset(args)
    print(f"{args.dataset}: {len(dataset)} pairs")
    # sparse-GT datasets (KITTI) take masked EPE + the bottom-pad protocol;
    # everything non-Sintel pads bottom-only as well (reference InputPadder
    # semantics: 'sintel' splits the vertical pad, everything else doesn't)
    mode = "sintel" if args.dataset == "sintel" else "downstream"
    m = validate(
        model,
        variables,
        dataset,
        num_flow_updates=args.iters,
        mode=mode,
        fps_pairs=args.fps_pairs,
        progress=True,
    )
    print(
        f"{args.arch} {args.dataset}/{args.split or 'default'}: "
        f"epe={m['epe']:.3f} 1px={m['1px']:.3f} 3px={m['3px']:.3f} "
        f"5px={m['5px']:.3f} f1={m['f1']:.3f} fps={m['fps']:.1f}"
    )


if __name__ == "__main__":
    main()
