#!/usr/bin/env python
"""Sintel-train validation (the reference's acceptance protocol,
``scripts/validate_sintel.py`` there; torch-free here).

Usage: python scripts/validate_sintel.py DATA_ROOT [--arch both] [--iters 32]
"""

import argparse

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))



def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("root", help="Sintel root (contains training/)")
    p.add_argument(
        "--arch", default="both", choices=["raft_small", "raft_large", "both"]
    )
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--pretrained", action="store_true", default=None)
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--corr-impl", default=None,
                   choices=["dense", "onthefly", "fused"],
                   help="correlation implementation (default: library "
                        "dense fp32 — the published-protocol semantics; "
                        "'fused' runs the Pallas deployment kernel)")
    p.add_argument("--corr-dtype", default=None,
                   choices=["bfloat16"],
                   help="reduced-precision correlation storage (bfloat16 "
                        "is the deployment config, golden-fixture EPE "
                        "delta bounded in tests/test_epe_golden.py)")
    args = p.parse_args()

    from raft_tpu.eval import validate_sintel
    from raft_tpu.models import raft_large, raft_small

    overrides = {}
    if args.corr_impl:
        overrides["corr_impl"] = args.corr_impl
    if args.corr_dtype:
        overrides["corr_dtype"] = args.corr_dtype
    archs = (
        ["raft_small", "raft_large"] if args.arch == "both" else [args.arch]
    )
    for arch in archs:
        factory = {"raft_small": raft_small, "raft_large": raft_large}[arch]
        pretrained = (
            args.pretrained
            if args.pretrained is not None
            else args.checkpoint is None
        )
        model, variables = factory(
            pretrained=pretrained, checkpoint=args.checkpoint, **overrides
        )
        results = validate_sintel(
            model, variables, args.root, num_flow_updates=args.iters
        )
        for dstype, m in results.items():
            print(
                f"{arch} {dstype}: epe={m['epe']:.3f} 1px={m['1px']:.3f} "
                f"3px={m['3px']:.3f} 5px={m['5px']:.3f} fps={m['fps']:.1f}"
            )


if __name__ == "__main__":
    main()
