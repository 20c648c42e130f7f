#!/usr/bin/env python
"""Single-pair inference demo (reference surface: ``examples/demo.py``).

Usage: python scripts/demo.py IMG1 IMG2 [--arch raft_small] [--out flow.png]
"""

import argparse

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


import numpy as np


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("image1")
    p.add_argument("image2")
    p.add_argument("--arch", default="raft_small", choices=["raft_small", "raft_large"])
    p.add_argument("--checkpoint", default=None, help="local .msgpack weights")
    p.add_argument("--pretrained", action="store_true")
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--out", default=None, help="write flow visualization PNG here")
    p.add_argument("--out-flo", default=None, help="write raw .flo here")
    args = p.parse_args()

    from raft_tpu import FlowEstimator
    from raft_tpu.data.io import read_image, write_flo
    from raft_tpu.models import raft_large, raft_small
    from raft_tpu.utils.flow_viz import flow_to_image

    factory = {"raft_small": raft_small, "raft_large": raft_large}[args.arch]
    model, variables = factory(
        pretrained=args.pretrained, checkpoint=args.checkpoint
    )

    # FlowEstimator owns the input contract: raw [0,255] images in, flow at
    # input resolution out (normalize + replicate-pad + jit inside)
    estimate = FlowEstimator(model, variables, num_flow_updates=args.iters)
    flow = estimate(read_image(args.image1), read_image(args.image2))
    print(
        f"flow: shape={flow.shape} mean |f|="
        f"{np.linalg.norm(flow, axis=-1).mean():.3f} px"
    )
    if args.out_flo:
        write_flo(args.out_flo, flow)
        print(f"wrote {args.out_flo}")
    if args.out:
        from PIL import Image

        Image.fromarray(flow_to_image(flow)).save(args.out)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
