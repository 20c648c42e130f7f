#!/usr/bin/env python
"""Convert a torchvision RAFT checkpoint (.pth) to Flax msgpack.

Usage: python scripts/convert_checkpoint.py INPUT.pth OUTPUT.msgpack
"""

import argparse

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))



def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("input", help="torch state_dict .pth")
    p.add_argument("output", help="output .msgpack path")
    args = p.parse_args()
    if not args.output.endswith(".msgpack"):
        p.error("output must end with .msgpack")

    from raft_tpu.checkpoint import convert_checkpoint_file

    convert_checkpoint_file(args.input, args.output)
    print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
