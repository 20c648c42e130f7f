"""Summarize a jax.profiler trace into per-component device-time buckets.

Usage:
    python bench.py --models raft_large --profile /tmp/prof
    python scripts/profile_stats.py /tmp/prof [--pairs 16] [--top 25]

Parses the xplane.pb with xprof's HLO-stats converter (JSON DataTable) and
groups HLO ops into RAFT buckets by their framework-op path (module
hierarchy), printing ms per image pair. Device self-time from the trace is the
attribution to trust: wall-clock micro-timings of sub-ms ops carry the
host's dispatch overhead and disagree across processes
(docs/perf_notes.md).
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

BUCKETS = [
    # (bucket, regex against "tf_op_name | hlo expression | category")
    ("fused lookup kernel", r"tpu_custom_call|pallas|xtap"),
    ("feature encoder", r"feature_encoder"),
    ("context encoder", r"context_encoder"),
    ("lookup y-dot", r"qjy|einsum.*corr|index_pyramid.*dot|ydot"),
    ("pyramid build (vol+pool)", r"build_pyramid|corr_volume|avg_pool|reduce-window"),
    ("motion encoder", r"motion_encoder|convcorr|convflow|project_taps"),
    ("GRU", r"convgru|recurrent_block"),
    ("flow head / mask", r"flow_head|mask_predictor"),
    ("upsample", r"upsample"),
    ("lookup x-side / taps", r"index_pyramid|index_project|lookup|separable"),
    ("data movement", r"\bcopy\b|copy\.|bitcast|relayout|transpose"),
]


def load_rows(profile_dir: str):
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        sys.exit(f"no .xplane.pb under {profile_dir}")
    path = max(paths, key=os.path.getmtime)
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data([path], "hlo_stats", {})
    tbl = json.loads(data.decode() if isinstance(data, bytes) else data)
    cols = [c["id"] for c in tbl["cols"]]
    for r in tbl["rows"]:
        yield {k: (c or {}).get("v") for k, c in zip(cols, r["c"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("profile_dir")
    ap.add_argument("--pairs", type=int, default=16,
                    help="image pairs in the profiled region (bench.py default 16)")
    ap.add_argument("--top", type=int, default=25, help="top single ops to list")
    args = ap.parse_args()

    per_bucket = collections.Counter()
    per_op = collections.Counter()
    total = 0.0
    for row in load_rows(args.profile_dir):
        us = float(row.get("total_self_time") or 0.0)
        if not us:
            continue
        key = " | ".join(
            str(row.get(k) or "") for k in ("tf_op_name", "hlo_op_expression", "category")
        )
        total += us
        per_op[f"[{row.get('category')}] {str(row.get('tf_op_name'))[-70:]} :: "
               f"{str(row.get('hlo_op_name'))[:40]}"] += us
        for bucket, pat in BUCKETS:
            if re.search(pat, key, re.I):
                per_bucket[bucket] += us
                break
        else:
            per_bucket[f"other:{row.get('category') or 'unknown'}"] += us

    n = args.pairs
    print(f"device total: {total/1e3:.1f} ms = {total/1e3/n:.2f} ms/pair over {n} pairs\n")
    print(f"{'bucket':34s} {'ms/pair':>8s} {'share':>6s}")
    for bucket, us in per_bucket.most_common():
        print(f"{bucket:34s} {us/1e3/n:8.2f} {us/total*100:5.1f}%")
    print(f"\ntop {args.top} ops (self time):")
    for name, us in per_op.most_common(args.top):
        print(f"  {us/1e3/n:7.3f} ms/pair  {name}")


if __name__ == "__main__":
    main()
