#!/usr/bin/env python3
"""``benchmarks/run.py`` with a parked set of per-layer metrics
(``benchmarks/parked/<name>/``: ``metrics.json`` + ``layer_metrics/``)
brought back for the cells its entries name: same arguments, same result
line, the parked metrics on it in a traced run.

    python3 benchmarks/tools/run_parked.py --parked sched_phases \\
        --workload raft_large.sintel_offline --seed 3000000077 --seconds 30 --trace 1

``sched_phases`` (PR 27) holds six metrics of the pool scheduler's phases
for the two offline cells. They are parked because a cell's own file lists
its per-layer metrics and ``loader.load_cell`` refuses a list that differs
from the manifest's: a metric cannot join a cell that is there without an
edit to that cell's file (PERF.md, Open questions). ``unpark_metrics``
makes that edit in a copy: the manifest with the entries appended, each
named cell's file with the names appended, nothing else changed."""

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def unpark_metrics(name: str, dest: str) -> str:
    """A copy of the benchmark's data under ``dest`` with the parked
    metrics ``name`` in place; returns ``dest``, for ``load_cell(cell, dest)``."""
    from benchmarks import loader

    park = os.path.join(loader.HERE, "parked", name)
    bench = os.path.join(dest, "benchmarks")
    shutil.copytree(loader.HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "parked", "*.py", "*.sh"))
    shutil.copytree(os.path.join(park, "layer_metrics"),
                    os.path.join(bench, "layer_metrics"), dirs_exist_ok=True)
    with open(os.path.join(park, "metrics.json")) as f:
        entries = json.load(f)["per_layer"]
    man = loader.manifest()
    man["per_layer"] = man["per_layer"] + entries
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    for m in entries:
        for cell in m["workloads"]:
            path = os.path.join(bench, "workloads", f"{cell}.json")
            with open(path) as f:
                spec = json.load(f)
            spec["per_layer"].append(m["name"])
            with open(path, "w") as f:
                json.dump(spec, f)
    return dest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parked", required=True, help="a directory under benchmarks/parked/")
    args, rest = ap.parse_known_args(argv)

    from benchmarks import loader, run as runmod

    root = unpark_metrics(args.parked, tempfile.mkdtemp(prefix="unparked_"))
    load_cell = loader.load_cell
    loader.load_cell = lambda name: load_cell(name, root=root)  # what run.main calls
    return runmod.main(rest)


if __name__ == "__main__":
    sys.exit(main())
