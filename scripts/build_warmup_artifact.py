#!/usr/bin/env python
"""Build + verify an AOT warmup artifact for a ServeConfig (ISSUE 7).

The artifact is the serving engine's whole compiled program set —
AOT-compiled from shape/dtype specs (never executed), serialized next to
a fingerprint (jax/jaxlib/backend/device, program-set config, precision
preset, weight-tree hash). A replica booting with
``ServeConfig(warmup_artifact=<path>)`` loads executables instead of
compiling them: ``stats()['boot']['programs_compiled'] == 0``,
counter-verified.

Build it on a machine identical to the fleet (same jaxlib, same
accelerator): the fingerprint refuses anything else with a typed
:class:`~raft_tpu.serve.ArtifactMismatch` naming the mismatched field —
and a booting engine that hits the mismatch logs it and degrades to
compiling (slower boot, never a refused boot).

The fingerprint keys on config + weights, never on replica identity, so
a homogeneous serving tier (``ServeRouter``, ISSUE 9) shares ONE
artifact across every replica boot, rebuild, and draining restart —
``--replicas N`` verifies exactly that by loading the artifact once per
replica after the build.

Build (production):   python scripts/build_warmup_artifact.py \
                          --arch raft_large --preset throughput \
                          --pretrained --out warm.raftaot --replicas 4
Build (CPU smoke):    python scripts/build_warmup_artifact.py --tiny \
                          --ladder 2,1 --max-batch 2 --out /tmp/w.raftaot
Check an artifact:    python scripts/build_warmup_artifact.py --tiny \
                          --ladder 2,1 --max-batch 2 --check /tmp/w.raftaot
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_config(args):
    from raft_tpu.serve import ServeConfig

    kw = dict(
        buckets=tuple(
            tuple(int(x) for x in b.split("x")) for b in args.bucket.split(",")
        ),
        ladder=tuple(int(x) for x in args.ladder.split(",")),
        max_batch=args.max_batch,
        pool_capacity=args.pool_capacity,
        mesh_devices=args.mesh_devices,
        stream_cache_size=args.stream_cache_size,
        warmup_workers=args.workers,
    )
    if args.batch_ladder:
        kw["batch_ladder"] = tuple(int(x) for x in args.batch_ladder.split(","))
    if args.preset:
        return ServeConfig.preset(args.preset, **kw)
    return ServeConfig(**kw)


def build_model(args, cfg):
    if args.tiny:
        from raft_tpu.models import build_raft, init_variables

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from serve_bench import tiny_config

        model = build_raft(tiny_config().replace(**cfg.model_overrides()))
        return model, init_variables(model)
    from raft_tpu.models.zoo import raft_for_serving

    return raft_for_serving(
        cfg, arch=args.arch, pretrained=args.pretrained,
        checkpoint=args.checkpoint,
    )


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="raft_large",
                    choices=["raft_small", "raft_large"])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-sized random-init model (smoke runs)")
    ap.add_argument("--preset", default=None,
                    choices=["quality", "throughput"],
                    help="precision preset baked into config + fingerprint")
    ap.add_argument("--pretrained", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--bucket", default=None,
                    help="comma list of HxW buckets (default 440x1024, "
                         "tiny: 48x64)")
    ap.add_argument("--ladder", default="32,20,12")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--batch-ladder", default=None)
    ap.add_argument("--pool-capacity", type=int, default=8)
    ap.add_argument("--mesh-devices", type=int, default=1,
                    help="build for an N-way serve mesh (ISSUE 8): the "
                         "artifact fingerprint keys on the dispatch "
                         "device count, so build at the fleet's "
                         "mesh_devices or the engines will refuse it "
                         "(typed, degrading to compile)")
    ap.add_argument("--stream-cache-size", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=1,
                    help="verify the built artifact loads once per "
                         "replica of an N-replica router tier (ISSUE 9: "
                         "one artifact is shared by every same-config "
                         "replica — the fingerprint keys on config + "
                         "weights, not replica identity)")
    ap.add_argument("--workers", type=int, default=0,
                    help="concurrent AOT compile threads (0 = auto)")
    ap.add_argument("--out", default=None, help="artifact path to write")
    ap.add_argument("--check", default=None,
                    help="verify an existing artifact against this "
                         "config/model instead of building")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the post-build load-back verification")
    args = ap.parse_args(argv)
    if args.bucket is None:
        args.bucket = "48x64" if args.tiny else "440x1024"
    if not args.out and not args.check:
        ap.error("one of --out / --check is required")

    from raft_tpu.serve import ArtifactMismatch, ServeEngine, aot

    cfg = build_config(args)
    model, variables = build_model(args, cfg)
    # never started: the engine is only the program-set/fingerprint host
    engine = ServeEngine(model, variables, cfg)

    if args.check:
        try:
            art = aot.load_artifact(args.check, aot.fingerprint(engine))
        except ArtifactMismatch as e:
            print(json.dumps({
                "metric": "warmup_artifact_check", "path": args.check,
                "ok": False, "field": e.field, "error": str(e),
            }), flush=True)
            raise SystemExit(2)
        report = {
            "metric": "warmup_artifact_check", "path": args.check,
            "ok": True, "programs": len(art["programs"]),
            "fingerprint": {
                k: str(v) for k, v in art["fingerprint"].items()
            },
        }
        print(json.dumps(report), flush=True)
        return report

    info = aot.save_artifact(engine, args.out, workers=args.workers)
    report = {"metric": "warmup_artifact_build", **info}
    if not args.no_verify:
        t0 = time.monotonic()
        art = aot.load_artifact(args.out, aot.fingerprint(engine))
        execs = aot.load_programs(art, engine.dispatch_devices)
        report["verified_programs"] = len(execs)
        report["verify_load_s"] = round(time.monotonic() - t0, 3)
        if args.replicas > 1:
            # the router tier's boot path: every replica (and every
            # rebuild after an eviction or draining restart) loads this
            # same artifact — verify one load per replica
            t0 = time.monotonic()
            loads = [
                len(aot.load_programs(
                    aot.load_artifact(args.out, aot.fingerprint(engine)),
                    engine.dispatch_devices,
                ))
                for _ in range(args.replicas)
            ]
            report["replicas_verified"] = args.replicas
            report["per_replica_programs_loaded"] = loads
            report["replica_verify_load_s"] = round(
                time.monotonic() - t0, 3
            )
    print(json.dumps(report), flush=True)
    return report


if __name__ == "__main__":
    main()
