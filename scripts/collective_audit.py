#!/usr/bin/env python
"""Compiled-HLO collective audit of the multi-chip paths (VERDICT r4 #3).

Turns the "4x+ is the multi-chip path" claim into a calculation: compiles
the REAL sharded programs over a virtual 8-device mesh, enumerates every
collective XLA emitted (kind, count, operand bytes), and divides the
byte totals by ICI bandwidth to produce predicted scaling tables.

Three audited programs:
  A. data=8 training step (the b=8/chip DP scaling config): expect one
     gradient all-reduce tree totaling ~the parameter bytes and nothing
     q-sized (the fused kernel shard_maps over the mesh, so its
     operands stay sharded — an all-gather of the correlation volume would be
     the scaling-killer this audit exists to rule out).
  B. space=8 batch-1 inference at the published Sintel geometry (the
     latency path): per-pair compute divides by 8, halo exchanges
     (collective-permutes around the convs + the partitioned lookup)
     are the overhead that decides whether the b=1 protocol scales.
  C. data=4 x space=2 training (the combined layout the dryrun runs).

Bandwidth assumptions are explicit constants below (public figures, the
scaling-book/TPU-datasheet ballpark): per-link ~45 GB/s each direction,
v5e 2D torus (2 links per axis), v4 3D torus. The report states bytes
and the formula, so any other bandwidth can be substituted by the
reader.

Run on any backend — the audit COMPILES for a virtual CPU mesh (the
same GSPMD partitioner as real chips; collective structure is identical,
only the runtime differs), it never executes the step.

Usage:
    python scripts/collective_audit.py            # full report
    python scripts/collective_audit.py --tiny     # tiny model (tests)
"""

import argparse
import functools
import json
import os as _os
import re
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

# must precede any jax import in the process (tests import this module
# under an already-provisioned conftest mesh, where it is a no-op)
def _provision_virtual_mesh(n: int = 8) -> None:
    flags = _os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in flags:
        _os.environ["XLA_FLAGS"] = f"{flags} {opt}".strip()
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

# one ICI link, one direction, bytes/s — public ballpark for v4/v5e
ICI_LINK_BW = 45e9
# links usable by a 1D ring embedded in the torus (both directions)
RING_LINKS = {"v5e": 2, "v4": 2}


def _shape_bytes(shape: str) -> int:
    total = 0
    for sm in re.finditer(r"(\w+)\[([\d,]*)\]", shape):
        dt = _DTYPE_BYTES.get(sm.group(1))
        if dt is None:
            continue
        n = 1
        for d in sm.group(2).split(","):
            if d:
                n *= int(d)
        total += n * dt
    return total


def _computations(hlo_text: str):
    """-> {name: body_text} for every HLO computation in the module.

    Computation headers sit at column 0 (``%name (...) -> ... {`` or
    ``ENTRY %name ...``; parameter TYPES may contain nested parens, so
    only the leading name is parsed); ops are indented, and a bare ``}``
    at column 0 closes the body.
    """
    comps = {}
    cur, buf = None, []
    head = re.compile(r"(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
    for line in hlo_text.splitlines():
        if cur is None:
            if not line[:1].isspace() and line.rstrip().endswith("{"):
                m = head.match(line)
                if m:
                    cur, buf = m.group(1), [line]
        else:
            if line.startswith("}"):
                comps[cur], cur = "\n".join(buf), None
            else:
                buf.append(line)
    return comps


def _trip_count(while_line: str, cond_text: str) -> tuple:
    """``(trip_count, exact)`` of a while loop. XLA records known counts
    verbatim in the op's ``backend_config={"known_trip_count":{"n":"N"}}``
    (exact). Otherwise fall back to the largest constant that FEEDS the
    condition's ``compare`` op — the loop bound of a scan-lowered counter
    — never an arbitrary constant elsewhere in the computation (a shape
    bound or clamp limit must not silently multiply every in-loop
    collective; ADVICE r5), then to 1 — an unknown loop still counts its
    body at least once. Both fallbacks are flagged inexact so the report
    can mark the derived counts approximate."""
    m = re.search(r"known_trip_count[^}]*\"n\":\"(\d+)\"", while_line)
    if m:
        return int(m.group(1)), True
    const_defs = {
        c.group(1): int(c.group(2))
        for c in re.finditer(
            r"%([\w.\-]+)\s*=[^=\n]*?\bconstant\((\d+)\)", cond_text
        )
    }
    bounds = [
        const_defs[op.group(1)]
        for cm in re.finditer(r"\bcompare\(([^)]*)\)", cond_text)
        for op in re.finditer(r"%([\w.\-]+)", cm.group(1))
        if op.group(1) in const_defs
    ]
    return (max(bounds) if bounds else 1), False


_COLL = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\("
)
_WHILE = re.compile(
    r"\bwhile\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)"
)
_CALLED = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def extract_collectives(hlo_text: str, meta: dict = None):
    """-> {kind: [executed_bytes, ...]} for every cross-device collective,
    with EXECUTION COUNTS honored: a collective inside a scan-lowered
    while body appears ONCE in the static HLO but runs trip-count times
    (the 32-iteration refinement loop!), so the call graph is walked
    from the entry computation, multiplying by each enclosing while's
    trip count. HLO call graphs are acyclic; a computation reached from
    two call sites is correctly counted once per site.

    Bytes are the RESULT shape(s) of the op (tuple shapes summed) — for
    all-reduce the reduced tensor size; for collective-permute the
    payload moved per execution.

    When ``meta`` (a dict) is passed, ``meta['approx_loops']`` receives
    the number of while loops whose trip count had to be derived by the
    compare-operand fallback rather than read from a recorded
    ``known_trip_count`` — nonzero means the per-execution counts are
    approximate and the report says so.
    """
    comps = _computations(hlo_text)
    entry_m = re.search(r"ENTRY\s+%?([\w.\-]+)", hlo_text)
    if meta is not None:
        meta.setdefault("approx_loops", 0)
    if not comps or not entry_m:
        # fallback: flat scan, multiplicity 1
        out = {}
        for m in _COLL.finditer(hlo_text):
            out.setdefault(m.group(2), []).append(_shape_bytes(m.group(1)))
        return out

    out = {}

    def walk(name: str, mult: int):
        body = comps.get(name)
        if body is None:
            return
        for m in _COLL.finditer(body):
            out.setdefault(m.group(2), []).extend(
                [_shape_bytes(m.group(1))] * mult
            )
        loop_comps = set()
        for m in _WHILE.finditer(body):
            cond, wbody = m.group(1), m.group(2)
            loop_comps.update((cond, wbody))
            line_end = body.find("\n", m.end())
            while_line = body[m.start(): line_end if line_end > 0 else None]
            trips, exact = _trip_count(while_line, comps.get(cond, ""))
            if not exact and meta is not None:
                meta["approx_loops"] += 1
            walk(wbody, mult * trips)
            walk(cond, mult)
        for m in _CALLED.finditer(body):
            if m.group(1) not in loop_comps:
                walk(m.group(1), mult)
        for m in _BRANCHES.finditer(body):
            for callee in re.split(r",\s*", m.group(1)):
                walk(callee.lstrip("%"), mult)

    walk(entry_m.group(1), 1)
    return out


# ---------------------------------------------------------------------------
# Pinned collective structure — ONE source of truth (ISSUE 8).
#
# tests/test_multichip.py lowers the REAL sharded programs (the windowed
# sharded train step, the data-sharded serve dispatch) on the 8-virtual-
# device mesh and pins their collective structure with the check_*
# functions below; main() runs the SAME checks on the audit programs it
# predicts scaling from. If either side drifts — a resharding bug, a
# partitioning-rule regression, or an audit prediction that no longer
# matches what XLA emits — the tests fail and the script exits loudly
# (exit 2), instead of the report quietly extrapolating from a stale
# structure.
# ---------------------------------------------------------------------------

STRUCTURE_PINS = {
    # DP training: the all-reduce total is at least the gradient tree
    # (every grad reduced once) and at most ~iters x params (XLA reduces
    # the update-block contribution inside the backward scan once per
    # refinement iteration); nothing q-sized is all-gathered; the b->2b
    # encoder concat/split reshard stays a single-digit all-to-all family
    # outside the scan.
    "train_ar_lower_x_params": 1.0,
    "train_ar_upper_x_params_per_iter": 1.05,
    "train_max_all_to_all_count": 8,
    # DP inference: total collective bytes below 2x the sharded input
    # pair, op count single-digit — nothing rides the refinement scan's
    # trip count.
    "infer_total_x_pair_bytes": 2.0,
    "infer_max_ops": 12,
}


class CollectiveDriftError(AssertionError):
    """A compiled sharded program's collective structure left the pinned
    envelope the scaling predictions (and the multi-chip CI lane) rest on."""


def check_train_structure(
    colls: dict, params: int, iters: int, steps: int = 1
) -> None:
    """Assert a DP train program's collectives match STRUCTURE_PINS.

    ``iters`` is the refinement-iteration count summed over the
    program's train steps; ``steps`` is how many train steps it scans (a
    fused window runs the per-step encoder reshard once per step, so the
    all-to-all pin scales with it)."""
    p = STRUCTURE_PINS
    ar = sum(colls.get("all-reduce", []))
    lo = p["train_ar_lower_x_params"] * params
    hi = p["train_ar_upper_x_params_per_iter"] * iters * params
    if not (lo <= ar <= hi):
        raise CollectiveDriftError(
            f"gradient all-reduce total {ar} bytes outside the pinned "
            f"[{lo:.0f}, {hi:.0f}] envelope (params={params}, iters={iters})"
        )
    big_ag = [s for s in colls.get("all-gather", []) if s > params]
    if big_ag:
        raise CollectiveDriftError(
            f"{len(big_ag)} all-gather(s) larger than the parameter tree "
            f"(max {max(big_ag)} bytes) — a q-sized gather is THE scaling "
            f"killer the partitioning rule exists to prevent"
        )
    a2a = colls.get("all-to-all", [])
    if len(a2a) > p["train_max_all_to_all_count"] * steps:
        raise CollectiveDriftError(
            f"{len(a2a)} all-to-alls (pinned <= "
            f"{p['train_max_all_to_all_count']} x {steps} step(s)): "
            f"encoder-reshard traffic "
            f"grew, or something new rides the scan"
        )


def check_infer_structure(colls: dict, pair_bytes: int) -> None:
    """Assert a DP inference program's collectives match STRUCTURE_PINS."""
    p = STRUCTURE_PINS
    total = sum(s for v in colls.values() for s in v)
    n_ops = sum(len(v) for v in colls.values())
    if total >= p["infer_total_x_pair_bytes"] * pair_bytes:
        raise CollectiveDriftError(
            f"inference collective bytes {total} >= "
            f"{p['infer_total_x_pair_bytes']}x the input pair "
            f"({pair_bytes}) — more than the encoder reshard"
        )
    if n_ops > p["infer_max_ops"]:
        raise CollectiveDriftError(
            f"{n_ops} executed collectives (pinned <= {p['infer_max_ops']}) "
            f"— something is riding the refinement scan's trip count"
        )


def _deployment_cfg(tiny: bool):
    if tiny:
        tests_dir = _os.path.join(_os.path.dirname(__file__), "..", "tests")
        if tests_dir not in _sys.path:
            _sys.path.insert(0, tests_dir)
        from test_train import tiny_cfg

        base = tiny_cfg(large=True)
    else:
        from raft_tpu.models.zoo import RAFT_LARGE

        base = RAFT_LARGE
    return base.replace(
        corr_impl="fused", corr_dtype="bfloat16",
        remat=True, remat_policy="dots",
    )


def audit_train(mesh, cfg, b: int, h: int, w: int, iters: int = 2,
                meta: dict = None):
    """Collectives of the full sharded train step (never executed)."""
    import jax
    import numpy as np

    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.parallel import (
        make_sharded_train_step,
        shard_batch,
        shard_state,
    )
    from raft_tpu.train import TrainState, make_optimizer

    model = build_raft(cfg)
    variables = init_variables(model)
    tx = make_optimizer(lambda _: 1e-4, clip_norm=1.0)
    state = shard_state(TrainState.create(variables, tx), mesh)
    rng = np.random.default_rng(0)
    batch = shard_batch(
        {
            "image1": rng.uniform(-1, 1, (b, h, w, 3)).astype(np.float32),
            "image2": rng.uniform(-1, 1, (b, h, w, 3)).astype(np.float32),
            "flow": rng.uniform(-3, 3, (b, h, w, 2)).astype(np.float32),
            "valid": np.ones((b, h, w), np.float32),
        },
        mesh,
    )
    step = make_sharded_train_step(model, tx, mesh, num_flow_updates=iters)
    hlo = step.lower(state, batch).compile().as_text()
    params = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(variables)
    )
    return extract_collectives(hlo, meta), params


def audit_infer(mesh, cfg, h: int, w: int, iters: int = 32,
                batch: int = 1, spec=(None, "space"), meta: dict = None):
    """Collectives of sharded inference: ``spec`` shards (B, H) — batch-1
    spatial sharding by default, ``("data", None)`` for DP inference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.models import build_raft, init_variables
    from raft_tpu.parallel.mesh import replicated, traced_under

    model = build_raft(cfg)
    variables = init_variables(model)

    @functools.partial(traced_under, mesh)  # the fused kernel shard_maps
    def fwd(variables, im1, im2):
        return model.apply(
            variables, im1, im2, train=False,
            num_flow_updates=iters, emit_all=False,
        )

    im_sh = NamedSharding(mesh, P(*spec))
    f = jax.jit(
        fwd,
        in_shardings=(replicated(mesh), im_sh, im_sh),
        out_shardings=im_sh,
    )
    im = jnp.zeros((batch, h, w, 3), jnp.float32)
    hlo = f.lower(variables, im, im).compile().as_text()
    return extract_collectives(hlo, meta)


def ring_all_reduce_s(bytes_: int, n: int, links: int = 2) -> float:
    """Ring all-reduce wall time: 2(N-1)/N x bytes over `links` ICI links."""
    return 2 * (n - 1) / n * bytes_ / (ICI_LINK_BW * links)


def fmt_collectives(colls, meta: dict = None) -> str:
    lines = []
    for kind in sorted(colls):
        sizes = colls[kind]
        lines.append(
            f"  {kind:20s} count={len(sizes):4d} "
            f"total={sum(sizes)/1e6:9.3f} MB  max={max(sizes)/1e6:.3f} MB"
        )
    if meta and meta.get("approx_loops"):
        lines.append(
            f"  NOTE: {meta['approx_loops']} while loop(s) carried no "
            "recorded known_trip_count; their counts above are APPROXIMATE "
            "(compare-operand fallback)"
        )
    return "\n".join(lines) if lines else "  (none)"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model widths (fast; used by the tests)")
    ap.add_argument("--train-pairs-s", type=float, default=17.3,
                    help="measured single-chip b=8 training pairs/s at "
                         "368x768 (docs/perf_notes.md round-5 table)")
    ap.add_argument("--infer-b8-pairs-s", type=float, default=43.2,
                    help="measured single-chip b=8 inference pairs/s "
                         "(the official _b8 config: fused+bf16 corr, "
                         "bf16 convs — docs/perf_notes.md round-5 "
                         "conv-dtype inversion table)")
    ap.add_argument("--infer-b1-ms", type=float, default=34.5,
                    help="measured single-chip b=1 Sintel latency ms/pair")
    args = ap.parse_args()

    _provision_virtual_mesh(8)
    from raft_tpu.parallel import make_mesh

    cfg = _deployment_cfg(args.tiny)
    geom = (128, 128) if args.tiny else (368, 768)

    print("# Collective audit (8-device virtual mesh, GSPMD)\n")

    # A: pure data parallelism at the REAL b=8/chip scaling config
    train_iters = 2 if args.tiny else 12
    b_a = 8 if args.tiny else 64  # global batch: 8 chips x b=8
    mesh = make_mesh(data=8)
    meta_a = {}
    colls_a, params = audit_train(
        mesh, cfg, b_a, *geom, iters=train_iters, meta=meta_a
    )
    print(f"## A. train step, data=8, b={b_a} global "
          f"(= {b_a // 8}/chip), {geom[0]}x{geom[1]}, "
          f"{train_iters} iters (collectives counted per EXECUTION: "
          "in-loop ops multiply by the scan trip count)")
    print(fmt_collectives(colls_a, meta_a))
    ar_bytes = sum(colls_a.get("all-reduce", []))
    print(f"  gradient tree = {params/1e6:.3f} MB; all-reduce total "
          f"{ar_bytes/1e6:.3f} MB = {ar_bytes/max(params,1):.2f}x params "
          "(XLA reduces the update-block gradient contribution INSIDE "
          "the backward scan, once per iteration, and the encoder "
          "gradients once outside — on real TPU the "
          "WhileLoopAllReduceCodeMotion pass may hoist the in-loop "
          "reduction, so this total is the conservative upper bound "
          "and params bytes the lower)")
    big_ag = [s for s in colls_a.get("all-gather", []) if s > params]
    print(f"  q-sized all-gathers (scaling killers): {len(big_ag)}\n")
    drift = []
    try:
        check_train_structure(colls_a, params, train_iters)
    except CollectiveDriftError as e:
        drift.append(f"train(A): {e}")

    # B: space-sharded b=1 inference at the published geometry
    mesh_s = make_mesh(data=1, space=8)
    h_s, w_s = (128, 128) if args.tiny else (440, 1024)
    infer_iters = 2 if args.tiny else 32
    meta_b = {}
    colls_b = audit_infer(mesh_s, cfg, h_s, w_s, iters=infer_iters,
                          meta=meta_b)
    print(f"## B. inference, space=8, b=1, {h_s}x{w_s}, final-only")
    print(fmt_collectives(colls_b, meta_b))
    halo = sum(colls_b.get("collective-permute", []))
    other_b = sum(sum(v) for k, v in colls_b.items()
                  if k != "collective-permute")
    print(f"  halo payload {halo/1e6:.3f} MB, other {other_b/1e6:.3f} MB\n")

    # C: the combined dryrun layout at b=8/chip
    b_c = 4 if args.tiny else 32
    mesh_c = make_mesh(data=4, space=2)
    meta_c = {}
    colls_c, _ = audit_train(
        mesh_c, cfg, b_c, *geom, iters=train_iters, meta=meta_c
    )
    print(f"## C. train step, data=4 x space=2, b={b_c} global, "
          f"{geom[0]}x{geom[1]}, {train_iters} iters")
    print(fmt_collectives(colls_c, meta_c))

    # D: DP inference (the b=8/chip throughput config) — the scaling
    # story needs this limited to the per-pair encoder reshard, with
    # nothing riding the 32x refinement scan
    b_d = 8 if args.tiny else 64
    meta_d = {}
    colls_d = audit_infer(
        mesh, cfg, h_s, w_s, iters=infer_iters, batch=b_d,
        spec=("data", None), meta=meta_d,
    )
    print(f"\n## D. inference, data=8, b={b_d} global, {h_s}x{w_s}")
    print(fmt_collectives(colls_d, meta_d))
    d_total = sum(s for v in colls_d.values() for s in v)
    print(f"  total {d_total/1e6:.3f} MB/step = "
          f"{d_total/b_d/1e6:.3f} MB/pair — the b->2b encoder "
          "concat/split reshard, once per pair, nothing in the scan")
    try:
        check_infer_structure(colls_d, 2 * b_d * h_s * w_s * 3 * 4)
    except CollectiveDriftError as e:
        drift.append(f"infer(D): {e}")

    # Scaling model (explicit formulae; bandwidths at the top of file)
    print("\n# Predicted scaling (ICI ring, "
          f"{ICI_LINK_BW/1e9:.0f} GB/s/link/dir, 2 links)\n")
    step_s = 8 / args.train_pairs_s
    # the b->2b encoder concat/split reshard (all-to-all + permute) is
    # per-device activation traffic, constant in N, absent at N=1
    rs_bytes = sum(colls_a.get("all-to-all", [])) + sum(
        colls_a.get("collective-permute", [])
    )
    t_rs = rs_bytes / (ICI_LINK_BW * 2) * 1e3
    print("## DP training, b=8/chip, 368x768 "
          f"(single-chip step {step_s*1e3:.0f} ms); all-reduce range = "
          "[param tree (hoisted), compiled in-loop total]; encoder "
          f"reshard {rs_bytes/1e6:.0f} MB = {t_rs:.1f} ms charged at "
          "every N")
    print("chips | all-reduce ms | efficiency | pairs/s/chip | aggregate")
    for n in (2, 4, 8, 16, 32):
        t_lo = ring_all_reduce_s(params, n) * 1e3
        t_hi = ring_all_reduce_s(ar_bytes, n) * 1e3
        eff = step_s / (step_s + (t_hi + t_rs) / 1e3)  # conservative
        pc = args.train_pairs_s * eff
        print(f"{n:5d} | {t_lo:5.2f}-{t_hi:5.2f} | {eff:10.4f} "
              f"| {pc:12.2f} | {pc*n:9.1f}")
    t_d = d_total / b_d / (ICI_LINK_BW * 2) * 1e3
    pair_ms = 1e3 / args.infer_b8_pairs_s
    eff_d = pair_ms / (pair_ms + t_d)
    print(f"\n## DP inference, b=8/chip (audit D: "
          f"{d_total/b_d/1e6:.3f} MB/pair encoder reshard = "
          f"{t_d:.3f} ms vs {pair_ms:.1f} ms/pair -> "
          f"efficiency {eff_d:.4f})")
    print(f"pairs/s/chip = {args.infer_b8_pairs_s * eff_d:.1f} at any N "
          f"(aggregate = N x that); per-chip vs the 3090 Ti stays "
          f"{args.infer_b8_pairs_s * eff_d / 11.8:.2f}x — DP adds "
          "chips, not per-chip speed.")
    print("\n## space=8 b=1 protocol latency path, 440x1024")
    comp = args.infer_b1_ms / 8
    # halo payload crosses one neighbor link per boundary; both
    # directions overlap on distinct links -> halo bytes / link BW
    t_halo = halo / ICI_LINK_BW * 1e3
    t_other = other_b / (ICI_LINK_BW * 2) * 1e3
    lat = comp + t_halo + t_other
    print(f"compute {comp:.2f} ms + halo {t_halo:.3f} ms + other "
          f"{t_other:.3f} ms = {lat:.2f} ms/pair -> "
          f"{1e3/lat:.1f} pairs/s on the b=1 protocol "
          f"({1e3/lat/11.8:.1f}x the 3090 Ti with 8 chips; "
          f"{1e3/lat/8/11.8:.2f}x per chip)")

    if drift:
        print("\n!! COLLECTIVE STRUCTURE DRIFT — the predictions above "
              "extrapolate from a structure that no longer holds "
              "(tests/test_multichip.py pins the same envelope on the "
              "executed sharded programs):", file=_sys.stderr)
        for d in drift:
            print(f"!!   {d}", file=_sys.stderr)
        _sys.exit(2)
    else:
        print("\n# structure cross-check OK: audit collectives inside "
              "the envelope tests/test_multichip.py pins on the "
              "executed programs")

    print("\n" + json.dumps({
        "metric": "collective_audit",
        "approx_trip_count_loops": sum(
            m.get("approx_loops", 0)
            for m in (meta_a, meta_b, meta_c, meta_d)
        ),
        "params_bytes": params,
        "dp8_all_reduce_bytes": ar_bytes,
        "dp8_big_all_gathers": len(big_ag),
        "space8_halo_bytes": halo,
        "space8_b1_pairs_s": round(1e3 / lat, 1),
        "dp_train_eff_32chip_worst": round(
            step_s
            / (step_s + ring_all_reduce_s(ar_bytes, 32) + t_rs / 1e3),
            5,
        ),
    }))


if __name__ == "__main__":
    main()
