"""Pin the collective structure of the sharded programs (VERDICT r4 #3).

The multi-chip scaling argument (docs/perf_notes.md "Quantified
multi-chip scaling") rests on three structural facts of the compiled
HLO; this file turns each into a regression test so a resharding bug or
a partitioning-rule regression is caught at test time, not at pod time:

  1. pure-DP training all-reduces exactly the gradient tree (~params
     bytes) — nothing activation-sized;
  2. no q-sized all-gather exists anywhere (the fused kernel's
     shard_map keeps every query-carrying operand sharded —
     an all-gather of the correlation volume is THE scaling killer);
  3. spatial sharding exchanges conv halos via collective-permute.

Runs the tiny-width model (same layer/collective structure as
raft_large, minutes faster to compile).
"""

import importlib.util
import os
import sys

import pytest

def _load_audit():
    if "collective_audit" in sys.modules:
        return sys.modules["collective_audit"]
    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "collective_audit.py"
    )
    spec = importlib.util.spec_from_file_location("collective_audit", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["collective_audit"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_dp_train_collective_structure():
    audit = _load_audit()
    from raft_tpu.parallel import make_mesh

    cfg = audit._deployment_cfg(tiny=True)
    mesh = make_mesh(data=8)
    iters = 2
    colls, params = audit.audit_train(mesh, cfg, 8, 128, 128, iters=iters)

    # the shared pinned envelope (collective_audit.STRUCTURE_PINS):
    # gradient all-reduce in [params, ~iters x params], no q-sized
    # all-gather (THE scaling killer), encoder-reshard all-to-alls
    # single-digit and outside the scan. The script's main() runs the
    # SAME checks on its predicted programs and exits 2 on drift, so a
    # divergence between prediction and pinned structure is loud in
    # both places.
    audit.check_train_structure(colls, params, iters)

    # byte bound local to this geometry: the reshard stays << one batch
    # of feature maps at 128x128 tiny
    assert sum(colls.get("all-to-all", [])) < 4 * 128 * 128 * 8 * 4, colls


def test_dp_inference_collectives_bounded_by_encoder_reshard():
    """The DP-inference scaling claim ('per-chip ~flat at any N') rests
    on the forward emitting only the b->2b encoder concat/split
    resharding (one fmap-sized all-to-all family per pair), never
    anything volume- or loop-iterated-sized. Bound it: total collective
    bytes under a few input-pair sizes, counts single-digit, and nothing
    multiplied by the refinement scan's trip count."""
    audit = _load_audit()
    from raft_tpu.parallel import make_mesh

    cfg = audit._deployment_cfg(tiny=True)
    mesh = make_mesh(data=8)
    colls = audit.audit_infer(
        mesh, cfg, 128, 128, iters=2, batch=8, spec=("data", None)
    )
    pair_bytes = 2 * 8 * 128 * 128 * 3 * 4  # the sharded input pair
    # shared envelope: total < 2x pair bytes, single-digit executed ops
    # (nothing rides the scan) — same checks the script's main() runs
    audit.check_infer_structure(colls, pair_bytes)


def test_space_sharding_emits_halos():
    audit = _load_audit()
    from raft_tpu.parallel import make_mesh

    cfg = audit._deployment_cfg(tiny=True)
    mesh = make_mesh(data=1, space=8)
    colls = audit.audit_infer(mesh, cfg, 128, 128, iters=2)

    # conv halo exchanges present, and each small (rows-of-boundary, not
    # whole activations): the largest permute payload must be far below
    # one full /1-scale activation slab
    perms = colls.get("collective-permute", [])
    assert len(perms) > 0, colls
    assert max(perms) < 128 * 128 * 64 * 4 / 8, colls

    # gradient-free forward: any all-reduce is a scalar/stat, never
    # activation-sized
    assert all(s < 1e5 for s in colls.get("all-reduce", [])), colls


def test_extract_collectives_parses_tuple_shapes():
    audit = _load_audit()
    hlo = """
  %ar.1 = f32[100,2]{1,0} all-reduce(f32[100,2]{1,0} %x), replica_groups={}
  %cp.2 = (f32[4,8]{1,0}, f32[4,8]{1,0}) collective-permute(...)
  %ag.3 = bf16[16]{0} all-gather(bf16[2]{0} %y), dimensions={0}
"""
    got = audit.extract_collectives(hlo)
    # result shapes only (tuples summed over members)
    assert got["all-reduce"] == [100 * 2 * 4]
    assert got["collective-permute"] == [4 * 8 * 4 * 2]
    assert got["all-gather"] == [16 * 2]


def test_extract_collectives_multiplies_loop_trip_counts():
    """A collective inside a while body counts once per iteration (the
    32-iteration refinement scan is where the halo exchanges live)."""
    audit = _load_audit()
    hlo = """\
%body.1 (p: (s32[], f32[8]{0})) -> (s32[], f32[8]{0}) {
  %cp = f32[8]{0} collective-permute(f32[8]{0} %x)
}

%cond.1 (p: (s32[], f32[8]{0})) -> pred[] {
  %c = s32[] constant(5)
}

ENTRY %main.2 (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  %ar = f32[8]{0} all-reduce(f32[8]{0} %y), to_apply=%cond.1
}
"""
    got = audit.extract_collectives(hlo)
    assert got["collective-permute"] == [32] * 5  # 8 f32 x trip count 5
    assert got["all-reduce"] == [32]  # entry-level: once


def test_trip_count_fallback_restricted_to_compare_operands():
    """Without a recorded known_trip_count, only constants FEEDING the
    condition's compare may set the trip count — an unrelated constant
    (shape bound, clamp limit) in the same computation must not multiply
    every in-loop collective (ADVICE r5) — and fallback-derived counts
    are flagged inexact so the report marks them approximate."""
    audit = _load_audit()

    cond = """\
%cond.2 (p: (s32[], f32[8]{0})) -> pred[] {
  %gte = s32[] get-tuple-element((s32[], f32[8]{0}) %p), index=0
  %huge = s32[] constant(4096)
  %pad = f32[8]{0} pad(f32[8]{0} %x, f32[] %z), padding=0_4096
  %bound = s32[] constant(7)
  ROOT %lt = pred[] compare(s32[] %gte, s32[] %bound), direction=LT
}"""
    n, exact = audit._trip_count("%w = while(...)", cond)
    assert (n, exact) == (7, False)  # 7 feeds the compare; 4096 ignored

    # no compare-feeding constant at all -> 1, still inexact
    n, exact = audit._trip_count("%w = while(...)", "%c = s32[] constant(99)")
    assert (n, exact) == (1, False)

    # recorded count wins and is exact
    n, exact = audit._trip_count(
        '%w = while(%t), backend_config={"known_trip_count":{"n":"5"}}', cond
    )
    assert (n, exact) == (5, True)

    # approximate loops surface in extract_collectives' meta
    hlo = """\
%body.9 (p: (s32[], f32[8]{0})) -> (s32[], f32[8]{0}) {
  %cp = f32[8]{0} collective-permute(f32[8]{0} %x)
}

%cond.9 (p: (s32[], f32[8]{0})) -> pred[] {
  %k = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %k), direction=LT
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]{0}) while(%t), condition=%cond.9, body=%body.9
}
"""
    meta = {}
    got = audit.extract_collectives(hlo, meta)
    assert got["collective-permute"] == [32] * 3
    assert meta["approx_loops"] == 1
    note = audit.fmt_collectives(got, meta)
    assert "APPROXIMATE" in note
