"""Admission programs — ``pool_begin_pair`` at 1080p and beside the
pool, the feature encoder's movement, the stream programs' operands and
``stream_swap``'s blocked search — and the memory budget against the
programs it names, compiled for a described TPU v5e — no chip (rules:
``tests/chip_compile_common.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# the fixtures are found by name in the module that uses them
from tests.chip_compile_common import (  # noqa: F401
    VIDEO_BUCKET, KITTI_BUCKET, KITTI_SLOTS, _on, _serving_model, topo,
    no_persistent_cache, one_chip, video_model, kitti_model,
)


@pytest.fixture(scope="module")
def begin_pair_program(topo, no_persistent_cache):
    """``pool_begin_pair`` of the served model for ``pairs`` pairs of a
    bucket, on one described v5e or a serve mesh of ``chips``, compiled
    once for the module (resid 20): two tests read the 1080p program,
    a minute of compile each time."""
    from raft_tpu.parallel.serve_shard import make_serve_mesh
    from raft_tpu.serve.pool import PoolPrograms

    memo = {}

    def get(arch, bucket, pairs=1, chips=1):
        key = arch, bucket, pairs, chips
        if key not in memo:
            _, _, model, variables, image = _serving_model(arch, bucket)
            images = jax.ShapeDtypeStruct(
                (pairs,) + image.shape[1:], image.dtype
            )
            if chips == 1:
                mesh = None
                rep = rows = SingleDeviceSharding(topo.devices[0])
            else:
                mesh = make_serve_mesh(chips, devices=topo.devices)
                rep = NamedSharding(mesh, P())
                rows = NamedSharding(mesh, P("data"))
            memo[key] = PoolPrograms(
                model, mesh=mesh, resid_len=20
            ).begin_pair.lower(
                _on(rep, variables), _on(rows, images), _on(rows, images)
            ).compile()
        return memo[key]

    return get


def test_hd1080_admission_fits_beside_the_pool(one_chip, begin_pair_program):
    """The 1080p cell's admission at its capacity (2 slots, one pair at a
    time): ``pool_begin_pair``'s row and temporaries beside the resident
    state, and the insert that follows, stay inside one v5e's 16 GB —
    with the room ``pool_final`` (0.8 GB of temporaries) and the frames
    need. ``insert`` writes into the donated state: its output is the
    state's own buffers, and its only temporaries are row prefetches.
    A third slot would not fit (9.9 + 3.3 + 2.3 GB before anything
    else): PERF.md, section 4."""
    from raft_tpu.serve.pool import PoolPrograms, state_spec

    slots, bucket = 2, (1088, 1920)
    _, _, model, variables, image = _serving_model("raft_large", bucket)
    progs = PoolPrograms(model, resid_len=20)
    rows = jax.eval_shape(progs.begin_pair, variables, image, image)
    state = state_spec(model, variables, slots, bucket, resid_len=20)
    begin = begin_pair_program("raft_large", bucket).memory_analysis()
    insert = progs.insert.lower(*_on(one_chip, (
        state, rows,
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.bool_),
    ))).compile().memory_analysis()
    gb = 1e9
    state_bytes = insert.output_size_in_bytes
    assert 6.4 * gb < state_bytes < 6.8 * gb           # 3.3 GB a slot
    assert insert.alias_size_in_bytes >= state_bytes - 4096  # in place
    assert insert.temp_size_in_bytes < 0.1 * gb
    at_begin = (state_bytes + begin.output_size_in_bytes
                + begin.temp_size_in_bytes)
    assert at_begin < 13.5 * gb, at_begin  # of 16: 2.5 GB left for the rest


_MOVES = ("copy", "reshape", "pad", "broadcast")


def _itemsize(hlo_dtype):
    """Bytes an element of an HLO type takes (``bf16``, ``s32``, ``pred``,
    ``f8e4m3fn``, ...), by way of the numpy dtype of the same meaning."""
    kind, bits = re.fullmatch(r"([a-z]+?)(\d.*)?", hlo_dtype).groups()
    name = {"pred": "bool", "bf": "bfloat", "f": "float", "s": "int",
            "u": "uint", "c": "complex"}.get(kind, kind) + (bits or "")
    try:
        return jnp.dtype(name.replace("float8", "float8_")).itemsize
    except TypeError:
        raise AssertionError(f"no element size known for HLO type {hlo_dtype!r}")


def _entry_bytes_written(hlo_text, scope):
    """Bytes the entry computation's ``_MOVES`` instructions write, for
    those whose ``op_name`` lies under ``scope``: what a compiled program
    spends on moving an activation it already has. Instructions inside a
    fusion are not counted — they write nothing of their own; each of
    these operations has one array as its result."""
    body = hlo_text[hlo_text.index("\nENTRY "):]
    body = body[:body.index("\n}")]
    instr = re.compile(r"^\s*(?:ROOT )?\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
    total = 0
    for line in body.splitlines():
        m = instr.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or m[3] not in _MOVES or not name or scope not in name[1]:
            continue
        n = _itemsize(m[1])
        for d in filter(None, m[2].split(",")):
            n *= int(d)
        total += n
    return total


@pytest.mark.parametrize(
    "arch,bucket,pairs,chips,ceiling,parent,parent_temp",
    [
        # reached / before the depth form, bytes (PERF.md, section 5)
        ("raft_large", (440, 1024), 1, 1, 0.126e9, 2.374e9, 358_275_072),
        ("raft_small", (440, 1024), 1, 1, 0.072e9, 0.701e9, 154_359_808),
        ("raft_large", (1088, 1920), 1, 1, 1.023e9, 15.709e9, 2_279_497_728),
        # the other side of FeatureEncoder's rule: 8 frames, batch form
        ("raft_large", (440, 1024), 4, 1, 0, 0, 639_968_768),
        # and the same 8 frames on a serve mesh of two: 4 a device
        ("raft_large", (440, 1024), 4, 2, 0.272e9, 4.748e9, 944_809_472),
    ],
    ids=["raft_large-sintel", "raft_small-sintel", "raft_large-hd1080",
         "raft_large-sintel-rung4", "raft_large-sintel-rung4-mesh2"],
)
def test_admission_moves_no_feature_encoder_activation(
    begin_pair_program, arch, bucket, pairs, chips, ceiling, parent,
    parent_temp,
):
    """``pool_begin_pair``: the feature encoder's instance norms run in
    the split the TPU compiler computes their convs in. Written over
    ``(B, H, W, C)`` with one pair's two frames as the batch, each
    half-resolution norm stood between four relayout copies of the whole
    fp32 activation and two materialised broadcasts of its statistics
    (at 1080p two reshapes more): half of all the program wrote. With the
    frames on a depth axis of batch-1 convs the sums leave the conv's own
    fusion as ``(B, C)`` and the normalisation joins the next one; what
    is left is the frames' way in and the feature maps' way out. From 8
    frames on (rung 4) the compiler splits nothing, the batch form moves
    nothing and is kept (the depth form there writes 0.44 GB of movement
    and 1.3x the bytes in all) — 8 frames *a device*: on a serve mesh of
    two, rung 4 is 4 frames each and the depth form again (bytes of one
    device's program). A ceiling is what the depth form reached plus 10%,
    far under half the parent's; the program's temporaries may not grow
    either. Read on jax 0.9.0 / libtpu 0.0.34: another compiler may split
    at other sizes, and these numbers are then to be read again, with
    ``FeatureEncoder``'s rule."""
    compiled = begin_pair_program(arch, bucket, pairs, chips)
    moved = _entry_bytes_written(compiled.as_text(), "feature_encoder")
    assert moved <= ceiling and (moved > 0) == (parent > 0), moved
    assert ceiling <= parent / 2
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


@pytest.mark.parametrize("rung", [1, 8])
def test_stream_admission_takes_the_encoders_own_dtype(
    one_chip, video_model, rung
):
    """``encode_frame`` and ``pool_begin_features`` at the cell's bucket,
    at the smallest and the largest admit rung, compiled for one v5e:
    the feature map and the context output leave the encode program in
    bf16 (the ``throughput`` preset's conv dtype) and ``pool_begin_features``
    is lowered for exactly those operands — what ``aot.program_specs``
    does, where the engine used to hand it fetched float32
    arrays (PERF.md, §7 row 4: "compiled with bfloat16[...] and called
    with float32[...]"). A rung-8 cohort's rows and temporaries fit
    beside a 32-slot pool."""
    from raft_tpu.serve.pool import PoolPrograms
    from raft_tpu.serve.stream_cache import encode_frame_program

    _, _, model, variables, _ = video_model
    frames = jax.ShapeDtypeStruct((rung,) + VIDEO_BUCKET + (3,), jnp.float32)
    encode = jax.jit(encode_frame_program(model.apply))
    fm, cx, ok = jax.eval_shape(encode, variables, frames)
    assert fm.dtype == cx.dtype == jnp.bfloat16 and ok.dtype == jnp.bool_
    assert fm.shape == (rung, 55, 128, 256) and cx.shape == fm.shape
    enc = encode.lower(*_on(one_chip, (variables, frames))).compile()
    progs = PoolPrograms(model, resid_len=32)
    init = jax.ShapeDtypeStruct((rung, 55, 128, 2), jnp.float32)
    begin = progs.begin_features.lower(
        *_on(one_chip, (variables, fm, fm, cx, init))
    ).compile()
    args = begin.as_text().split("ENTRY ")[1].split("\n")[0]
    assert f"bf16[{rung},55,128,256]" in args and "f32[" + f"{rung},55,128,256]" not in args
    gb = 1e9
    mem = begin.memory_analysis()
    # a row is 0.22 GB (the slot's resident pyramid); 32 slots are 7.2 GB
    assert mem.output_size_in_bytes < 0.23 * gb * rung
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 4.0 * gb
    assert enc.memory_analysis().temp_size_in_bytes < 1.5 * gb


@pytest.mark.parametrize(
    "bucket,rung",
    [((440, 1024), 8), ((1088, 1920), 1)],
    ids=["sintel-440x1024-rung8", "hd1080-1088x1920"],
)
def test_stream_swap_interpolates_in_blocks(one_chip, bucket, rung):
    """``stream_swap`` with warm start (the session table's gather, the
    in-place row writes and upstream's ``forward_interpolate`` a lane)
    for one v5e: the nearest-point search runs in blocks of target cells,
    so no ``Q x Q`` array exists — Q is 7,040 here (198 MB whole) and
    32,640 at 1088x1920 (4.3 GB) — and the program's temporaries stay
    under 64 MB at both, whatever the rung (lanes are walked one after
    another). The table is written in place: the output aliases it."""
    from raft_tpu.serve.stream_cache import StreamPrograms

    sessions = 48 if bucket == (440, 1024) else 4
    h8, w8 = bucket[0] // 8, bucket[1] // 8
    rows = lambda n, c, dt: jax.ShapeDtypeStruct((n, h8, w8, c), dt)
    table = {"fmap": rows(sessions, 256, jnp.bfloat16),
             "ctx": rows(sessions, 256, jnp.bfloat16),
             "flow": rows(sessions, 2, jnp.float32)}
    lane = lambda dt: jax.ShapeDtypeStruct((rung,), dt)
    compiled = StreamPrograms(warm_start=True).swap.lower(*_on(one_chip, (
        table, rows(rung, 256, jnp.bfloat16), rows(rung, 256, jnp.bfloat16),
        lane(jnp.int32), lane(jnp.bool_), lane(jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    table_bytes = sessions * h8 * w8 * (2 * 256 * 2 + 2 * 4)
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= table_bytes - 4096   # in place
    assert " conditional(" in compiled.as_text()   # a cold lane skips the search


def test_the_budget_agrees_with_the_programs_it_names(one_chip, kitti_model):
    """``pool.memory_plan`` for the KITTI pool, from shapes (the engine's
    construction-time figures) and the compiled admission programs'
    temporaries (what boot adds): the state it counts is within 0.5% of
    what ``insert`` writes in place, the row within 0.5% of what
    ``pool_begin_pair`` returns (the compiler pads a few small leaves,
    coordinates and the residual history, to whole tiles), and the
    temporaries it takes are the larger program's own."""
    from raft_tpu.serve.pool import (
        PoolPrograms, memory_plan, state_spec, tree_bytes,
    )

    _, _, model, variables, image = kitti_model
    progs = PoolPrograms(model, resid_len=32)
    state = state_spec(model, variables, KITTI_SLOTS, KITTI_BUCKET,
                       resid_len=32)
    rows = jax.eval_shape(progs.begin_pair, variables, image, image)
    begin = progs.begin_pair.lower(
        *_on(one_chip, (variables, image, image))
    ).compile().memory_analysis()
    insert = progs.insert.lower(*_on(one_chip, (
        state, rows,
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.bool_),
    ))).compile().memory_analysis()
    label = f"{KITTI_BUCKET[0]}x{KITTI_BUCKET[1]}"
    state_bytes = tree_bytes(state)
    temps = {"pool_begin_pair 1 376 1248": begin.temp_size_in_bytes,
             "pool_insert 1 47 156": insert.temp_size_in_bytes}
    plan = memory_plan({label: state_bytes},
                       {label: state_bytes // KITTI_SLOTS}, temps, None)
    assert plan["state_bytes"][label] == pytest.approx(
        insert.output_size_in_bytes, rel=5e-3)
    assert insert.alias_size_in_bytes >= insert.output_size_in_bytes - 4096
    assert plan["row_bytes"] == pytest.approx(
        begin.output_size_in_bytes, rel=5e-3)
    assert plan["temp_program"] == "pool_begin_pair 1 376 1248"
    assert plan["temp_bytes"] == begin.temp_size_in_bytes > 0.1e9
    assert plan["planned_bytes"] == (
        state_bytes + state_bytes // KITTI_SLOTS + begin.temp_size_in_bytes)
