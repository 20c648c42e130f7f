"""The main path's kernels compiled for a described TPU v5e — no chip.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2
step 3). Interpret-mode tests cannot see what it refuses — an unaligned
slice, too much VMEM, a kernel that cannot be partitioned (the
``custom_partitioning`` form of the fused lookup passed every
interpret-mode mesh test and was refused here: "Custom emitter for
CustomSPMDPartitioning not found") — so a few compiles at real widths
stay among the tests and guard every later PR at no chip time.

Nothing here runs, so nothing is said about results or times. The
kernels are called directly, ~20 s of Mosaic compile each for the fused
lookup; whole-program compiles (serve 32-iteration program, train step:
36-94 s each) live in the builder's scratch rehearsal, not here.

Rules this file keeps (the driver runs the suite under pytest-xdist):
the topology is described inside a module-scoped fixture, never at
import, in a ``skipif``, in ``parametrize`` or in ``conftest.py``; the
fixture is not ``autouse``; the persistent compilation cache is off
around the compiles (a TPU entry written without a chip cannot be read
back); no child process compiles; all such tests live in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

# raft_large: 4 levels, radius 4, 256-channel feature maps, convcorr1 to 256
LEVELS, RADIUS, FMAP_C, PROJ_C = 4, 4, 256, 256
TAPS = LEVELS * (2 * RADIUS + 1) ** 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compile with the persistent cache off, restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _serving_model(arch, bucket):
    """The model ``ServeConfig.preset("throughput")`` serves, on the
    deployment kernel, with its variables and one frame as shapes."""
    from raft_tpu.models import build_raft, zoo
    from raft_tpu.serve import ServeConfig

    cfg = zoo.CONFIGS[arch].replace(
        **ServeConfig.preset("throughput").model_overrides()
    )
    block = FusedLookupCorrBlock(
        cfg.corr_levels, cfg.corr_radius, dtype=jnp.bfloat16, interpret=False
    )
    model = build_raft(cfg, corr_block=block)
    image = jax.ShapeDtypeStruct((1,) + bucket + (3,), jnp.float32)
    variables = jax.eval_shape(
        lambda x: model.init(
            jax.random.PRNGKey(0), x, x, train=False, num_flow_updates=1
        ),
        image,
    )
    return cfg, block, model, variables, image


def _project_args(block, batch, h8, w8):
    """Abstract operands of one fused lookup+projection call at the
    (h8, w8) feature grid: the packed pyramid ``build_pyramid`` makes
    (real level shapes), centroids, and raft_large's convcorr1 weights."""
    fmap = jax.ShapeDtypeStruct((batch, h8, w8, FMAP_C), jnp.float32)
    pyramid = jax.eval_shape(block.build_pyramid, fmap, fmap)
    assert isinstance(pyramid, dict), "geometry not fusable: XLA fallback"
    return (
        pyramid,
        jax.ShapeDtypeStruct((batch, h8, w8, 2), jnp.float32),
        jax.ShapeDtypeStruct((1, 1, TAPS, PROJ_C), jnp.float32),
        jax.ShapeDtypeStruct((PROJ_C,), jnp.float32),
    )


@pytest.mark.parametrize(
    "h8,w8",
    [
        (55, 128),  # Sintel 440x1024: pow2 widths, 640-row tiles
        (47, 156),  # KITTI-pad 376x1248: chunked >128-lane gathers and the
                    # masked tail tile (7332 rows have no 8-aligned divisor)
        (136, 240),  # 1080p 1088x1920: three raw levels, 97 KB of blocks a
                     # row unpadded as the build leaves them (136 rows are
                     # whole row tiles, so levels 0-1 are read by window)
    ],
    ids=["sintel-440x1024", "kitti-376x1248", "hd1080-1088x1920"],
)
def test_lookup_xtap_forward_compiles(one_chip, h8, w8):
    """The deployment kernel (fused lookup + convcorr1, bf16 storage,
    y-dot in kernel) at the real level shapes of both eval geometries."""
    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=False
    )
    args = _on(one_chip, _project_args(block, 1, h8, w8))
    compiled = jax.jit(
        lambda p, c, k, b: block.index_project(p, c, k, b)
    ).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize(
    "arch,bucket,held",
    [
        # radius 4: levels 0-1 raw volumes (whole tiles), 2-3 flats
        ("raft_large", (440, 1024), [(56, 128), (32, 128), (13, 32), (6, 16)]),
        # radius 3: level 0 raw, 1-3 flats
        ("raft_small", (440, 1024), [(56, 128), (27, 64), (13, 32), (6, 16)]),
        # KITTI-pad: 47x156 grid, level 0 lane-padded to 256 by the build
        ("raft_large", (376, 1248), [(48, 256), (24, 128), (11, 39), (5, 19)]),
        # 1080p: levels 0-2 raw (level 2's 34x60 is 16 packed rows, over
        # the 4 a flat level may have), level 3 flat; 65,280 rows of
        # coordinates do not fit VMEM beside the blocks: blocked by tile
        ("raft_large", (1088, 1920), [(136, 256), (72, 128), (40, 128), (17, 30)]),
    ],
    ids=["raft_large-sintel", "raft_small-sintel", "raft_large-kitti",
         "raft_large-hd1080"],
)
def test_lookup_xtap_reads_pool_state_in_place(one_chip, arch, bucket, held):
    """The lookup + projection lowered from operands as the slot pool
    holds them (2 slots; leaf shapes from ``state_spec`` of the serving
    model, layouts the compiler's defaults): the state's buffers go to
    the Mosaic call as they are — no relayout ``copy`` of a level, no
    level-sized temporary. (Held in the pooled shapes, ``[.., 55, 128,
    1]`` and ``[.., 27, 64, 1]``, the default layout tiles over Q, and
    every pool tick began with a copy of each raw level: half of
    raft_large's tick, PERF.md, PR 29.)"""
    from raft_tpu.serve.pool import state_spec

    slots = 2
    h8, w8 = bucket[0] // 8, bucket[1] // 8
    cfg, block, model, variables, _ = _serving_model(arch, bucket)
    pyramid = state_spec(model, variables, slots, bucket)["pyramid"]
    q = h8 * w8
    assert [v.shape for v in pyramid["levels"]] == [
        (slots, q, hl, wl, 1) for hl, wl in held
    ]

    def lookup(pyramid, cents, kernel, bias):
        # iterate_step's view of the state: slots folded into the rows
        pyramid = jax.tree.map(
            lambda v: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:]),
            pyramid,
        )
        return block.index_project(pyramid, cents, kernel, bias)

    taps = cfg.corr_levels * (2 * cfg.corr_radius + 1) ** 2
    compiled = jax.jit(lookup).lower(*_on(one_chip, (
        pyramid,
        jax.ShapeDtypeStruct((slots, h8, w8, 2), jnp.float32),
        jax.ShapeDtypeStruct((1, 1, taps, PROJ_C), jnp.float32),
        jax.ShapeDtypeStruct((PROJ_C,), jnp.float32),
    ))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # a level, whole or with the slots folded in (not the 3-D flats,
    # which XLA may prefetch as they are)
    level = re.compile(rf" = bf16\[({slots},{q}|{slots * q}),\d+,\d+[,\]]")
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if " copy(" in line and level.search(line)
    ]
    assert not copies, copies
    one_slot_level0 = q * h8 * w8 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_slot_level0


@pytest.mark.parametrize(
    "arch,bucket,slots,iters,windows,parent",
    [
        # (argument, temporary, output) bytes of the parent's step program
        # (PERF.md, PR 32: compiled here for a described v5e, no chip)
        ("raft_large", (440, 1024), 16, 32, (640, (56, 32), (56, 32)),
         (2_866_390_016, 117_085_184, 29_746_688)),
        ("raft_small", (440, 1024), 16, 32, (640, (56,), (56,)),
         (2_708_046_336, 116_730_368, 22_537_728)),
        ("raft_large", (1088, 1920), 2, 20, (640, (32, 24, 40), (136, 72, 40)),
         (6_595_431_424, 148_333_568, 17_271_296)),
    ],
    ids=["raft_large-sintel", "raft_small-sintel", "raft_large-hd1080"],
)
def test_step_program_reads_windows_in_the_parents_footprint(
    one_chip, arch, bucket, slots, iters, windows, parent
):
    """The three cells' step programs since the lookup reads a level by
    window where the window is under half of it (PR 34: levels 0 and 1
    at 1088x1920, none at 440x1024): the resident state is the parent's to the byte (same
    argument bytes: no level re-padded, nothing admission or ``insert``
    has to learn), the temporaries stay within 10% of the parent's, and
    there is one program with one Mosaic call in it — no second instance
    under a conditional, no fallback program. That this compiles is the
    proof that the tile the one VMEM rule picks with the window blocks
    counted (640 rows at 1088x1920, where whole levels allowed 408) fits
    the call's limit: Mosaic refuses a body that does not."""
    from raft_tpu.serve.pool import PoolPrograms, state_spec

    _, block, model, variables, _ = _serving_model(arch, bucket)
    state = state_spec(model, variables, slots, bucket, resid_len=iters)
    tile, heights, rows = windows
    plan = block.lookup_plan(
        jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                (v.shape[0] * v.shape[1],) + v.shape[2:], v.dtype
            ),
            state["pyramid"],
        ),
        bucket[1] // 8,
    )
    assert (plan.tile, plan.heights, plan.rows) == (tile, heights, rows)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    progs = PoolPrograms(model, resid_len=iters)
    compiled = progs.step.lower(
        *_on(one_chip, (variables, state, scalar, count, count))
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " conditional(" not in text
    mem = compiled.memory_analysis()
    arg_bytes, temp_bytes, out_bytes = parent
    assert mem.argument_size_in_bytes == arg_bytes
    assert mem.temp_size_in_bytes <= 1.1 * temp_bytes
    # the token carries the two row counts: eight bytes, under the
    # allocator's granule
    assert out_bytes <= mem.output_size_in_bytes <= out_bytes + 4096


def test_hd1080_admission_fits_beside_the_pool(one_chip):
    """The 1080p cell's admission at its capacity (2 slots, one pair at a
    time): ``pool_begin_pair``'s row and temporaries beside the resident
    state, and the insert that follows, stay inside one v5e's 16 GB —
    with the room ``pool_final`` (0.8 GB of temporaries) and the frames
    need. ``insert`` writes into the donated state: its output is the
    state's own buffers, and its only temporaries are row prefetches.
    A third slot would not fit (9.9 + 3.3 + 2.3 GB before anything
    else): PERF.md, PR 30."""
    from raft_tpu.serve.pool import PoolPrograms, state_spec

    slots, bucket = 2, (1088, 1920)
    _, _, model, variables, image = _serving_model("raft_large", bucket)
    progs = PoolPrograms(model, resid_len=20)
    rows = jax.eval_shape(progs.begin_pair, variables, image, image)
    state = state_spec(model, variables, slots, bucket, resid_len=20)
    begin = progs.begin_pair.lower(
        *_on(one_chip, (variables, image, image))
    ).compile().memory_analysis()
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
        # reached / the parent's, bytes (PERF.md, PR 31)
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
    topo, no_persistent_cache, arch, bucket, pairs, chips, ceiling, parent,
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
    device's program). A ceiling is what PR 31 reached plus 10%,
    far under half the parent's; the program's temporaries may not grow
    either. Read on jax 0.9.0 / libtpu 0.0.34: another compiler may split
    at other sizes, and these numbers are then to be read again, with
    ``FeatureEncoder``'s rule."""
    from raft_tpu.parallel.serve_shard import make_serve_mesh
    from raft_tpu.serve.pool import PoolPrograms

    _, _, model, variables, image = _serving_model(arch, bucket)
    images = jax.ShapeDtypeStruct((pairs,) + image.shape[1:], image.dtype)
    if chips == 1:
        mesh = None
        rep = rows = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = make_serve_mesh(chips, devices=topo.devices)
        rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    compiled = PoolPrograms(model, mesh=mesh, resid_len=20).begin_pair.lower(
        _on(rep, variables), _on(rows, images), _on(rows, images)
    ).compile()
    moved = _entry_bytes_written(compiled.as_text(), "feature_encoder")
    assert moved <= ceiling and (moved > 0) == (parent > 0), moved
    assert ceiling <= parent / 2
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


def test_lookup_xtap_partitions_over_four_chips(topo, no_persistent_cache):
    """The same kernel under a 4-device data mesh at the training crop's
    level shapes (368x768, global batch 8): traced under the mesh it
    shard_maps over the query rows — one Mosaic call on per-shard shapes,
    and no all-gather of the volume."""
    from raft_tpu.parallel import make_mesh, traced_under

    mesh = make_mesh(space=1, devices=topo.devices)
    assert mesh.devices.shape == (4, 1)
    row = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=False
    )
    pyramid, cents, kernel, bias = _project_args(block, 8, 46, 96)
    compiled = jax.jit(
        traced_under(
            mesh, lambda p, c, k, b: block.index_project(p, c, k, b)
        ),
        out_shardings=row,
    ).lower(
        _on(row, pyramid), _on(row, cents), _on(rep, kernel), _on(rep, bias)
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "num_partitions=4" in text
    q = 8 * 46 * 96
    assert f"bf16[{q // 4},46,96" in text, "no per-shard level-0 block"
    assert f"bf16[{q},46,96" not in text, "global-q volume: not partitioned"


def test_lookup_xtap_unsharded_under_mesh_is_refused(topo, no_persistent_cache):
    """No quiet replicated kernel on the chip: a multi-device program
    that reaches the kernel WITHOUT the ambient mesh (not traced under
    ``traced_under``) is an error, not a silent all-gather."""
    from raft_tpu.parallel import make_mesh

    mesh = make_mesh(space=1, devices=topo.devices)
    row = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=False
    )
    pyramid, cents, kernel, bias = _project_args(block, 8, 46, 96)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(
            lambda p, c, k, b: block.index_project(p, c, k, b)
        ).lower(
            _on(row, pyramid), _on(row, cents), _on(rep, kernel),
            _on(rep, bias),
        )


# -- the video deployment (PR 36): stream programs and the 32-slot pool --------

VIDEO_BUCKET = (440, 1024)


@pytest.fixture(scope="module")
def video_model():
    return _serving_model("raft_large", VIDEO_BUCKET)


@pytest.mark.parametrize("rung", [1, 8])
def test_stream_admission_takes_the_encoders_own_dtype(
    one_chip, video_model, rung
):
    """``encode_frame`` and ``pool_begin_features`` at the cell's bucket,
    at the smallest and the largest admit rung, compiled for one v5e:
    the feature map and the context output leave the encode program in
    bf16 (the ``throughput`` preset's conv dtype) and ``pool_begin_features``
    is lowered for exactly those operands — what ``aot.program_specs``
    does since PR 36, where the engine used to hand it fetched float32
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


def test_step_program_on_a_32_slot_sintel_pool_blocks_its_coordinates(
    one_chip, video_model
):
    """The video cell's pool is twice as deep as the Sintel cells': the
    lookup's coordinate operand, ``f32[32 x 7040, 2]``, is 115 MB
    lane-padded against the call's 100 MiB, so ``_plan_tile`` blocks it
    by tile (640 rows) — by itself since PR 30, never compiled at this
    shape before PR 36. One Mosaic call, no level copied, the state read
    in place (temporaries far under a level)."""
    from raft_tpu.serve.pool import PoolPrograms, state_spec

    slots = 32
    _, block, model, variables, _ = video_model
    state = state_spec(model, variables, slots, VIDEO_BUCKET, resid_len=32)
    plan = block.lookup_plan(
        jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                (v.shape[0] * v.shape[1],) + v.shape[2:], v.dtype
            ),
            state["pyramid"],
        ),
        VIDEO_BUCKET[1] // 8,
    )
    assert plan.tile == 640 and plan.coords_blocked
    assert plan.heights == plan.rows == (56, 32)       # levels read whole
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = PoolPrograms(model, resid_len=32).step.lower(
        *_on(one_chip, (variables, state, scalar, count, count))
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    # 16 slots are 2.87 GB of arguments (the test above): twice that
    assert 5.6e9 < mem.argument_size_in_bytes < 5.9e9
    assert mem.temp_size_in_bytes < 0.3e9


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
