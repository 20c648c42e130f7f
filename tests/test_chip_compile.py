"""The lookup kernel as the slot pool holds its operands, over a mesh,
and an admission cohort's pyramid, compiled for a described TPU v5e — no
chip. The rules every ``tests/test_chip_compile*.py`` file keeps, and why
they are four files, are in ``tests/chip_compile_common.py``."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock
# the fixtures are found by name in the module that uses them
from tests.chip_compile_common import (  # noqa: F401
    LEVELS, RADIUS, FMAP_C, PROJ_C, _on, _serving_model, _project_args,
    topo, no_persistent_cache, one_chip,
)


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


# -- an admission cohort's pyramids, one pair at a time ------------------------


def _program(hlo_text):
    """A compiled program's computations without their metadata: what
    two programs compiled from different source lines share when they
    are the same program."""
    lines = hlo_text.splitlines()
    first = next(
        i for i, l in enumerate(lines) if l.startswith(("%", "ENTRY"))
    )
    return [re.sub(r", metadata=\{[^}]*\}", "", l) for l in lines[first:]]


def _pooled_layouts(hlo_text):
    """Shape and ``minor_to_major`` of every ``reduce-window`` (the
    pyramid's 2x2 poolings), memory space left out."""
    return re.findall(
        r"= (\w+\[[\d,]*\])\{([\d,]+)[^}]*\} reduce-window\(", hlo_text
    )


def _writes_rows(hlo_text, rows):
    """``reshape`` / ``pad`` instructions whose result has ``rows``
    leading rows."""
    return re.findall(
        rf"= \w+\[{rows},[\d,]*\]\S* (?:reshape|pad)\(", hlo_text
    )


@pytest.fixture(scope="module")
def pyramid_program(one_chip):
    """The pyramid a pool slot holds, as ``RAFT.begin_refinement`` builds
    it (``build_resident_pyramid``) — or, ``batched``, as every admission
    built it before cohorts were mapped — compiled for one described
    v5e from bf16 raft_large feature maps (~3 s each, memoised)."""
    from raft_tpu.models.raft import build_resident_pyramid

    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=False
    )

    def pyramid(fmap1, fmap2):
        return build_resident_pyramid(block, fmap1, fmap2)

    def batched(fmap1, fmap2):
        b, h8, w8 = fmap1.shape[:3]
        return jax.tree.map(
            lambda lvl: lvl.reshape((b, h8 * w8) + lvl.shape[1:]),
            block.resident_pyramid(block.build_pyramid(fmap1, fmap2)),
        )

    batched.__name__ = "pyramid"  # one program name on both sides
    memo = {}

    def get(pairs, grid, as_batched=False):
        key = pairs, grid, as_batched
        if key not in memo:
            fmap = jax.ShapeDtypeStruct(
                (pairs,) + grid + (FMAP_C,), jnp.bfloat16, sharding=one_chip
            )
            memo[key] = jax.jit(batched if as_batched else pyramid).lower(
                fmap, fmap
            ).compile()
        return memo[key]

    return get


@pytest.mark.parametrize(
    "pairs,grid",
    [(1, (55, 128)), (2, (55, 128)), (4, (55, 128)), (2, (47, 156))],
    ids=["sintel-1", "sintel-2", "sintel-4", "kitti-2"],
)
def test_a_cohorts_pyramid_compiles_as_one_pairs(pyramid_program, pairs, grid):
    """At one pair the build is the batched one, the same program to the
    instruction. At two and four pairs the batched build writes its
    volume flat, materialises a reshape of it to ``k·Q`` rows and pools
    the rows across sublanes (Sintel: ``{2,1,0,3}`` against one pair's
    ``{2,0,1,3}``); built a pair at a time, in one ``while``, every
    pooling has the layout and shape one pair's program gives it, no
    ``reshape`` or ``pad`` writes a ``k·Q``-row volume, and the
    temporaries are one pair's plus at most the stacked outputs (at
    KITTI's 47x156 both forms pool with the queries on the lanes; the
    batched one still writes ``k·Q``-row volumes). Read on jax 0.9.0 /
    libtpu 0.0.34."""
    one = pyramid_program(1, grid)
    if pairs == 1:
        assert _program(one.as_text()) == _program(
            pyramid_program(1, grid, as_batched=True).as_text()
        )
        return
    got = pyramid_program(pairs, grid)
    text = got.as_text()
    rows = pairs * grid[0] * grid[1]
    assert text.count(" while(") == 1
    assert _pooled_layouts(text) == _pooled_layouts(one.as_text())
    assert not _writes_rows(text, rows)
    temp = got.memory_analysis().temp_size_in_bytes
    assert temp <= (
        one.memory_analysis().temp_size_in_bytes
        + got.memory_analysis().output_size_in_bytes
    ), temp
    # what the batched build of the same pairs does
    flat = pyramid_program(pairs, grid, as_batched=True).as_text()
    assert _writes_rows(flat, rows)
    if grid == (55, 128):  # at KITTI's width both pool queries on lanes
        assert _pooled_layouts(flat) != _pooled_layouts(one.as_text())


def test_a_cohorts_pyramid_maps_each_devices_pairs(
    topo, no_persistent_cache, pyramid_program
):
    """Four pairs on a serve mesh of two: one ``while`` a device over its
    own two pairs, one pair's poolings, and no collective — no level is
    gathered from the other device."""
    from raft_tpu.models.raft import build_resident_pyramid
    from raft_tpu.parallel.mesh import traced_under
    from raft_tpu.parallel.serve_shard import make_serve_mesh

    mesh = make_serve_mesh(2, devices=topo.devices)
    rows = NamedSharding(mesh, P("data"))
    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=False
    )
    fmap = jax.ShapeDtypeStruct((4, 55, 128, FMAP_C), jnp.bfloat16,
                                sharding=rows)
    text = jax.jit(
        traced_under(
            mesh, lambda a, b: build_resident_pyramid(block, a, b)
        ),
        out_shardings=rows,
    ).lower(fmap, fmap).compile().as_text()
    assert "num_partitions=2" in text
    assert text.count(" while(") == 1
    for op in ("all-gather", "all-to-all", "collective-permute", "all-reduce"):
        assert op not in text, op
    assert "bf16[2,7040,56,128,1]" in text  # a device's two slots of level 0
    assert _pooled_layouts(text) == _pooled_layouts(
        pyramid_program(1, (55, 128)).as_text()
    )
