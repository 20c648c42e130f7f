"""The three single-bucket cells' step programs and the mixed cell's
KITTI step, compiled for a described TPU v5e — no chip (rules:
``tests/chip_compile_common.py``)."""

import jax
import jax.numpy as jnp
import pytest

# the fixtures are found by name in the module that uses them
from tests.chip_compile_common import (  # noqa: F401
    KITTI_BUCKET, KITTI_SLOTS, _on, _serving_model, topo,
    no_persistent_cache, one_chip, kitti_model,
)


@pytest.mark.parametrize(
    "arch,bucket,slots,iters,windows,parent",
    [
        # (argument, temporary, output) bytes of the parent's step program
        # (PERF.md: compiled here for a described v5e, no chip)
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
    window where the window is under half of it (levels 0 and 1
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


@pytest.mark.parametrize("slots, tile", [(KITTI_SLOTS, 624), (13, 640)])
def test_kitti_step_compiles_at_its_tile(one_chip, kitti_model, slots, tile):
    """The mixed cell's KITTI pool at its planned 12 slots folds the slots
    into 12 x 7,332 = 87,984 query rows, which 624-row tiles divide; at
    13 slots the 95,316 rows have no 8-aligned divisor under the 640-row
    tile, so the kernel runs its cdiv grid with a masked last block —
    compiled here, since no cell's pool has such a count. Level 0 is 156
    wide, held in 256 lanes (the >128-lane chunked gather), and the
    coordinates, ~45 MB lane-padded at this depth, are blocked by tile.
    One Mosaic call, no conditional, the state read in place."""
    from raft_tpu.kernels.lookup_xtap import _pick_tile
    from raft_tpu.serve.pool import PoolPrograms, state_spec

    _, block, model, variables, _ = kitti_model
    state = state_spec(model, variables, slots, KITTI_BUCKET, resid_len=32)
    q = slots * 47 * 156
    assert state["pyramid"]["levels"][0].shape == (slots, 7332, 48, 256, 1)
    plan = block.lookup_plan(
        jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                (v.shape[0] * v.shape[1],) + v.shape[2:], v.dtype
            ),
            state["pyramid"],
        ),
        KITTI_BUCKET[1] // 8,
    )
    assert plan.tile == _pick_tile(q, plan.tile) == tile
    assert (q % tile == 0) == (slots == KITTI_SLOTS)   # masked tail at 13
    assert plan.coords_blocked and plan.heights == plan.rows == (48, 24)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = PoolPrograms(model, resid_len=32).step.lower(
        *_on(one_chip, (variables, state, scalar, count, count))
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " conditional(" not in text
    mem = compiled.memory_analysis()
    # 246 MB a slot
    assert 0.245e9 < mem.argument_size_in_bytes / slots < 0.25e9
    assert mem.temp_size_in_bytes < 0.3e9
