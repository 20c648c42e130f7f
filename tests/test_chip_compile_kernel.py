"""The fused lookup + projection alone at the real level shapes of the
Sintel, KITTI and 1080p geometries, and the video cell's 32-slot step
program, compiled for a described TPU v5e — no chip (rules:
``tests/chip_compile_common.py``)."""

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock
# the fixtures are found by name in the module that uses them
from tests.chip_compile_common import (  # noqa: F401
    LEVELS, RADIUS, VIDEO_BUCKET, _on, _project_args, topo,
    no_persistent_cache, one_chip, video_model,
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


def test_step_program_on_a_32_slot_sintel_pool_blocks_its_coordinates(
    one_chip, video_model
):
    """The video cell's pool is twice as deep as the Sintel cells': the
    lookup's coordinate operand, ``f32[32 x 7040, 2]``, is 115 MB
    lane-padded against the call's 100 MiB, so ``_plan_tile`` blocks it
    by tile (640 rows) — by itself, from the operand's size, and
    compiled here at this shape. One Mosaic call, no level copied, the state read
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
