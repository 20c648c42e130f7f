"""What the ``tests/test_chip_compile*.py`` files share: the main path's
programs compiled for a described TPU v5e — no chip.

The TPU compiler is installed with jax here and compiles for a topology
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
36-94 s each) are not made here.

Rules the files keep (the tier-1 suite runs under pytest-xdist): the
topology is described inside a module-scoped fixture (``topo`` here,
imported by name into each test file, so that each file has its own),
never at import, in a ``skipif``, in ``parametrize`` or in
``conftest.py``; the fixture is not ``autouse``; the persistent
compilation cache is off around the compiles (a TPU entry written
without a chip cannot be read back); no child process compiles. They
are four files of about equal compile time, so that ``--dist loadfile``
gives them to four workers: in one file they held one worker for most
of the tier-1 run's time limit. Each worker that takes one loads the TPU
library, which only one process at a time may do unless
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set, as the tier-1 command sets it:
under several workers without it, a second file's ``topo`` skips its
tests.
"""


import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


VIDEO_BUCKET = (440, 1024)


@pytest.fixture(scope="module")
def video_model():
    return _serving_model("raft_large", VIDEO_BUCKET)


KITTI_BUCKET, KITTI_SLOTS = (376, 1248), 12


@pytest.fixture(scope="module")
def kitti_model():
    return _serving_model("raft_large", KITTI_BUCKET)
