"""Test configuration: force CPU with 8 virtual devices.

Parity/unit tests run on CPU for determinism and speed; the virtual 8-device
topology exercises the same `jax.sharding.Mesh` code paths as a real TPU slice
(standard JAX practice via `--xla_force_host_platform_device_count`). TPU
benchmarks live in `bench.py`, not the test suite.

`JAX_PLATFORMS=cpu` pins the test process to CPU; it is set here, before
jax is imported, so a bare `pytest` run never reaches for an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)



# Under `-p xdist --dist loadfile` each worker takes whole files, in the
# order they are queued. xdist queues files by their number of tests, so
# files of a few long tests (compiles for a described TPU, trainer runs)
# came last and the run ended on them with the other workers idle. Here
# the first file of each worker is still xdist's (the most tests: it runs
# in a fresh process, which several serve modules that wire a
# process-global compile cache count on), and the rest go longest first —
# the tier-1 run's own per-file seconds, six workers on an 8-core host —
# then by count. Tests within a file keep their order.
_LONGEST_FIRST = (
    "test_train_window.py", "test_stability.py", "test_hd_frames.py",
    "test_pallas.py", "test_serve_zzz_rollout.py",
    "test_chip_compile_admission.py", "test_serve_worker.py",
    "test_chip_compile.py", "test_multichip.py", "test_serve_ztrace.py",
    "test_serve_zzwire.py", "test_serve_pool.py", "test_train.py",
    "test_chip_compile_kernel.py", "test_faults.py",
    "test_chip_compile_step.py", "test_trainer_scripts.py",
    "test_serve_router.py", "test_observability.py", "test_serve.py",
    "test_instance_norm.py", "test_serve_xport.py", "test_multihost.py",
)


def pytest_configure(config):
    if getattr(config.option, "dist", "no") == "loadfile":
        config.option.loadscopereorder = False  # the order below is kept


def pytest_collection_modifyitems(config, items):
    count = {}
    for item in items:
        count[item.path.name] = count.get(item.path.name, 0) + 1
    by_count = sorted(count, key=lambda name: -count[name])  # stable
    # an xdist worker's collection is the one the run is queued from
    workers = getattr(config, "workerinput", {}).get("workercount", 0)
    first = {name: i for i, name in enumerate(by_count[:workers])}
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: (
        first.get(item.path.name, len(first)),
        rank.get(item.path.name, len(rank)), -count[item.path.name],
        item.path.name,
    ))
