"""Device-mesh construction and sharding helpers.

The reference has no distribution at all (SURVEY.md §2.5); this module is the
TPU-native communication backend that replaces what would be NCCL/MPI in
CUDA-land: a ``jax.sharding.Mesh`` over the slice, ``NamedSharding``
annotations, and XLA-compiled collectives over ICI/DCN.

Axes:
  * ``data``  — batch (data parallelism; gradient all-reduce over ICI).
  * ``space`` — image-height spatial sharding (the sequence-parallel analog
    for this model class, SURVEY.md §5.7): GSPMD partitions the convolutions
    with halo exchanges and shards the quadratic correlation volume's query
    axis, so very-high-resolution pairs fit when one chip's HBM can't hold
    the ``(h·w)²`` volume.

Multi-host: call :func:`initialize_distributed` first on each host; meshes
here are built over ``jax.devices()`` (all hosts), and per-host input
pipelines should feed ``jax.process_index()``-local shards
(`make_array_from_process_local_data`).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "initialize_distributed",
    "make_mesh",
    "batch_sharding",
    "replicated",
    "shard_batch",
    "traced_under",
    "window_batch_sharding",
    "BATCH_SPEC",
    "WINDOW_BATCH_SPEC",
]

# Canonical PartitionSpec for flow-training batches (NHWC images + NHW2 flow):
# batch over `data`, H over `space` (identity when the mesh axis has size 1).
BATCH_SPEC = P("data", "space")

# Stacked batch windows (train.step.make_window_step): the leading window
# axis is the scan axis — every device sees every step of the window, so it
# stays unsharded; batch/height shard exactly as per-step batches.
WINDOW_BATCH_SPEC = P(None, "data", "space")


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """`jax.distributed.initialize` wrapper; no-op for single-process runs."""
    if num_processes is None and coordinator_address is None:
        return  # single-process (possibly multi-chip) — nothing to do
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    data: Optional[int] = None,
    space: int = 1,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``(data, space)`` mesh over the given (default: all) devices.

    ``data=None`` uses every remaining device for data parallelism.

    Device placement is topology-aware: on real TPU slices the grid comes
    from ``jax.experimental.mesh_utils.create_device_mesh``, which reads the
    slice's physical ICI coordinates so that (a) the innermost ``space`` axis
    lands on physically adjacent chips (halo exchanges ride neighbor ICI
    links) and (b) the ``data`` all-reduce maps onto torus rings instead of
    whatever order ``jax.devices()`` happens to enumerate. On virtual/CPU
    device sets (tests, the host-platform dryrun) ``mesh_utils`` has no
    topology to read and we fall back to a plain row-major reshape —
    placement is meaningless there. On TPU devices a ``mesh_utils`` failure
    raises: no quiet enumeration-order mesh on the chip.
    """
    devs = list(devices if devices is not None else jax.devices())
    if data is None:
        if len(devs) % space:
            raise ValueError(f"{len(devs)} devices not divisible by space={space}")
        data = len(devs) // space
    n = data * space
    if n > len(devs):
        raise ValueError(f"mesh {data}x{space} needs {n} devices, have {len(devs)}")
    from jax.experimental import mesh_utils

    try:
        grid = mesh_utils.create_device_mesh((data, space), devices=devs[:n])
    except Exception:
        # CPU/virtual device sets have no topology for mesh_utils to
        # factor — sequential order is the only assignment there. On TPU
        # devices enumeration order would silently degrade collective/halo
        # placement, so the failure propagates instead.
        if any(d.platform == "tpu" for d in devs[:n]):
            raise
        grid = np.asarray(devs[:n]).reshape(data, space)
    return Mesh(grid, ("data", "space"))


def traced_under(mesh: Optional[Mesh], fn):
    """``fn``, traced with ``mesh`` as the ambient (abstract) mesh.

    The sharded step and serve programs wrap their bodies in this so
    code deep inside the model that must know the mesh at trace time —
    the fused lookup kernel ``shard_map``s itself over it
    (``kernels.lookup_xtap._partitioned_xtap``) — can read it from
    ``jax.sharding.get_abstract_mesh()``. The context is entered INSIDE
    the traced function, so ``jax.jit(...)``'s own ``lower``/cache
    surface is untouched. ``mesh=None`` returns ``fn`` unchanged."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return inner


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for batch arrays: batch over `data`, height over `space`."""
    return NamedSharding(mesh, BATCH_SPEC)


def window_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for stacked ``(window, batch, H, ...)`` train windows."""
    return NamedSharding(mesh, WINDOW_BATCH_SPEC)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (parameters, optimizer state)."""
    return NamedSharding(mesh, P())


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    """Device-put a host batch with the canonical batch sharding.

    The whole tree moves through ONE ``jax.device_put`` call with a
    matching tree of shardings — one async transfer enqueue instead of
    one host call per leaf (the same optimization the training
    pipeline's ``_to_device`` landed in PR 5). Arrays keep their logical
    (global) shape; under multi-host, prefer building global arrays with
    ``jax.make_array_from_process_local_data`` in the input pipeline
    instead.
    """
    # (B, H, ...) arrays shard batch+height; (B,) / (B, K) batch only.
    shardings = {
        k: NamedSharding(mesh, BATCH_SPEC if np.ndim(v) >= 3 else P("data"))
        for k, v in batch.items()
    }
    return jax.device_put(dict(batch), shardings)
