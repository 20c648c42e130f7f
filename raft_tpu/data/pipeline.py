"""Input pipeline: sharded, prefetched, augmented batches for training.

Replaces the reference's serial host-blocking loading (SURVEY.md §3.3) with
a pipeline that keeps the TPU fed:

  * deterministic epoch shuffling from a seed (restartable: the pipeline
    state is just ``(seed, step)``);
  * per-host index sharding — each process loads only its slice of the
    global batch (``jax.process_index()``), the standard multi-host JAX
    feeding pattern;
  * a thread pool for parallel decode+augment (cv2/numpy release the GIL);
  * bounded-queue prefetch so host I/O overlaps device compute;
  * optional device_put with the canonical ``(data, space)`` batch sharding.
"""

from __future__ import annotations

import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional

import numpy as np

from raft_tpu.data.augment import FlowAugmentor
from raft_tpu.data.datasets import FlowDataset
from raft_tpu.utils.faults import BadSampleBudgetError, DataFaultPolicy
from raft_tpu.utils.prefetch import prefetch

__all__ = ["TrainPipeline", "collate", "normalize_images"]


class _WindowStaging:
    """Rotating preallocated host buffers for stacked batch windows.

    The serve engine's ``_StagingPool`` pattern applied to training: ``k``
    consecutive host batches are copied row-by-row into ONE preallocated
    ``(k, ...)``-per-key buffer set, replacing a per-window
    ``np.stack`` allocation — and because ``jax.device_put`` of the window
    is asynchronous, ``slots >= prefetch_depth + 1`` rings guarantee a
    buffer is never rewritten while a previous transfer could still be
    copying from it.

    That holds where the transfer COPIES (an accelerator). On the CPU
    backend ``jax.device_put`` of an aligned numpy array is zero-copy —
    the device array IS the host buffer, for as long as it lives — so a
    slot :meth:`transferred` there is retired: its memory now belongs to
    the device array and the slot allocates anew on its next turn.
    """

    def __init__(self, slots: int):
        self._slots = max(2, int(slots))
        self._rings: Dict[tuple, List[Optional[Dict[str, np.ndarray]]]] = {}
        self._idx: Dict[tuple, int] = {}

    def stack(self, batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
        k = len(batches)
        first = batches[0]
        sig = (k,) + tuple(
            (key, v.shape, str(v.dtype)) for key, v in sorted(first.items())
        )
        ring = self._rings.get(sig)
        if ring is None:
            ring = self._rings[sig] = [None] * self._slots
            self._idx[sig] = 0
        i = self._idx[sig]
        self._idx[sig] = (i + 1) % len(ring)
        buf = ring[i]
        if buf is None:  # first turn, or retired on its last one
            buf = ring[i] = {
                key: np.empty((k,) + v.shape, v.dtype)
                for key, v in first.items()
            }
        for j, b in enumerate(batches):
            for key, v in b.items():
                buf[key][j] = v
        return buf

    def transferred(self, buf: Dict[str, np.ndarray], out) -> None:
        """Tell the ring that ``buf`` went to the device as ``out``. Where
        any leaf of ``out`` lives on a CPU-platform device the transfer
        may have been zero-copy, so ``buf``'s memory is given away: the
        slot that held it allocates anew on its next turn. A ``buf`` that
        is not a ring slot is ignored."""
        import jax

        if not any(
            d.platform == "cpu"
            for leaf in jax.tree.leaves(out)
            for d in leaf.devices()
        ):
            return
        for ring in self._rings.values():
            for i, slot in enumerate(ring):
                if slot is buf:
                    ring[i] = None


def normalize_images(batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """uint8-range images -> [-1, 1] float32 (model input contract)."""
    out = dict(batch)
    for k in ("image1", "image2"):
        out[k] = batch[k].astype(np.float32) / 255.0 * 2.0 - 1.0
    return out


def collate(samples) -> Dict[str, np.ndarray]:
    # "sparse" is a per-sample augmentation marker, not batch data
    keys = [k for k in samples[0].keys() if k != "sparse"]
    return {
        k: np.stack([np.asarray(s[k], np.float32) for s in samples]) for k in keys
    }


class TrainPipeline:
    """Infinite iterator of training batches.

    Args:
        dataset: index-able ``FlowDataset``.
        global_batch_size: batch size across all hosts.
        augmentor: per-sample augmentation (None = raw center-crop-free
            samples; dataset resolutions must then be uniform).
        seed: shuffling/augmentation seed (same on every host).
        mesh: if given, batches are device_put with the canonical batch
            sharding (global arrays built from process-local data).
        start_step: resume point — skips the RNG streams, not the data.
        fault_policy: what a failing ``dataset[idx]`` does to the run
            (``utils.faults.DataFaultPolicy``). None = propagate, the
            fail-fast pre-policy behavior. With ``mode='skip'`` bad
            samples are quarantined (bounded budget, transient OSErrors
            retried with backoff) and their batch slots refilled from the
            index stream; ``counters`` surfaces ``data/skipped`` /
            ``data/retries`` for the trainer's log boundary.
        window_size: with ``window_size=k > 1`` the iterator yields
            stacked batch *windows* — every leaf gains a leading ``(k,)``
            axis holding ``k`` consecutive batches (identical data order
            to ``k`` per-step draws) — staged through preallocated
            rotating host buffers and transferred with ONE async
            ``jax.device_put`` per window, for the fused multi-step train
            dispatch (``train.step.make_window_step``). ``step``
            bookkeeping still counts per-batch steps.
    """

    def __init__(
        self,
        dataset: FlowDataset,
        global_batch_size: int,
        *,
        augmentor: Optional[FlowAugmentor] = None,
        seed: int = 0,
        num_workers: int = 4,
        prefetch_depth: int = 2,
        mesh=None,
        start_step: int = 0,
        fault_policy: Optional[DataFaultPolicy] = None,
        window_size: int = 1,
    ):
        import jax

        if window_size < 1:
            raise ValueError(f"window_size must be >= 1, got {window_size}")
        self.dataset = dataset
        self.augmentor = augmentor
        self.seed = seed
        self.mesh = mesh
        self.prefetch_depth = prefetch_depth
        self.num_workers = num_workers
        self.step = start_step
        self.fault_policy = fault_policy
        self.window_size = window_size
        self._staging = (
            _WindowStaging(prefetch_depth + 1) if window_size > 1 else None
        )
        self.counters: Dict[str, int] = {"data/skipped": 0, "data/retries": 0}
        self.quarantined: set = set()
        self._fault_lock = threading.Lock()

        self.process_count = jax.process_count()
        self.process_index = jax.process_index()
        if global_batch_size % self.process_count:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{self.process_count} processes"
            )
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // self.process_count

    def _index_stream(self) -> Iterator[int]:
        """Deterministic infinite shuffled index stream, host-sharded."""
        n = len(self.dataset)
        epoch = 0
        # fast-forward for resume
        consumed = self.step * self.global_batch_size
        while True:
            rng = np.random.default_rng((self.seed, epoch))
            perm = rng.permutation(n)
            if consumed >= len(perm):
                consumed -= len(perm)
                epoch += 1
                continue
            for i in perm[consumed:]:
                yield int(i)
            consumed = 0
            epoch += 1

    def _quarantine_sample(self, idx: int, exc: BaseException) -> None:
        """Record a permanently bad sample; raise once over budget."""
        policy = self.fault_policy
        with self._fault_lock:
            new = idx not in self.quarantined
            self.quarantined.add(idx)
            self.counters["data/skipped"] += 1
            n_bad = len(self.quarantined)
        if new:
            print(
                f"data: quarantined sample {idx} "
                f"({type(exc).__name__}: {exc}); {n_bad} bad so far"
            )
        if n_bad > policy.max_bad_samples:
            raise BadSampleBudgetError(
                f"{n_bad} distinct bad samples exceed the budget of "
                f"{policy.max_bad_samples} (last: index {idx}: "
                f"{type(exc).__name__}: {exc})"
            ) from exc

    def _load_sample(self, idx: int):
        """``dataset[idx]`` under the fault policy; None = skipped.

        Transient errors retry with capped exponential backoff; parse
        errors fail fast (the bytes on disk will not change). Quarantined
        indices skip without touching storage again.
        """
        policy = self.fault_policy
        if policy is None:
            return self.dataset[idx]
        if idx in self.quarantined:
            with self._fault_lock:
                self.counters["data/skipped"] += 1
            return None
        delay = policy.base_delay
        attempt = 0
        while True:
            try:
                return self.dataset[idx]
            except policy.deterministic as e:
                if policy.mode == "raise":
                    raise
                self._quarantine_sample(idx, e)
                return None
            except policy.transient as e:
                if attempt >= policy.max_retries:
                    if policy.mode == "raise":
                        raise
                    self._quarantine_sample(idx, e)
                    return None
                attempt += 1
                with self._fault_lock:
                    self.counters["data/retries"] += 1
                time.sleep(
                    min(delay, policy.max_delay) * (1.0 + 0.25 * random.random())
                )
                delay *= 2.0

    def _make_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        stream = self._index_stream()
        pool = ThreadPoolExecutor(max_workers=self.num_workers)

        def load_one(args):
            step, slot, idx = args
            sample = self._load_sample(idx)
            if sample is None:
                return None
            if self.augmentor is not None:
                rng = np.random.default_rng((self.seed, 1 << 20, step, slot))
                sample = self.augmentor(rng, sample)
            return sample

        step = self.step
        try:
            while True:
                # Global index order is identical on every host; each host
                # takes its contiguous slice of the global batch.
                global_idx = [
                    next(stream) for _ in range(self.global_batch_size)
                ]
                lo = self.process_index * self.local_batch_size
                work = [
                    (step, lo + j, global_idx[lo + j])
                    for j in range(self.local_batch_size)
                ]
                samples = list(pool.map(load_one, work))
                # Fault policy: refill skipped slots from the tail of the
                # host-local view of the stream. Replacement draws shift
                # only this host's future slices — hosts may then overlap
                # samples (a sampling-distribution wobble), but batch
                # shapes and collectives stay in lockstep.
                for j, s in enumerate(samples):
                    while s is None:
                        if len(self.quarantined) >= len(self.dataset):
                            raise BadSampleBudgetError(
                                "every sample in the dataset is quarantined"
                            )
                        s = load_one((step, lo + j, next(stream)))
                    samples[j] = s
                batch = normalize_images(collate(samples))
                yield batch
                step += 1
        finally:
            pool.shutdown(wait=False, cancel_futures=True)

    def _shardings(self, batch, *, window: bool):
        """Per-leaf NamedSharding tree for a batch or a stacked window."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raft_tpu.parallel.mesh import BATCH_SPEC, WINDOW_BATCH_SPEC

        def spec(v):
            if window:
                return WINDOW_BATCH_SPEC if v.ndim >= 4 else P(None, "data")
            return BATCH_SPEC if v.ndim >= 3 else P("data")

        return {k: NamedSharding(self.mesh, spec(v)) for k, v in batch.items()}

    def _to_device(self, batch, *, window: bool = False):
        """Transfer a whole batch tree in ONE host call.

        Single-process: one ``jax.device_put`` of the tree with a matching
        tree of shardings — one async transfer enqueue instead of one per
        leaf. Multi-host global arrays still build per leaf
        (``make_array_from_process_local_data`` takes one array at a
        time). Windows are transferred even without a mesh so the H2D copy
        of window ``n+1`` overlaps window ``n``'s compute.
        """
        import jax

        if self.mesh is None:
            if not window:
                return batch
            out = jax.device_put(batch)
        else:
            shardings = self._shardings(batch, window=window)
            if self.process_count > 1:
                out = {
                    k: jax.make_array_from_process_local_data(shardings[k], v)
                    for k, v in batch.items()
                }
            else:
                out = jax.device_put(batch, shardings)
        if window and self._staging is not None:
            self._staging.transferred(batch, out)
        return out

    def _make_windows(self) -> Iterator[Dict[str, np.ndarray]]:
        """Stack ``window_size`` consecutive batches into one staged tree."""
        it = self._make_batches()
        while True:
            host = [next(it) for _ in range(self.window_size)]
            yield self._staging.stack(host)

    def __iter__(self):
        k = self.window_size
        if k == 1:
            source = (self._to_device(b) for b in self._make_batches())
        else:
            source = (
                self._to_device(w, window=True) for w in self._make_windows()
            )
        for batch in prefetch(source, self.prefetch_depth):
            self.step += k
            yield batch
