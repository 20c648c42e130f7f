#!/usr/bin/env python
"""Chip smoke: raft_large through ``ServeEngine`` and ``Trainer`` on one TPU.

The quickest proof that the system still starts on the chip. One process,
no child, no network, no dataset on disk — weights and data come from a
fixed seed. Run from the repo root:

    python chip_smoke.py              # one chip: serve phase + train phase
    python chip_smoke.py --multichip  # four chips: ONLY the mesh trainer
                                      # and the single-device run it is
                                      # compared with

*serve phase* — ``ServeConfig.preset("throughput")`` (bf16 convs + bf16
correlation on the fused Pallas lookup) at the 440x1024 Sintel bucket,
full raft_large width, 32 iterations: a few 436x1024 raw pairs through
``engine.submit``; every result non-degraded, finite, (436, 1024, 2), and
within a stated tolerance of the plain fp32 ``dense`` model (same weights,
same iterations, ``model.apply`` under ``jax.jit``).

*train phase* — ``Trainer`` at the paper crop 368x768, 12 iterations,
remat ``dots``, over an in-memory synthetic dataset: a few steps with
``corr_impl="dense"`` and the same steps with ``corr_impl="fused"`` /
``corr_dtype="bfloat16"``; losses finite, not rising (median of the
second half of the steps vs the first), first-step losses of the two
agreeing.

Any phase that raises fails the run (no try/except that carries on). It
refuses to run off a TPU. The last line printed is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

SEED = 0
# Weights are ``init_variables``'s (PRNGKey(0)) with ONE change: the flow
# head's output conv is scaled by this factor. A raw random-init RAFT is not
# contractive — each iteration moves the flow ~30 px, 32 of them reach
# ~1000 px fields (measured on the chip: mean |flow| 1000 px, and ANY two
# precisions then disagree by 37% of the field) — so comparing the bf16
# serve path with the fp32 reference would be a statement about chaos, not
# about the system. Scaled, the same net refines to Sintel-like flows (tens
# of px) and the comparison has a meaning; the parameter tree is unchanged.
FLOW_HEAD_SCALE = 0.01

# -- serve phase ------------------------------------------------------------
BUCKET = (440, 1024)
IMAGE_HW = (436, 1024)            # Sintel; replicate-padded to the bucket
SERVE_LADDER = (32, 20, 12)       # the engine's default anytime ladder
SERVE_ITERS = SERVE_LADDER[0]
SERVE_REQUESTS = 3
# |served - fp32 dense| over the 436x1024 flow field. Measured on one v5e
# (PR 23, three requests, mean |flow| ~10 px): mean 0.115-0.121 px = 1.16-
# 1.23% of the field's mean magnitude, max 2.1-2.2% of its max — the
# throughput preset's bf16 convs + bf16 correlation against fp32. The
# limits are ~2.5x what was measured.
SERVE_ABS_MEAN_TOL_PX = 0.3
SERVE_REL_MEAN_TOL = 0.03
SERVE_REL_MAX_TOL = 0.06

# -- train phase ------------------------------------------------------------
CROP = (368, 768)
TRAIN_ITERS = 12
# batch 2: memory_analysis() of the fused+bf16 / dense step at 368x768,
# 12 iterations, remat dots, compiled for v5e in the sandbox, reads
# 7.8 / 8.6 GiB of temporaries at batch 2 and 10.6 / 12.2 at batch 4
# (16.4+ at batch 6) — batch 2 leaves half of a 16 GB chip free
TRAIN_BATCH = 2
TRAIN_STEPS = 16
# AdamW moves every weight by ~lr per step whatever the gradient's scale,
# and the scaled flow-head kernel is ~2e-4 in magnitude: at 1e-4 the loss
# tripled by step 3 (chip run 2, PR 23); 1e-5 is a 5% nudge per step
TRAIN_LR = 1e-5
# Every step draws a fresh random crop/scale/colour of the same pairs, so
# single losses move ~+-15% with the batch alone: "not rising" compares the
# median of the second half of the steps with the median of the first
# (CPU rehearsal at a 128x256 crop: 30.7 -> 25.1 over 16 steps).
TRAIN_NOT_RISING_TOL = 0.05
# fused+bf16 vs dense fp32 at the first step (identical weights and batch):
# measured 2.4e-5 relative on one v5e (PR 23)
TRAIN_FIRST_LOSS_RTOL = 2e-3

# -- --multichip ------------------------------------------------------------
MESH_BATCH = 8                    # global; 2 per device on four chips
# The data_mesh=False twin holds all 8 pairs on ONE chip. With fp32 convs
# its step reads 14.8 (full remat) to 16.7 GiB (dots) of temporaries in
# rehearsal — too much — and cutting iterations does not help (the
# full-resolution encoder activations dominate, not the scan). bf16 convs
# with full remat read 10.5 GiB, so BOTH runs of this comparison use that
# supported TrainConfig; widths, crop and the 12 iterations stay.
MESH_CONFIG = dict(corr_impl="fused", corr_dtype="bfloat16",
                   compute_dtype="bfloat16", remat_policy=None)
MESH_STEPS = 3
MESH_LOSS_RTOL = 0.02


def log(**kw) -> None:
    print(json.dumps(kw), flush=True)


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits/misses (monitoring
    events), so a second run in the same command can show its hits."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


def peak_bytes() -> dict:
    """Peak device memory so far. On this runtime ``peak_bytes_in_use``
    counts live arrays only — a program with an 8 GiB temporary left it
    unmoved (PR 23 probe) — so ``peak_bytes_reserved`` is logged beside it."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return {k: int(stats.get(k, 0))
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


@functools.lru_cache(maxsize=1)
def smoke_weights():
    """The smoke's raft_large weights as a HOST tree (every consumer makes
    its own device copy: the train step donates its state). The tree is
    the same for every precision/corr_impl — those knobs cast activations
    and storage, never parameters."""
    import jax

    from raft_tpu.models import build_raft, init_variables, zoo

    host = jax.device_get(init_variables(build_raft(zoo.CONFIGS["raft_large"])))
    conv2 = host["params"]["update_block"]["flow_head"]["conv2"]
    for name in ("kernel", "bias"):
        conv2[name] = conv2[name] * FLOW_HEAD_SCALE
    return host


def smooth_pair(rng, hw, shift=3):
    """A raw [0, 255] frame pair: band-limited texture and its shifted,
    slightly perturbed copy (so the correlation volume has structure)."""
    import numpy as np

    h, w = hw
    coarse = rng.uniform(0, 255, (h // 8 + 2, w // 8 + 2, 3))
    im1 = np.kron(coarse, np.ones((8, 8, 1)))[:h, :w]
    im1 = im1 + rng.normal(0, 4.0, im1.shape)
    im2 = np.roll(im1, (shift, -shift), axis=(0, 1))
    im2 = im2 + rng.normal(0, 2.0, im2.shape)
    return (
        np.clip(im1, 0, 255).astype(np.float32),
        np.clip(im2, 0, 255).astype(np.float32),
    )


def assert_full_width_fused(model, variables) -> None:
    """Full raft_large width, and no quiet fallback on the smoke's shapes:
    the fused block runs the real kernel (not interpret mode) and packs
    this geometry's pyramid (``build_pyramid`` returns the packed dict —
    a list would be the XLA path)."""
    import jax

    n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
    assert n_params == 5_257_536, n_params
    block = model.corr_block
    assert block._interpret() is False, "fused kernel in interpret mode"
    for h, w in (BUCKET, CROP):
        fmap = jax.ShapeDtypeStruct((1, h // 8, w // 8, 256), jax.numpy.float32)
        packed = jax.eval_shape(block.build_pyramid, fmap, fmap)
        assert isinstance(packed, dict), (
            f"build_pyramid did not pack the {h}x{w} pyramid: XLA fallback"
        )


def assert_kernel_in(compiled_text: str, what: str) -> None:
    assert "tpu_custom_call" in compiled_text, (
        f"no Pallas kernel in the {what} program"
    )


def serve_phase(cache_dir: str) -> None:
    import jax
    import numpy as np

    from raft_tpu.inference import FlowEstimator
    from raft_tpu.models import build_raft, zoo
    from raft_tpu.serve import ServeConfig, ServeEngine
    from raft_tpu.serve.bucketing import BucketRouter

    t0 = time.monotonic()
    cfg = ServeConfig.preset(
        "throughput",
        buckets=(BUCKET,),
        ladder=SERVE_LADDER,
        max_batch=2,
        pool_capacity=4,
        stream_cache_size=0,          # pairwise traffic only: no stream programs
        queue_capacity=16,
        default_deadline_ms=600_000.0,  # nothing may degrade or expire
        high_watermark=1.0,
        warmup=True,
        compilation_cache_dir=cache_dir,
    )
    model, variables = zoo.raft_for_serving(cfg, arch="raft_large")
    assert_full_width_fused(model, variables)
    assert jax.tree.structure(variables) == jax.tree.structure(smoke_weights())
    variables = smoke_weights()

    engine = ServeEngine(model, variables, cfg).start()
    boot_s = time.monotonic() - t0
    try:
        stats = engine.stats()
        boot = stats["boot"]
        # the rung we expected: no artifact given, cache dir wired -> every
        # program compiled (or served by the persistent cache), none loaded
        assert boot["artifact_error"] is None, boot
        assert boot["source"] == "persistent_cache", boot
        assert boot["programs_loaded"] == 0, boot
        assert boot["programs_compiled"] == boot["programs_total"] > 0, boot
        step_keys = [k for k in engine._aot_execs if k[0] == "pool_step"]
        assert step_keys, sorted(engine._aot_execs)
        for k in step_keys:
            assert_kernel_in(engine._aot_execs[k].as_text(), f"served {k}")

        ref_model = build_raft(zoo.CONFIGS["raft_large"])  # fp32, dense
        ref_apply = jax.jit(
            lambda v, a, b: ref_model.apply(
                v, a, b, train=False, num_flow_updates=SERVE_ITERS,
                emit_all=False,
            )
        )

        rng = np.random.default_rng(SEED)
        pairs = [smooth_pair(rng, IMAGE_HW) for _ in range(SERVE_REQUESTS)]
        t1 = time.monotonic()
        results = [engine.submit(a, b) for a, b in pairs]
        serve_s = time.monotonic() - t1
        rel_means, rel_maxes, abs_means, scales = [], [], [], []
        for (a, b), res in zip(pairs, results):
            assert not res.degraded and res.level == 0, res
            assert res.num_flow_updates == SERVE_ITERS, res
            assert res.exit_reason == "target", res
            assert res.flow.shape == IMAGE_HW + (2,), res.flow.shape
            assert np.isfinite(res.flow).all()
            p1 = BucketRouter.pad_to(FlowEstimator._normalize(a), BUCKET)
            p2 = BucketRouter.pad_to(FlowEstimator._normalize(b), BUCKET)
            want = BucketRouter.crop(
                np.asarray(ref_apply(variables, p1, p2))[0], IMAGE_HW
            )
            assert np.isfinite(want).all()
            scale = float(np.abs(want).mean())
            diff = np.abs(res.flow - want)
            scales.append(scale)
            abs_means.append(float(diff.mean()))
            rel_means.append(float(diff.mean()) / scale)
            rel_maxes.append(float(diff.max()) / float(np.abs(want).max()))
        log(phase="serve", boot_s=round(boot_s, 2),
            programs=boot["programs_total"],
            backend_compiles=boot["backend_compiles"],
            serve_s=round(serve_s, 3),
            latency_ms=[round(r.latency_ms, 1) for r in results],
            mean_abs_flow_px=scales, abs_mean_px=abs_means,
            rel_mean=rel_means, rel_max=rel_maxes,
            tol={"abs_mean_px": SERVE_ABS_MEAN_TOL_PX,
                 "rel_mean": SERVE_REL_MEAN_TOL, "rel_max": SERVE_REL_MAX_TOL},
            peak_bytes=peak_bytes())
        assert max(abs_means) <= SERVE_ABS_MEAN_TOL_PX, abs_means
        assert max(rel_means) <= SERVE_REL_MEAN_TOL, rel_means
        assert max(rel_maxes) <= SERVE_REL_MAX_TOL, rel_maxes
        assert engine.stats()["completed"] == SERVE_REQUESTS
    finally:
        engine.stop()


class SyntheticFlowDataset:
    """In-memory Sintel-sized pairs with a known constant-shift flow."""

    def __init__(self, n: int, hw=(436, 1024)):
        import numpy as np

        rng = np.random.default_rng(SEED + 1)
        self.samples = []
        for i in range(n):
            dx, dy = 2 + i % 3, 1 + i % 2
            im1, _ = smooth_pair(rng, hw)
            im2 = np.roll(im1, (dy, dx), axis=(0, 1))
            flow = np.zeros(hw + (2,), np.float32)
            flow[..., 0], flow[..., 1] = dx, dy
            self.samples.append({
                "image1": im1.astype(np.uint8), "image2": im2.astype(np.uint8),
                "flow": flow, "valid": np.ones(hw, bool),
            })

    def __len__(self):
        return len(self.samples)

    def __getitem__(self, i):
        return {k: v.copy() for k, v in self.samples[i].items()}


def run_trainer(tag: str, *, batch: int, iters: int, steps: int, **config_kw):
    """One ``Trainer.run`` of ``steps`` steps; returns per-step losses."""
    import jax
    import numpy as np

    from raft_tpu.train.trainer import TrainConfig, Trainer

    config = TrainConfig(
        arch="raft_large", stage="sintel", crop_size=CROP,
        global_batch_size=batch, num_flow_updates=iters, num_steps=steps,
        learning_rate=TRAIN_LR, log_every=1, seed=SEED, remat=True,
        **{"remat_policy": "dots", **config_kw},
    )
    trainer = Trainer(
        config, SyntheticFlowDataset(batch), init_from=smoke_weights()
    )
    losses, stamps = [], []
    t0 = time.monotonic()

    def on_log(step, metrics):  # log_every=1: one fetched boundary a step
        losses.append(metrics["loss"])
        stamps.append(time.monotonic())

    state = trainer.run(log_fn=on_log)
    jax.block_until_ready(state.params)
    dt = time.monotonic() - t0
    assert int(state.step) == steps and len(losses) == steps, (state.step, losses)
    assert np.isfinite(losses).all(), losses
    log(phase=f"train/{tag}", seconds=round(dt, 2),
        first_step_s=round(stamps[0] - t0, 2),  # compile (or cache load) + step
        later_step_s=[round(b - a, 3) for a, b in zip(stamps, stamps[1:])],
        losses=losses,
        mesh=None if trainer.mesh is None else dict(trainer.mesh.shape),
        peak_bytes=peak_bytes())
    return trainer, state, losses


def train_phase() -> None:
    import numpy as np

    kw = dict(batch=TRAIN_BATCH, iters=TRAIN_ITERS, steps=TRAIN_STEPS,
              data_mesh=False)
    _, _, dense = run_trainer("dense", corr_impl="dense", **kw)
    fused_trainer, _, fused = run_trainer(
        "fused_bf16", corr_impl="fused", corr_dtype="bfloat16", **kw
    )
    assert_full_width_fused(fused_trainer.model, fused_trainer.state.variables())
    half = TRAIN_STEPS // 2
    for name, losses in (("dense", dense), ("fused_bf16", fused)):
        first, second = np.median(losses[:half]), np.median(losses[half:])
        assert second <= first * (1 + TRAIN_NOT_RISING_TOL), (
            f"{name} loss rose over {TRAIN_STEPS} steps: {losses}"
        )
    np.testing.assert_allclose(
        fused[0], dense[0], rtol=TRAIN_FIRST_LOSS_RTOL,
        err_msg="first-step loss: fused+bf16 vs dense fp32",
    )
    log(phase="train", first_loss_rel_diff=abs(fused[0] - dense[0]) / dense[0],
        tol={"not_rising": TRAIN_NOT_RISING_TOL,
             "first_loss_rtol": TRAIN_FIRST_LOSS_RTOL})


def multichip_phase() -> None:
    """The mesh path and what it is compared with — nothing else."""
    import jax
    import numpy as np

    assert len(jax.devices()) == 4, f"--multichip needs 4 chips: {jax.devices()}"
    kw = dict(batch=MESH_BATCH, iters=TRAIN_ITERS, steps=MESH_STEPS,
              **MESH_CONFIG)
    trainer, state, mesh_losses = run_trainer("mesh4", data_mesh=True, **kw)
    assert trainer.mesh is not None and trainer.mesh.size == 4
    # all four devices hold the (replicated) state, and the batch shards
    # four ways: code that has only seen virtual CPU devices could have
    # placed everything on device 0
    leaf = jax.tree.leaves(state.params)[0]
    assert {s.device for s in leaf.addressable_shards} == set(jax.devices())
    from raft_tpu.parallel import batch_sharding

    batch_spec = {
        k: jax.ShapeDtypeStruct(
            (MESH_BATCH,) + CROP + tail, np.float32,
            sharding=batch_sharding(trainer.mesh),
        )
        for k, tail in (("image1", (3,)), ("image2", (3,)), ("flow", (2,)),
                        ("valid", ()))
    }
    text = trainer.step_fn.lower(state, batch_spec).compile().as_text()
    assert "all-reduce" in text, "no gradient all-reduce in the mesh step"
    assert_kernel_in(text, "mesh train step")
    n_partitions = f"num_partitions={trainer.mesh.size}"
    assert n_partitions in text, f"{n_partitions} not in the compiled step"
    _, _, single_losses = run_trainer("single", data_mesh=False, **kw)
    np.testing.assert_allclose(
        mesh_losses, single_losses, rtol=MESH_LOSS_RTOL,
        err_msg="4-chip data-mesh losses vs one-chip losses",
    )
    log(phase="multichip", all_reduces=text.count("all-reduce("),
        loss_rel_diff=[abs(a - b) / b for a, b in
                       zip(mesh_losses, single_losses)],
        tol={"loss_rtol": MESH_LOSS_RTOL})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the 4-chip data-mesh Trainer and the "
                         "single-device run it is compared with")
    args = ap.parse_args()

    import jax

    first = jax.devices()[0]
    if first.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports {first.platform!r}; "
              "refusing before compiling anything", file=sys.stderr)
        return 1

    from raft_tpu.utils.runtime import device_info, enable_persistent_cache

    device = device_info()
    cache_dir = enable_persistent_cache()
    cache = CacheCounter()
    t0 = time.monotonic()
    log(phase="start", device=device, cache_dir=cache_dir,
        multichip=args.multichip)
    if args.multichip:
        multichip_phase()
    else:
        serve_phase(cache_dir)
        train_phase()
    log(phase="done", seconds=round(time.monotonic() - t0, 2),
        cache_hits=cache.hits, cache_misses=cache.misses,
        memory_stats=jax.devices()[0].memory_stats())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
