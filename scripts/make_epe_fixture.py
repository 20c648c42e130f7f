"""Generate the offline golden-EPE acceptance fixture (VERDICT r3 #5).

The EPE *protocol* path (loader -> padder -> normalize -> 32 iterations ->
final-only EPE aggregation, reference ``scripts/validate_sintel.py:164-206``)
previously had no end-to-end numeric pin: full-scale functional parity was
proven with shared weights (PARITY.md), but nothing asserted that
``raft_tpu.eval.validate.validate()`` reproduces the REFERENCE protocol's
scalar on a real Sintel-layout directory. This script builds that pin once:

  1. trains a tiny (but genuinely converging) RAFT on synthetic warped
     pairs — trained weights make the 32-step refinement contractive, so
     cross-implementation fp32 noise cannot chaotically amplify (the same
     argument as the bf16 storage evidence, scripts/parity_report.py);
  2. writes a miniature Sintel-layout dataset (two scenes, clean+final
     passes, .flo ground truth, non-%8 frame size so the split replicate
     padding genuinely engages);
  3. scores it with the REFERENCE implementation's own
     ``validate_sintel_jax`` (imported read-only from /root/reference as a
     numeric oracle, same policy as scripts/parity_report.py), loading the
     SAME weights — tree identity is asserted;
  4. scores it with OUR ``validate()`` and records both in
     ``expected.json``.

``tests/test_epe_golden.py`` then replays step 4 against the committed
expectation — after which the only untested variable between this repo and
a real Sintel EPE table is the checkpoint file itself.

Run from the repo root (the reference must be present read-only):

    python scripts/make_epe_fixture.py --out tests/fixtures/epe_golden
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# fixture geometry: NOT divisible by 8 on either side, so the protocol's
# replicate split-padding (92 -> 96: 2 top / 2 bottom; 132 -> 136: 2/2)
# is genuinely exercised; padded /8 feature maps are 12x17 >= 8 per side,
# the 3-level pyramid's minimum.
FRAME_H, FRAME_W = 92, 132
SCENES = (("alley_a", 3), ("market_b", 2))  # (name, frame count)
ITERS = 32  # the published protocol's flow-update count


def fixture_arch():
    """The fixture's RAFT architecture — one definition, mirrored exactly
    for the reference's assembler in :func:`build_reference_model`."""
    from raft_tpu.models.zoo import RAFT_SMALL

    return RAFT_SMALL.replace(
        feature_encoder_widths=(16, 16, 24, 32, 48),
        context_encoder_widths=(16, 16, 24, 32, 80),
        motion_corr_widths=(48,),
        motion_flow_widths=(32, 16),
        motion_out_channels=40,
        gru_hidden=48,
        flow_head_hidden=64,
        corr_levels=3,
        corr_radius=3,
    )


def build_reference_model():
    """The same architecture via the reference's ``_raft`` assembler."""
    from functools import partial

    import flax.linen as ref_nn

    sys.path.insert(0, "/root/reference")
    from jax_raft import model as ref_model_mod

    return ref_model_mod._raft(
        feature_encoder_layers=(16, 16, 24, 32, 48),
        feature_encoder_block=ref_model_mod.BottleneckBlock,
        feature_encoder_norm_layer=partial(
            ref_nn.InstanceNorm, epsilon=1e-5, use_bias=False, use_scale=False
        ),
        context_encoder_layers=(16, 16, 24, 32, 80),
        context_encoder_block=ref_model_mod.BottleneckBlock,
        context_encoder_norm_layer=None,
        corr_block_num_levels=3,
        corr_block_radius=3,
        motion_encoder_corr_layers=(48,),
        motion_encoder_flow_layers=(32, 16),
        motion_encoder_out_channels=40,
        recurrent_block_hidden_state_size=48,
        recurrent_block_kernel_size=((3, 3),),
        recurrent_block_padding=((1, 1),),
        flow_head_hidden_size=64,
        use_mask_predictor=False,
    )


def train_weights(steps: int):
    """Train the fixture model on synthetic warped pairs (the contraction
    prerequisite); returns the trained variables (plain fp32 pytree)."""
    import jax

    from raft_tpu.models.zoo import build_raft, init_variables
    from raft_tpu.train import TrainState, make_optimizer, make_train_step
    from raft_tpu.train.optim import one_cycle_lr

    sys.path.insert(0, os.path.join(os.path.dirname(__file__)))
    from parity_report import _warped_batch

    # fused corr for training speed on-chip; the weights are impl-free
    model = build_raft(fixture_arch().replace(corr_impl="fused"))
    variables = init_variables(model)
    tx = make_optimizer(one_cycle_lr(4e-4, steps), weight_decay=1e-5,
                        clip_norm=1.0)
    state = TrainState.create(variables, tx)
    step_fn = make_train_step(model, tx, num_flow_updates=12)

    key = jax.random.PRNGKey(0)
    for i in range(steps):
        key, sub = jax.random.split(key)
        state, metrics = step_fn(state, _warped_batch(sub, 4, 256, 256))
        if (i + 1) % 100 == 0:
            m = {k: float(v) for k, v in jax.device_get(metrics).items()}
            print(f"train step {i + 1}/{steps}: loss={m['loss']:.3f} "
                  f"epe={m['epe']:.2f}", flush=True)
    return jax.device_get(state.variables())


def synth_scene(key, n_frames: int):
    """Chained smooth warps: frame k+1 = frame k backward-warped by a fresh
    smooth flow (constant shift + weak long-wavelength field — the same
    label-accuracy reasoning as parity_report._warped_batch). Returns
    fp32 frames in [-1, 1] and the (n-1) GT flows."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.resize import resize_bilinear_align_corners
    from raft_tpu.ops.sampling import bilinear_sample, coords_grid

    h, w = FRAME_H, FRAME_W
    key, k1, k2 = jax.random.split(key, 3)
    coarse = jax.random.uniform(k1, (1, h // 16, w // 16, 3), jnp.float32, -1, 1)
    fine = jax.random.uniform(k2, (1, h // 2, w // 2, 3), jnp.float32, -1, 1)
    frame = (
        0.7 * resize_bilinear_align_corners(coarse, h, w)
        + 0.3 * resize_bilinear_align_corners(fine, h, w)
    )
    frames, flows = [frame], []
    for _ in range(n_frames - 1):
        key, ks, kf = jax.random.split(key, 3)
        shift = jax.random.uniform(ks, (1, 1, 1, 2), jnp.float32, -6.0, 6.0)
        field = jax.random.uniform(
            kf, (1, max(h // 64, 1), max(w // 64, 1), 2), jnp.float32, -1.5, 1.5
        )
        flow = shift + resize_bilinear_align_corners(field, h, w)
        frame = bilinear_sample(frames[-1], coords_grid(1, h, w) - flow)
        frames.append(frame)
        flows.append(flow)
    return (
        [np.asarray(f[0]) for f in frames],
        [np.asarray(f[0]) for f in flows],
    )


def to_uint8(img: np.ndarray) -> np.ndarray:
    return np.clip(np.round((img + 1.0) * 0.5 * 255.0), 0, 255).astype(np.uint8)


def box_blur(img: np.ndarray) -> np.ndarray:
    """3x3 replicate-edge box blur — the 'final' pass's degradation."""
    p = np.pad(img, ((1, 1), (1, 1), (0, 0)), mode="edge")
    out = np.zeros_like(img)
    for dy in range(3):
        for dx in range(3):
            out += p[dy : dy + img.shape[0], dx : dx + img.shape[1]]
    return out / 9.0


def write_dataset(out: str):
    """Miniature Sintel layout: training/{clean,final,flow}/<scene>/..."""
    import jax
    from PIL import Image

    from raft_tpu.data.io import write_flo

    for sub in ("clean", "final", "flow"):
        for scene, _ in SCENES:
            os.makedirs(os.path.join(out, "training", sub, scene), exist_ok=True)

    key = jax.random.PRNGKey(7)
    for scene, n in SCENES:
        key, sub = jax.random.split(key)
        frames, flows = synth_scene(sub, n)
        for i, fr in enumerate(frames):
            name = f"frame_{i + 1:04d}.png"
            Image.fromarray(to_uint8(fr)).save(
                os.path.join(out, "training", "clean", scene, name)
            )
            Image.fromarray(to_uint8(box_blur(fr))).save(
                os.path.join(out, "training", "final", scene, name)
            )
        for i, fl in enumerate(flows):
            write_flo(
                os.path.join(
                    out, "training", "flow", scene, f"frame_{i + 1:04d}.flo"
                ),
                fl.astype(np.float32),
            )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="tests/fixtures/epe_golden")
    ap.add_argument("--train-steps", type=int, default=600)
    ap.add_argument("--stage", default="all", choices=["train", "score", "all"],
                    help="'train' (any backend, e.g. TPU) writes weights + "
                    "dataset; 'score' (run it pinned to CPU, the backend "
                    "the test uses) writes expected.json from them")
    ap.add_argument("--device", default=None, choices=[None, "cpu", "tpu"])
    args = ap.parse_args()
    if args.device == "cpu" or args.stage == "score":
        os.environ["JAX_PLATFORMS"] = "cpu"  # read by the jax import below

    import flax.serialization
    import jax

    os.makedirs(args.out, exist_ok=True)

    if args.stage in ("train", "all"):
        print("== training fixture weights ==", flush=True)
        trained = train_weights(args.train_steps)
        with open(os.path.join(args.out, "weights.msgpack"), "wb") as f:
            f.write(flax.serialization.to_bytes(trained))

        print("== writing dataset ==", flush=True)
        write_dataset(args.out)
        if args.stage == "all":
            # scoring must run on the CPU backend (the one the test uses;
            # the backend choice is process-global, so re-exec) — TPU-scored
            # expectations would pin bf16-MXU numerics the CPU test can't hit.
            # This parent may hold the chip (one process per chip): the
            # child is pinned to CPU through its environment so it never
            # probes the device.
            import subprocess

            raise SystemExit(subprocess.call(
                [sys.executable, os.path.abspath(__file__),
                 "--stage", "score", "--out", args.out],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            ))
        return

    if args.stage == "score":
        from raft_tpu.models.zoo import build_raft, init_variables

        tmpl = jax.tree.map(
            np.zeros_like,
            jax.device_get(
                init_variables(build_raft(fixture_arch().replace(corr_impl="fused")))
            ),
        )
        with open(os.path.join(args.out, "weights.msgpack"), "rb") as f:
            trained = flax.serialization.from_bytes(tmpl, f.read())

    print("== scoring with the REFERENCE protocol ==", flush=True)
    ref_model, ref_init = build_reference_model()
    # tree identity: the reference's freshly-initialized tree must match
    # the trained tree leaf-for-leaf (path + shape)
    import jax.tree_util as jtu

    def spec(tree):
        return sorted(
            ("/".join(str(k.key) for k in path), tuple(np.shape(leaf)))
            for path, leaf in jtu.tree_flatten_with_path(tree)[0]
        )

    assert spec(ref_init) == spec(trained), "variable trees diverge"

    import importlib.util

    vs_spec = importlib.util.spec_from_file_location(
        "ref_validate_sintel", "/root/reference/scripts/validate_sintel.py"
    )
    ref_vs = importlib.util.module_from_spec(vs_spec)
    vs_spec.loader.exec_module(ref_vs)
    ref_results = ref_vs.validate_sintel_jax(
        ref_model, trained, data_root=os.path.join(args.out), iters=ITERS
    )
    ref_results = {k: float(v) for k, v in ref_results.items()}
    print("reference:", ref_results, flush=True)

    print("== scoring with OUR validate() ==", flush=True)
    from raft_tpu.data.datasets import Sintel
    from raft_tpu.eval.validate import validate
    from raft_tpu.models.zoo import build_raft

    model = build_raft(fixture_arch())
    ours = {}
    for dstype in ("clean", "final"):
        ds = Sintel(args.out, split="training", dstype=dstype)
        m = validate(
            model, trained, ds, num_flow_updates=ITERS, mode="sintel",
            fps_pairs=0, progress=False,
        )
        ours[dstype] = {k: float(v) for k, v in m.items() if k != "fps"}
    print("ours:", ours, flush=True)

    deltas = {k: abs(ours[k]["epe"] - ref_results[k]) for k in ref_results}
    print("epe deltas:", deltas, flush=True)

    with open(os.path.join(args.out, "expected.json"), "w") as f:
        json.dump(
            {
                "protocol": {
                    "iters": ITERS,
                    "frame_hw": [FRAME_H, FRAME_W],
                    "scenes": [list(s) for s in SCENES],
                },
                "reference": ref_results,
                "ours_at_generation": ours,
                "epe_delta_at_generation": deltas,
            },
            f,
            indent=2,
        )
    print("fixture written to", args.out, flush=True)


if __name__ == "__main__":
    main()
