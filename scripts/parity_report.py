#!/usr/bin/env python
"""Full-scale numeric parity: our framework vs the reference implementation.

The acceptance story of the reference is its Sintel EPE table
(``/root/reference/README.md:7-12``). This environment has no network and no
pretrained checkpoint on disk, so the strongest producible evidence is an
*implementation-parity* run at the full acceptance scale: both frameworks,
the SAME full-size architecture and the SAME weights, the SAME full-res
Sintel-shaped inputs through the whole pipeline (436x1024 -> replicate pad ->
32 flow updates -> final prediction), comparing outputs per iteration.

If the implementations agree at full scale, loading the published
checkpoint into either one produces identical EPE by construction (the
variable trees are identical; see tests/test_model_parity.py).

Writes PARITY.md. Run: python scripts/parity_report.py [--device cpu|default]
"""

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, "/root/reference")

import numpy as np


def run_arch(arch: str, iters: int, precision: str, variant: str = "dense"):
    """``variant``: 'dense' (pure fp32 reference semantics) or 'fused'
    (the flagship kernel path at fp32 — implementation-exact, so it
    belongs in a tolerance table; the flagship's corr_dtype=bfloat16
    storage is deliberately NOT compared here: trajectory deltas under
    32 chaotic random-weight iterations say nothing about trained-model
    EPE, and its tap-level error bound is covered by
    tests/test_bf16.py::test_corr_dtype_knob)."""
    import jax
    import jax.numpy as jnp
    import jax_raft  # the reference, imported read-only as the oracle

    from raft_tpu.eval.padder import InputPadder
    from raft_tpu.models import build_raft
    from raft_tpu.models.zoo import CONFIGS

    factory = {"raft_large": jax_raft.raft_large, "raft_small": jax_raft.raft_small}
    ref_model, variables = factory[arch](pretrained=False)
    cfg = CONFIGS[arch]
    if variant == "fused":
        cfg = cfg.replace(corr_impl="fused")
    ours = build_raft(cfg)

    rng = np.random.default_rng(42)
    im1 = rng.uniform(-1, 1, (1, 436, 1024, 3)).astype(np.float32)
    im2 = rng.uniform(-1, 1, (1, 436, 1024, 3)).astype(np.float32)
    padder = InputPadder(im1.shape, mode="sintel")
    im1, im2 = padder.pad(im1, im2)

    ref_fn = jax.jit(
        partial(ref_model.apply, variables, train=False, num_flow_updates=iters)
    )
    our_fn = jax.jit(
        partial(ours.apply, variables, train=False, num_flow_updates=iters)
    )
    our_final_fn = jax.jit(
        partial(
            ours.apply,
            variables,
            train=False,
            num_flow_updates=iters,
            emit_all=False,
        )
    )

    with jax.default_matmul_precision(precision):
        ref_out = np.asarray(ref_fn(im1, im2))  # (iters, 1, 440, 1024, 2)
        our_out = np.asarray(our_fn(im1, im2))
        our_final = np.asarray(our_final_fn(im1, im2))

    per_iter_max = np.abs(our_out - ref_out).reshape(iters, -1).max(axis=1)
    final_ref = padder.unpad(ref_out[-1])
    final_ours = padder.unpad(our_final)
    final_delta = np.abs(final_ours - final_ref)
    epe_between = np.linalg.norm(final_ours - final_ref, axis=-1).mean()
    flow_mag = np.linalg.norm(final_ref, axis=-1).mean()

    return {
        "arch": f"{arch} ({variant})" if variant != "dense" else arch,
        "iters": iters,
        "per_iter_max": per_iter_max,
        "final_max_abs": float(final_delta.max()),
        "final_mean_abs": float(final_delta.mean()),
        "epe_between_impls": float(epe_between),
        "ref_flow_mag": float(flow_mag),
        "emit_all_vs_final_max": float(
            np.abs(our_out[-1] - our_final).max()
        ),
    }


def _warped_batch(key, b, h, w, max_flow=8.0):
    """Synthetic correlation-dependent pairs: smooth texture, smooth flow
    field, image2 = image1 backward-warped by the flow. Context alone
    cannot predict the warp — solving it requires correlation matching."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.resize import resize_bilinear_align_corners
    from raft_tpu.ops.sampling import bilinear_sample, coords_grid

    k1, k2, k3, k4 = jax.random.split(key, 4)
    # multi-scale texture: coarse structure + fine detail for sub-pixel
    # matchability
    coarse = jax.random.uniform(k1, (b, h // 16, w // 16, 3), jnp.float32, -1, 1)
    fine = jax.random.uniform(k3, (b, h // 2, w // 2, 3), jnp.float32, -1, 1)
    image1 = (
        0.7 * resize_bilinear_align_corners(coarse, h, w)
        + 0.3 * resize_bilinear_align_corners(fine, h, w)
    )
    # Label accuracy bounds the learnable EPE: with image2(x) =
    # image1(x - f(x)), the true forward flow differs from f by
    # ~|grad f|*|f|. A short-wavelength field at full amplitude makes the
    # labels wrong by ~2 px (a trained toy plateaus at EPE ~= the mean
    # flow magnitude — measured). So: a constant per-sample translation
    # (exact labels, still correlation-dependent — the shift differs per
    # sample) plus a weak long-wavelength field (label error ~0.3 px).
    shift = jax.random.uniform(k2, (b, 1, 1, 2), jnp.float32,
                               -max_flow, max_flow)
    field = jax.random.uniform(k4, (b, h // 64, w // 64, 2), jnp.float32,
                               -max_flow / 4, max_flow / 4)
    flow = shift + resize_bilinear_align_corners(field, h, w)
    coords = coords_grid(b, h, w) - flow
    image2 = bilinear_sample(image1, coords)
    return {
        "image1": image1,
        "image2": image2,
        "flow": flow,
        "valid": jnp.ones((b, h, w), jnp.float32),
    }


def run_storage_evidence(steps: int = 600, train_hw=(256, 256), iters: int = 32):
    """Train a tiny fused-impl RAFT on synthetic warped pairs ON-CHIP, then
    compare flows from the SAME trained weights across corr storage dtypes
    at the FULL acceptance scale (436x1024 padded, 32 iters).

    This is the reproducible evidence behind bf16 correlation storage
    (the 'throughput' preset): trained iterative refinement is
    contractive, so per-iteration tap rounding noise below the matching
    basin's margin converges to the same flow — random-weight trajectory
    deltas (chaotic) say nothing, which is why this trains first."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.zoo import RAFT_SMALL, build_raft, init_variables
    from raft_tpu.train import TrainState, make_optimizer, make_train_step

    tiny = RAFT_SMALL.replace(
        feature_encoder_widths=(16, 16, 24, 32, 48),
        context_encoder_widths=(16, 16, 24, 32, 80),
        motion_corr_widths=(48,),
        motion_flow_widths=(32, 16),
        motion_out_channels=40,
        gru_hidden=48,
        flow_head_hidden=64,
        corr_levels=3,
        corr_radius=3,
        corr_impl="fused",
    )
    from raft_tpu.train.optim import one_cycle_lr

    model = build_raft(tiny)
    variables = init_variables(model)
    tx = make_optimizer(one_cycle_lr(4e-4, steps), weight_decay=1e-5,
                        clip_norm=1.0)
    state = TrainState.create(variables, tx)
    step_fn = make_train_step(model, tx, num_flow_updates=12)

    h, w = train_hw
    key = jax.random.PRNGKey(0)
    for i in range(steps):
        key, sub = jax.random.split(key)
        batch = _warped_batch(sub, 4, h, w)
        state, metrics = step_fn(state, batch)
        if (i + 1) % 500 == 0:
            m = {k: float(v) for k, v in jax.device_get(metrics).items()}
            print(f"evidence train step {i + 1}: epe={m.get('epe'):.2f}",
                  flush=True)
    final = {k: float(v) for k, v in jax.device_get(metrics).items()}
    trained = state.variables()

    # train-scale holdout: contraction evidence is only meaningful where
    # the model actually converged; report this alongside full scale
    hold = _warped_batch(jax.random.PRNGKey(123), 2, h, w)
    hold_fn = jax.jit(
        partial(model.apply, trained, train=False, num_flow_updates=iters,
                emit_all=False)
    )
    hold_flow = np.asarray(hold_fn(hold["image1"], hold["image2"]))
    hold_epe = float(
        np.linalg.norm(hold_flow - np.asarray(hold["flow"]), axis=-1).mean()
    )

    # full-scale eval pair (same synthetic generator, acceptance shapes)
    from raft_tpu.eval.padder import InputPadder

    ev = _warped_batch(jax.random.PRNGKey(99), 1, 436, 1024)
    padder = InputPadder((1, 436, 1024, 3), mode="sintel")
    im1, im2 = padder.pad(np.asarray(ev["image1"]), np.asarray(ev["image2"]))

    flows = {}
    for cdt in ("float32", "bfloat16"):
        m = build_raft(tiny.replace(corr_dtype=cdt))
        fn = jax.jit(
            partial(m.apply, trained, train=False, num_flow_updates=iters,
                    emit_all=False)
        )
        flows[cdt] = padder.unpad(np.asarray(fn(im1, im2)))

    gt = np.asarray(ev["flow"])  # generated at 436x1024, never padded
    gt_mag = float(np.linalg.norm(gt, axis=-1).mean())
    epe = float(np.linalg.norm(flows["float32"] - gt, axis=-1).mean())
    out = {
        "train_steps": steps,
        "final_train_epe": final.get("epe", float("nan")),
        "holdout_epe_train_scale": hold_epe,
        "eval_epe_fp32": epe,
        "eval_flow_mag": gt_mag,
    }
    d = np.abs(flows["bfloat16"].astype(np.float64) - flows["float32"])
    out["bfloat16_max_dflow"] = float(d.max())
    out["bfloat16_mean_dflow"] = float(d.mean())
    return out


def storage_evidence_section(ev) -> list:
    # margin matters: the documented dead-end generator plateaus AT
    # EPE ~= flow magnitude (labels wrong by ~|grad f||f|), which a bare
    # '<' would pass; demand clear separation before calling it trained
    bar = 0.5 * ev["eval_flow_mag"]
    converged = (
        ev["eval_epe_fp32"] < bar and ev["holdout_epe_train_scale"] < bar
    )
    caveat = []
    if not converged:
        caveat = [
            "",
            "**WARNING: the toy model did NOT converge (eval or "
            "train-scale holdout EPE exceeds 0.5x the mean flow "
            "magnitude, the bar that separates real convergence from the "
            "wrong-labels plateau) — the deltas in the table above are "
            "chaotic random-weight behavior, not contraction evidence. "
            "Re-run with more --evidence-steps.**",
        ]
    return [
        "",
        "## bf16 correlation storage on TRAINED weights, full scale",
        "",
        f"Reproducible evidence for the bf16-storage deployment config "
        f"(`scripts/parity_report.py --storage-evidence`): a tiny "
        f"fused-impl RAFT (corr_levels=3, radius=3) "
        f"trained {ev['train_steps']} steps on-chip on synthetic warped "
        f"pairs (correlation-dependent by construction), then the SAME "
        f"trained weights evaluated at the full acceptance scale "
        f"(436x1024 padded, 32 updates). Convergence: held-out EPE "
        f"{ev['holdout_epe_train_scale']:.2f} px at the train scale, "
        f"{ev['eval_epe_fp32']:.2f} px at full scale, mean flow "
        f"magnitude {ev['eval_flow_mag']:.1f} px:",
        "",
        r"| corr storage | max \|Δflow\| vs fp32 | mean \|Δflow\| vs fp32 |",
        "|---|---|---|",
        f"| bfloat16 | {ev['bfloat16_max_dflow']:.2e} | "
        f"{ev['bfloat16_mean_dflow']:.2e} |",
        "",
        "Trained refinement is contractive: per-iteration tap rounding",
        "noise converges to the same flow (random-weight trajectory deltas",
        "are chaotic and say nothing — which is why this trains first).",
        "A real-checkpoint Sintel EPE run remains the definitive check the",
        "moment weights/data are available.",
    ] + caveat


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--device", default="default", choices=["default", "cpu"])
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--out", default="PARITY.md")
    ap.add_argument("--variants", default="dense,fused",
                    help="comma list of 'dense'/'fused'; use --variants "
                         "dense for the quick CPU run (the fused path "
                         "runs in interpret mode off-TPU)")
    ap.add_argument(
        "--storage-evidence", action="store_true",
        help="also train a tiny fused RAFT on synthetic warped pairs and "
             "record bf16-vs-fp32 correlation-storage flow deltas from the "
             "trained weights at full scale")
    ap.add_argument(
        "--evidence-only", action="store_true",
        help="skip the (slow) parity variants; run only the storage evidence "
             "and splice its section into the existing PARITY.md")
    ap.add_argument("--evidence-steps", type=int, default=3000)
    ap.add_argument(
        "--precision",
        default="highest",
        choices=["default", "float32", "highest"],
        help="jax matmul precision: 'highest' makes the TPU MXU compute true "
        "fp32 (3-pass) so the comparison measures the implementations, not "
        "the MXU's default bf16 truncation",
    )
    args = ap.parse_args()
    if (args.storage_evidence or args.evidence_only) and args.evidence_steps < 1:
        ap.error("--evidence-steps must be >= 1")
    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    if args.evidence_only:
        evidence = run_storage_evidence(steps=args.evidence_steps)
        section = "\n".join(storage_evidence_section(evidence))
        text = ""
        if os.path.exists(args.out):
            with open(args.out) as f:
                text = f.read()
        # replace ONLY the old evidence section (plus a legacy pre-table
        # WARNING immediately before it); any sections added after it
        # survive the splice
        marker = "\n## bf16 correlation storage"
        hpos = text.find(marker)
        start = hpos
        legacy_warn = text.find("\n**WARNING: the toy model did NOT converge")
        if legacy_warn != -1 and (start == -1 or legacy_warn < start):
            start = legacy_warn  # legacy placement: WARNING above the section
        if start == -1:
            text = text.rstrip("\n") + "\n" + section + "\n"
        else:
            # the replaced region ends at the next heading AFTER the
            # section heading itself (not after a legacy WARNING start)
            after = (
                text.find("\n## ", hpos + len(marker)) if hpos != -1 else -1
            )
            tail = text[after:] if after != -1 else "\n"
            text = text[:start].rstrip("\n") + "\n" + section + tail
        with open(args.out, "w") as f:
            f.write(text)
        print(section)
        return

    platform = jax.devices()[0].platform
    results = [
        run_arch(a, args.iters, args.precision, variant=v)
        for a in ("raft_small", "raft_large")
        for v in args.variants.split(",")
    ]

    lines = [
        "# PARITY — full-scale numeric parity vs the reference implementation",
        "",
        f"Device: `{jax.devices()[0]}` (platform `{platform}`), matmul "
        f"precision `{args.precision}`. "
        f"Protocol: 436x1024 random [-1,1] inputs, replicate-padded to "
        f"440x1024 (`InputPadder('sintel')`), {args.iters} flow updates — "
        "the exact acceptance-protocol shapes of the reference "
        "(`scripts/validate_sintel.py:164-188`). Both implementations run "
        "the SAME variable tree (reference `init`, loaded unchanged into "
        "our model — possible because the checkpoint trees are identical).",
        "",
        r"| model | max \|Δflow\| (final) | mean \|Δflow\| (final) | EPE between impls | ref mean \|flow\| | max per-iter Δ (worst iter) |",
        "|---|---|---|---|---|---|",
    ]
    for r in results:
        worst = int(np.argmax(r["per_iter_max"]))
        lines.append(
            f"| {r['arch']} | {r['final_max_abs']:.3e} | "
            f"{r['final_mean_abs']:.3e} | {r['epe_between_impls']:.3e} | "
            f"{r['ref_flow_mag']:.3f} | {r['per_iter_max'].max():.3e} (iter {worst}) |"
        )
    lines += [
        "",
        "Per-iteration max-abs deltas (full 440x1024 upsampled flow):",
        "",
        "```",
    ]
    for r in results:
        vals = " ".join(f"{v:.1e}" for v in r["per_iter_max"])
        lines.append(f"{r['arch']}: {vals}")
    evidence = None
    if args.storage_evidence:
        evidence = run_storage_evidence(steps=args.evidence_steps)

    lines += [
        "```",
        "",
        f"`emit_all=False` (final-only inference mode) matches the last "
        f"emitted prediction to "
        + ", ".join(
            f"{r['emit_all_vs_final_max']:.1e} ({r['arch']})" for r in results
        )
        + ".",
        "",
        "## What this proves, and what remains",
        "",
        "Proved at full acceptance scale: identical variable tree, identical",
        "padding, identical 32-iteration recurrence — the two implementations",
        "compute the same function to floating-point tolerance on the exact",
        "shapes of the published benchmark.",
        "",
        "Remaining (blocked in this environment, no network egress and no",
        "checkpoint on disk): loading `raft_large_C_T_SKHT_V2` /",
        "`raft_small_C_T_V2` and reproducing the EPE 0.649/1.020 table on",
        "real MPI-Sintel frames. With the tree and function proven equal,",
        "that number transfers by construction the moment the msgpack is",
        "placed in `~/.cache/raft_tpu/` (see `raft_tpu/models/zoo.py`).",
        "",
    ]
    if evidence is not None:
        lines += storage_evidence_section(evidence)
    with open(args.out, "w") as f:
        f.write("\n".join(lines))
    print("\n".join(lines))


if __name__ == "__main__":
    main()
