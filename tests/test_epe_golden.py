"""Offline golden-EPE acceptance test (VERDICT r3 #5).

``tests/fixtures/epe_golden`` is a committed miniature Sintel-layout
dataset plus trained weights plus the EPE scalars the REFERENCE
implementation's own validation protocol (`/root/reference/scripts/
validate_sintel.py:164-206`, run via ``scripts/make_epe_fixture.py``)
produced for them. This test replays OUR protocol path — Sintel loader ->
replicate split-padding -> [-1,1] normalization -> 32 flow updates ->
final-only pixel-concatenated EPE — through ``raft_tpu.eval.validate``
and pins the scalars.

At fixture generation both implementations agreed to < 1e-6 px
(``expected.json: epe_delta_at_generation``) — trained weights make the
32-step refinement contractive, so cross-implementation fp32 noise cannot
amplify. The 1e-3 px test tolerance is therefore ~3 orders of margin
while still catching any real protocol deviation (a wrong pad mode,
normalization, iteration count, or aggregation moves the scalar by
>> 0.01 px). With this pin, the only untested variable between this repo
and a real Sintel EPE table is the checkpoint file itself.
"""

import json
import os

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "epe_golden")


@pytest.fixture(scope="module")
def fixture_data():
    if not os.path.isdir(FIXTURE):
        pytest.skip("epe_golden fixture not present")
    with open(os.path.join(FIXTURE, "expected.json")) as f:
        expected = json.load(f)

    import flax.serialization
    import jax

    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(FIXTURE), "..", ".."))
    from scripts.make_epe_fixture import fixture_arch

    from raft_tpu.models.zoo import build_raft, init_variables

    model = build_raft(fixture_arch())
    tmpl = jax.tree.map(
        np.zeros_like, jax.device_get(init_variables(model))
    )
    with open(os.path.join(FIXTURE, "weights.msgpack"), "rb") as f:
        trained = flax.serialization.from_bytes(tmpl, f.read())
    return model, trained, expected


@pytest.mark.parametrize("dstype", ["clean", "final"])
def test_protocol_reproduces_reference_epe(fixture_data, dstype):
    from raft_tpu.data.datasets import Sintel
    from raft_tpu.eval.validate import validate

    model, trained, expected = fixture_data
    iters = expected["protocol"]["iters"]
    ds = Sintel(FIXTURE, split="training", dstype=dstype)
    assert len(ds) == 3  # 2 + 1 pairs across the two scenes

    m = validate(
        model, trained, ds, num_flow_updates=iters, mode="sintel",
        fps_pairs=0, progress=False,
    )
    ref_epe = expected["reference"][dstype]
    assert abs(m["epe"] - ref_epe) < 1e-3, (m["epe"], ref_epe)
    # the threshold metrics were recorded from OUR validator at
    # generation time on this same (CPU) backend — pin them tightly
    gen = expected["ours_at_generation"][dstype]
    for k in ("1px", "3px", "5px"):
        assert abs(m[k] - gen[k]) < 1e-3, (k, m[k], gen[k])


@pytest.mark.parametrize(
    "knobs,tol",
    [
        # raft_large deployment: fused kernel + bf16 correlation storage.
        # Measured delta on this fixture: 3.3e-4 px (tol = ~15x margin).
        (dict(corr_impl="fused", corr_dtype="bfloat16"), 5e-3),
        # raft_small deployment adds bf16 convs. Measured: 5.6e-3 px
        # (tol = ~5x margin) — consistent with PARITY.md's trained-weight
        # bf16 perturbation scale.
        (
            dict(
                corr_impl="fused",
                corr_dtype="bfloat16",
                compute_dtype="bfloat16",
            ),
            3e-2,
        ),
    ],
    ids=["deploy-raft-large-knobs", "deploy-raft-small-knobs"],
)
def test_deployment_config_epe_pinned(fixture_data, knobs, tol):
    """VERDICT r4 #5: bound each DEPLOYMENT config's EPE against the
    reference-produced golden scalar on real frames — previously the
    golden pin covered only the fp32 protocol path while the bf16
    fidelity evidence lived on synthetic toys."""
    from raft_tpu.data.datasets import Sintel
    from raft_tpu.eval.validate import validate
    from raft_tpu.models.zoo import build_raft

    # fixture_data already put the repo root on sys.path
    from scripts.make_epe_fixture import fixture_arch

    _, trained, expected = fixture_data
    # the deployment knobs only change activation/storage casts, never
    # the variable tree — the fixture's fp32-trained weights apply
    # directly to the knob-modified model
    model = build_raft(fixture_arch().replace(**knobs))

    # the pin is only meaningful if the fused path actually engages at
    # the fixture geometry (it does since the round-5 width
    # generalization — non-pow2 level widths fuse)
    import jax.numpy as jnp

    probe = jnp.zeros((1, 12, 17, 4))
    assert isinstance(
        model.corr_block.build_pyramid(probe, probe), dict
    ), "fused path did not engage at the fixture geometry"

    ds = Sintel(FIXTURE, split="training", dstype="clean")
    m = validate(
        model, trained, ds,
        num_flow_updates=expected["protocol"]["iters"],
        mode="sintel", fps_pairs=0, progress=False,
    )
    ref_epe = expected["reference"]["clean"]
    assert abs(m["epe"] - ref_epe) < tol, (knobs, m["epe"], ref_epe)


def test_throughput_preset_is_the_gated_bf16_config():
    """ISSUE 7 preset gate, tier-1 half: ``ServeConfig.preset
    ('throughput')`` must name exactly the knob set whose trained-weight
    EPE the deploy-raft-small case above pins — the preset inherits that
    golden gate by identity, so a preset drift silently escaping the
    gate is impossible."""
    from raft_tpu.serve import ServeConfig

    assert ServeConfig.preset("throughput").model_overrides() == dict(
        corr_impl="fused", corr_dtype="bfloat16", compute_dtype="bfloat16"
    )
