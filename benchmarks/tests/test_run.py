"""``run.py`` end to end without a chip: it refuses off a TPU; past that
look, each driver is driven at tiny shapes on the CPU (Pallas in interpret
mode) through the harness's own ``run_cell`` and the result line's keys
are checked — a rehearsal of control flow, never a measurement. Then the
same run with the timed path broken underneath has to come out as not
correct, once for each fault a cell can have, and so has the control."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import loader, run as runmod
from benchmarks.reference import compare as cmp

ROOT = loader.ROOT
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}
SEED = 2**31 + 77


def tiny(name, root=ROOT):
    """The cell as committed (its limits too), at a size a test can hold."""
    cell = loader.load_cell(name, root=root)
    if cell["driver"].startswith("serve"):
        cell.update(image_hw=[120, 152], bucket=[128, 160], iters=4,
                    distinct_pairs=3, ramp_s=0.5, compare_pairs=2,
                    clients=6)
        cell["serve"] = dict(cell["serve"], pool_capacity=4, max_batch=2,
                             ladder=[4, 3, 2])
    else:
        cell.update(image_hw=[140, 200], crop=[128, 160], iters=3,
                    dataset_size=4, warm_steps=2, schedule_steps=1000)
    return cell


def rehearse(cell, tmp_path, seconds=2.0):
    return runmod.run_cell(cell, SEED, seconds, 0, PEAKS,
                           cache_root=str(tmp_path / "cache"))


def check_line(cell, result):
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"  # and so never a device number
    assert set(cell["limits"]) <= set(result["compared"])
    json.dumps(result)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
         "raft_large.sintel_offline", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "Refusing" in p.stderr


@pytest.mark.parametrize("name", ["raft_small.sintel_offline", "raft_large.sintel_offline"])
def test_serve_rehearsal(name, tmp_path):
    cell = tiny(name)
    result = rehearse(cell, tmp_path)
    check_line(cell, result)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0


def test_serve_altered_answer_is_not_correct(tmp_path, monkeypatch):
    """An answer altered where it is produced (the engine's per-request
    output hook): every flow comes back mirrored."""
    from raft_tpu.serve.engine import ServeEngine

    monkeypatch.setattr(ServeEngine, "_request_flow",
                        lambda self, req, flow: 8.0 + flow[:, ::-1])
    result = rehearse(tiny("raft_small.sintel_offline"), tmp_path)
    assert not result["correct"]
    assert any(not c["ok"] for k, c in result["compared"].items() if k.startswith("flow"))


TRAIN = "raft_large.sintel_train"  # parked: brought back as files and entries


@pytest.fixture(scope="module")
def unparked(tmp_path_factory):
    return loader.unpark(TRAIN, str(tmp_path_factory.mktemp("unparked")))


@pytest.fixture(scope="module")
def train_rehearsal(tmp_path_factory, unparked):
    cell = tiny(TRAIN, unparked)
    return cell, rehearse(cell, tmp_path_factory.mktemp("train"), seconds=1.0)


def test_train_rehearsal(train_rehearsal):
    cell, result = train_rehearsal
    check_line(cell, result)
    assert result["correct"], result["compared"]


def _break_step(monkeypatch, wrap):
    """Plant a fault under ``Trainer``: ``wrap`` gets the real jitted step."""
    from raft_tpu.train.trainer import Trainer

    real = Trainer._make_step_fn
    monkeypatch.setattr(Trainer, "_make_step_fn", lambda self: wrap(real(self)))


def test_train_state_left_unchanged_is_not_correct(tmp_path, monkeypatch, unparked):
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def broken(state, batch):
            # copies first: the real step donates (deletes) what it is given
            params = jax.tree.map(jnp.array, state.params)
            opt_state = jax.tree.map(jnp.array, state.opt_state)
            new, metrics = step(state, batch)
            return new.replace(params=params, opt_state=opt_state), metrics
        return broken

    _break_step(monkeypatch, wrap)
    result = rehearse(tiny(TRAIN, unparked), tmp_path, seconds=1.0)
    assert not result["correct"]
    assert not result["compared"]["change_norm_gap"]["ok"]


def test_train_half_batch_is_not_correct(tmp_path, monkeypatch, unparked):
    """Half of the batch left out, the mean taken over the rest."""
    def wrap(step):
        return lambda state, batch: step(
            state, {k: np.concatenate([v[:1], v[:1]]) for k, v in batch.items()})

    _break_step(monkeypatch, wrap)
    result = rehearse(tiny(TRAIN, unparked), tmp_path, seconds=1.0)
    assert not result["correct"]


@pytest.mark.parametrize("config", ["raft_small", "raft_large"])
def test_serve_control_fails_the_limit(config):
    """The control — the reference in the program's place, computed in the
    precision the configuration names (fp8, the nearest below the stated
    bf16) — comes out of ``judge`` as not correct under the cell's limits
    at a size a test can hold (128x256, 32 iterations), where the stated
    precision (the reference rounded to bf16) is correct."""
    from benchmarks import inputs, weights
    from benchmarks.reference import raft as ref

    cell = loader.load_cell(f"{config}.sintel_offline")
    arch, prec = cell["config"]["arch"], cell["config"]["precision"]["serve"]
    variables = weights.make_variables(ref.param_shapes(arch), SEED, 0.01)
    pair = inputs.serve_pairs(SEED, 1, (124, 256))[0]
    flow = lambda p: cmp.reference_flow(arch, variables, pair, bucket=(128, 256),
                                        iters=32, precision=p)
    want = flow(prec["reference"])

    def verdict(got):
        stats = cmp.flow_stats(got, want)
        return runmod.judge({k: (stats[k], limit) for k, limit in cell["limits"].items()})

    compared, ok = verdict(flow(prec["control"]))
    assert not ok, compared
    compared, ok = verdict(flow("bf16"))
    assert ok, compared
