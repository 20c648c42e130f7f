"""The plain reference equals the program's plain ``dense`` model at a tiny
size, for both configurations: the forward pass, and the training loss and
gradients by the comparison's own measure."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader, weights
from benchmarks.reference import compare as cmp, raft as ref

CONFIGS = ("raft_large", "raft_small")


def _config(name):
    with open(os.path.join(loader.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _setup(name, seed=2**31 + 12345):
    from raft_tpu.models import build_raft, zoo

    config = _config(name)
    variables = weights.make_variables(ref.param_shapes(config["arch"]), seed, 0.01)
    model = build_raft(zoo.CONFIGS[name])
    rng = np.random.default_rng(0)
    im = lambda: jnp.asarray(rng.uniform(-1, 1, (2, 128, 160, 3)), jnp.float32)
    return config["arch"], variables, model, im(), im(), rng


@pytest.mark.parametrize("name", CONFIGS)
def test_param_tree_is_the_programs(name):
    from raft_tpu.models import build_raft, init_variables, zoo

    want = jax.eval_shape(lambda: init_variables(build_raft(zoo.CONFIGS[name])))
    got = ref.param_shapes(_config(name)["arch"])
    is_shape = lambda x: isinstance(x, tuple)
    want = jax.tree.map(lambda x: tuple(x.shape), want)
    assert jax.tree.structure(want, is_leaf=is_shape) == jax.tree.structure(got, is_leaf=is_shape)
    assert jax.tree.leaves(want, is_leaf=is_shape) == jax.tree.leaves(got, is_leaf=is_shape)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(got["params"], is_leaf=is_shape))
    assert n == _config(name)["parameters"]


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_equals_dense_model(name):
    arch, variables, model, im1, im2, _ = _setup(name)
    with jax.default_matmul_precision("highest"):
        want = model.apply(variables, im1, im2, train=False,
                           num_flow_updates=6, emit_all=False)
    got = jax.jit(lambda v, a, b: ref.forward(arch, v, a, b, iters=6))(variables, im1, im2)
    assert float(jnp.abs(want).max()) > 1.0  # a field worth comparing
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_gradients_equal_dense_model(name):
    from raft_tpu.train.loss import sequence_loss

    arch, variables, model, im1, im2, rng = _setup(name)
    gt = jnp.asarray(rng.normal(0, 3, (2, 128, 160, 2)), jnp.float32)
    valid = jnp.ones((2, 128, 160), jnp.float32)

    def loss_fn(params):
        v = {**variables, "params": params}
        if "batch_stats" in v:
            out, _ = model.apply(v, im1, im2, train=True, num_flow_updates=4,
                                 mutable=["batch_stats"])
        else:
            out = model.apply(v, im1, im2, train=True, num_flow_updates=4)
        return sequence_loss(out, gt, valid)[0]

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(loss_fn)(variables["params"])
    batch = {"image1": im1, "image2": im2, "flow": gt, "valid": valid}
    got_loss, got = jax.jit(lambda v, b: ref.loss_and_grads(arch, v, b, iters=4))(variables, batch)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    gap, leaf = cmp.worst_leaf_gap(cmp.leaf_norms(got), cmp.leaf_norms(want))
    assert gap < 1e-3, (gap, leaf)


def test_adamw_equals_optax():
    import optax

    rng = np.random.default_rng(1)
    params = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    from raft_tpu.train.optim import make_optimizer, one_cycle_lr

    sched = one_cycle_lr(1e-3, 1000)
    tx = make_optimizer(sched, weight_decay=1e-4, clip_norm=1.0)
    state, mine = tx.init(params), ref.adamw_init(params)
    p_opt = p_ref = params
    for step in range(3):
        grads = jax.tree.map(lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params)
        up, state = tx.update(grads, state, p_opt)
        p_opt = optax.apply_updates(p_opt, up)
        lr = ref.one_cycle_lr(step, max_lr=1e-3, total_steps=1000)
        assert lr == pytest.approx(float(sched(step)), rel=1e-5)
        p_ref, mine, _ = ref.adamw_update(p_ref, grads, mine, lr=lr)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]), np.asarray(p_opt[k]), rtol=2e-6, atol=1e-7)


def test_weights_follow_the_seed():
    arch = _config("raft_small")["arch"]
    shapes = ref.param_shapes(arch)
    a = weights.make_variables(shapes, 2**31 + 5, 0.01)
    b = weights.make_variables(shapes, 2**31 + 5, 0.01)
    c = weights.make_variables(shapes, 2**31 + 6, 0.01)
    leaf = lambda v: np.asarray(v["params"]["update_block"]["flow_head"]["conv2"]["kernel"])
    assert np.array_equal(leaf(a), leaf(b)) and not np.array_equal(leaf(a), leaf(c))
    # He-normal (fan-out 3*3*2) times the assumed x0.01 head
    assert leaf(a).std() == pytest.approx(0.01 * (2 / 18) ** 0.5, rel=0.1)


def test_precisions_order():
    """Lower precision, larger gap: what the control rests on."""
    arch, variables, _, im1, im2, _ = _setup("raft_small")
    run = lambda p: np.asarray(jax.jit(lambda v, a, b: ref.forward(
        arch, v, a, b, iters=6, precision=p))(variables, im1, im2))
    want = run("fp32")
    err = {p: cmp.flow_stats(run(p)[0], want[0])["flow_epe_mean_px"]
           for p in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"] / 3
