"""The feature encoder's instance norm, and the two forms it runs in.

``layers.instance_norm`` takes ``(B, H, W, C)`` or, for fewer than 8
frames a device, ``(1, B, H, W, C)`` — frames on a depth axis of batch-1
convs (``layers.frames_conv``; why: PERF.md, PR 31). Both are held to
``flax.linen.InstanceNorm(use_bias=False, use_scale=False)``, values and
gradients, and the encoder is held to give a frame the same features
whichever form the batch it arrives in selects.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.models.encoders import FeatureEncoder
from raft_tpu.models.layers import (
    BottleneckBlock,
    ResidualBlock,
    frames_conv,
    instance_norm,
)

_ORACLE = nn.InstanceNorm(epsilon=1e-5, use_bias=False, use_scale=False)


def _oracle(x, relu):
    y = _ORACLE.apply({}, x)
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("channels", [32, 64, 128])
@pytest.mark.parametrize("frames", [2, 8])
@pytest.mark.parametrize("relu", [False, True], ids=["plain", "relu"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_instance_norm_matches_flax(rng, dtype, relu, frames, channels):
    """Values and gradient, batch form and depth form, against flax's
    InstanceNorm: fp32 statistics per frame and channel whatever the
    input dtype, output in the input dtype."""
    shape = (frames, 12, 20, channels)
    x = jnp.asarray(rng.normal(size=shape) * 3.0 + 1.5, dtype)
    w = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(
        rtol=2e-2, atol=2e-2
    )

    def loss(fn):
        return lambda x: jnp.sum(fn(x).astype(jnp.float32) * w)

    want = _oracle(x, relu)
    want_grad = jax.jit(jax.grad(loss(lambda x: _oracle(x, relu))))(x)
    forms = {
        "batch": lambda x: instance_norm(x, relu=relu),
        "depth": lambda x: instance_norm(x[None], relu=relu)[0],
    }
    for name, fn in forms.items():
        got = fn(x)
        assert got.dtype == x.dtype and got.shape == x.shape, name
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name, **tol,
        )
        got_grad = jax.jit(jax.grad(loss(fn)))(x)
        assert got_grad.dtype == x.dtype, name
        # a gradient's scale is 1/std of the frame (here ~1/3)
        np.testing.assert_allclose(
            np.asarray(got_grad, np.float32), np.asarray(want_grad, np.float32),
            err_msg=name, **tol,
        )
    # the two forms are the same sums: equal far inside the oracle's room
    np.testing.assert_allclose(
        np.asarray(forms["depth"](x), np.float32),
        np.asarray(forms["batch"](x), np.float32),
        rtol=0, atol=1e-5 if dtype == "float32" else 2 ** -6,
    )


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("ksize", [1, 3, 7])
def test_frames_conv_is_the_conv_of_each_frame(rng, ksize, stride):
    """The depth form's 3-D convolution (kernel one frame deep, batch 1)
    gives every frame the 2-D convolution's sums."""
    x = jnp.asarray(rng.normal(size=(3, 14, 18, 5)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(ksize, ksize, 5, 7)), jnp.float32)
    pad = ((ksize // 2,) * 2,) * 2
    want = frames_conv(x, k, (stride, stride), pad)
    got = frames_conv(x[None], k, (stride, stride), pad)
    assert got.shape == (1,) + want.shape
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("s2d_stem", [False, True], ids=["stem7x7", "stem-s2d"])
@pytest.mark.parametrize(
    "block,widths",
    [(ResidualBlock, (16, 16, 24, 32, 48)), (BottleneckBlock, (8, 8, 16, 24, 32))],
    ids=["residual", "bottleneck"],
)
def test_encoder_gives_a_frame_the_same_features_in_either_form(
    rng, block, widths, s2d_stem
):
    """Two frames go through the encoder in the depth form, eight in the
    batch form; instance norm is per frame, so the two frames' features —
    and the gradient of a loss on them — do not depend on which. One
    variable tree serves both (the checkpoint contract)."""
    enc = FeatureEncoder(
        block=block, widths=widths, norm="instance", s2d_stem=s2d_stem
    )
    eight = jnp.asarray(rng.uniform(-1, 1, size=(8, 16, 32, 3)), jnp.float32)
    two = eight[:2]
    variables = jax.jit(enc.init)(jax.random.PRNGKey(0), two)
    shapes = lambda tree: jax.tree.map(lambda v: v.shape, tree)
    assert shapes(variables) == shapes(
        jax.eval_shape(enc.init, jax.random.PRNGKey(0), eight)
    )

    def loss(params, x):
        out = enc.apply({"params": params}, x)
        return jnp.sum(out[:2] ** 2), out

    # jitted: op by op, the CPU's 3-D conv gradients take a minute
    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, out2), grad2 = step(variables["params"], two)
    (_, out8), grad8 = step(variables["params"], eight)
    assert out2.shape == (2, 2, 4, widths[-1])
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(out8[:2]), rtol=1e-4, atol=1e-4
    )
    flat2, flat8 = jax.tree.leaves(grad2), jax.tree.leaves(grad8)
    scale = max(float(jnp.abs(g).max()) for g in flat8)
    for g2, g8 in zip(flat2, flat8):
        np.testing.assert_allclose(
            np.asarray(g2), np.asarray(g8), rtol=1e-3, atol=1e-4 * scale
        )


@pytest.mark.parametrize("frames,depth", [(16, True), (32, False)])
def test_under_a_data_mesh_the_form_follows_a_devices_share(rng, frames, depth):
    """Traced under a mesh that shards the batch four ways over ``data``
    (the sharded train step, the serve mesh), 16 frames are 4 a device —
    the depth form, as on one chip — and 32 are 8: the batch form. The
    features are those of the program without a mesh."""
    from raft_tpu.parallel import make_mesh, traced_under
    from raft_tpu.parallel.mesh import batch_sharding, replicated

    mesh = make_mesh(data=4, space=1, devices=jax.devices()[:4])
    enc = FeatureEncoder(
        block=ResidualBlock, widths=(8, 8, 12, 16, 24), norm="instance"
    )
    x = jnp.asarray(rng.uniform(-1, 1, size=(frames, 16, 32, 3)), jnp.float32)
    variables = jax.jit(enc.init)(jax.random.PRNGKey(0), x[:2])
    sharded = jax.jit(
        traced_under(mesh, enc.apply),
        in_shardings=(replicated(mesh), batch_sharding(mesh)),
    )
    ranks = {
        len(eqn.params["dimension_numbers"].lhs_spec)
        for eqn in jax.make_jaxpr(traced_under(mesh, enc.apply))(
            variables, x
        ).eqns if eqn.primitive.name == "conv_general_dilated"
    }
    assert ranks == ({5} if depth else {4})
    np.testing.assert_allclose(
        np.asarray(sharded(variables, x)),
        np.asarray(jax.jit(enc.apply)(variables, x)),
        rtol=1e-4, atol=1e-4,
    )
