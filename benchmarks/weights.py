"""Weights from ``--seed``: one jitted call on the device, fp32 (the type
both the program and the reference hold parameters in).

The benchmark makes its own weights (the reference may take nothing the
program has made): He-normal kernels (fan-out, as RAFT initialises),
small random biases and batch-norm statistics so that no term is an
identity, and — ``assumed`` in every configuration file — the flow head's
output conv scaled by ``flow_head_scale``: a raw random-init RAFT is not
contractive (32 iterations reach ~1000 px and any two precisions then
disagree by a third of the field, PERF.md PR 23), scaled it refines to
Sintel-like fields of tens of pixels.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A raw threefry key from any whole-number seed (seeds above 2**31
    do not fit ``jax.random.PRNGKey``)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _leaf(key, path, shape):
    name = path[-1]
    if name == "kernel":
        kh, kw, _, cout = shape
        return jax.random.normal(key, shape) * math.sqrt(2.0 / (kh * kw * cout))
    if name in ("scale", "var"):
        return jax.random.uniform(key, shape, minval=0.5, maxval=1.5)
    if name == "mean":
        return 0.1 * jax.random.normal(key, shape)
    if name == "bias":
        return 0.05 * jax.random.normal(key, shape)
    raise ValueError(f"no rule for parameter {'/'.join(path)}")


def _flatten(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flatten(v, prefix + (k,))
        else:
            yield prefix + (k,), tuple(v)


def make_variables(shapes, seed: int, flow_head_scale: float):
    """``shapes`` is ``reference.raft.param_shapes(arch)``; returns the
    variable tree as device arrays."""
    leaves = list(_flatten(shapes))

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out: dict = {}
        for k, (path, shape) in zip(keys, leaves):
            x = _leaf(k, path, shape).astype(jnp.float32)
            if path[-3:-1] == ("flow_head", "conv2"):
                x = x * flow_head_scale
            node = out
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = x
        return out

    return jax.jit(build)(seed_key(seed, 1))
