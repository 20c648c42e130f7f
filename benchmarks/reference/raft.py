"""The benchmark's plain reference: RAFT (Teed & Deng, arXiv:2003.12039) in
straightforward ``jax.numpy``.

fp32, every contraction at ``Precision.HIGHEST`` unless the precision a
configuration states says otherwise; no kernel, no slot pool, no batching
trick, nothing imported from the program. It reads the same
parameter tree the program reads (the names of a torchvision checkpoint
converted to flax), and is built from a configuration file's ``arch``
sizes alone (``benchmarks/configs/<name>.json``).

``precision`` selects how the *same* mathematics is rounded:

* ``"fp32"``  — the reference;
* ``"bf16"``  — conv inputs/weights and the correlation volume rounded to
  bfloat16 (what the configurations state for serving); used by the seed
  study and the control's test, never by a run;
* ``"fp8"``   — the same places rounded to float8_e4m3 (outputs to
  bfloat16): the *control*, the nearest precision below the stated one,
  which the comparison has to fail;
* ``"default_bf16corr"`` — what the configurations state for training:
  fp32 storage, convolutions and the all-pairs product multiplied at the
  backend's *default* matmul precision (on a TPU one bfloat16 pass with
  fp32 accumulation, forward and backward; on a CPU plain fp32), the
  correlation volume stored in bfloat16.

Departures from the paper, shared with torchvision: biases on every conv,
relu after the residual sum, mask predictor x0.25, ``raft_small`` upsamples
bilinearly (align_corners) instead of convexly.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# what a precision rounds: (conv operands, conv outputs, correlation volume),
# and how convolutions and the all-pairs product multiply
PRECISIONS = {
    "fp32": (None, None, None, HI),
    "bf16": (jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, HI),
    "fp8": (F8, jnp.bfloat16, F8, HI),
    "default_bf16corr": (None, None, jnp.bfloat16, None),
}


def _round(x, dtype):
    """Round ``x`` to ``dtype`` and carry on in fp32."""
    if dtype is None:
        return x
    if dtype == F8:
        x = jnp.clip(x, -F8_MAX, F8_MAX)
    return x.astype(dtype).astype(jnp.float32)


class _Ops:
    """The rounding points of one precision."""

    def __init__(self, precision: str):
        self.operand, self.output, self.corr, self.matmul = PRECISIONS[precision]

    def conv(self, x, p, stride=1, padding=None, exact=False):
        k = p["kernel"]
        kh, kw = k.shape[0], k.shape[1]
        if padding is None:
            padding = ((kh - 1) // 2, (kw - 1) // 2)
        pad = ((padding[0], padding[0]), (padding[1], padding[1]))
        op = None if exact else self.operand
        y = lax.conv_general_dilated(
            _round(x, op), _round(k, op), (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=self.matmul,
        )
        y = y + _round(p["bias"], op)
        return y if exact else _round(y, self.output)


# -- norms ------------------------------------------------------------------

def _instance_norm(x, eps=1e-5):
    mu = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(1, 2), keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _batch_norm(x, p, stats, train, eps=1e-5):
    if train:  # statistics of this batch (running averages unused)
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mu)
    else:
        mu, var = stats["mean"], stats["var"]
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _conv_norm_act(ops, x, p, stats, *, stride, norm, act, train):
    y = ops.conv(x, p["layers_0"], stride)
    if norm == "instance":
        y = _instance_norm(y)
    elif norm == "batch":
        y = _batch_norm(y, p["layers_1"], (stats or {}).get("layers_1"), train)
    return jax.nn.relu(y) if act else y


# -- encoder ------------------------------------------------------------------

def _block(ops, x, p, stats, *, kind, stride, norm, train):
    stats = stats or {}
    cna = lambda name, h, s, act=True: _conv_norm_act(
        ops, h, p[name], stats.get(name), stride=s, norm=norm, act=act,
        train=train,
    )
    if kind == "residual":
        y = cna("convnormrelu1", x, stride)
        y = cna("convnormrelu2", y, 1)
    else:  # bottleneck: 1x1 (C/4) -> 3x3 (C/4, stride) -> 1x1 (C)
        y = cna("convnormrelu1", x, 1)
        y = cna("convnormrelu2", y, stride)
        y = cna("convnormrelu3", y, 1)
    if stride != 1:
        x = cna("downsample", x, stride, act=False)
    return jax.nn.relu(x + y)


def encoder(ops, x, p, stats, *, kind, norm, train):
    """7x7/2 stem, three 2-block stages (strides 1, 2, 2), 1x1 head: /8."""
    stats = stats or {}
    x = _conv_norm_act(ops, x, p["convnormrelu"], stats.get("convnormrelu"),
                       stride=2, norm=norm, act=True, train=train)
    for name, stride in (("layer1", 1), ("layer2", 2), ("layer3", 2)):
        st = stats.get(name, {})
        x = _block(ops, x, p[name]["layers_0"], st.get("layers_0"),
                   kind=kind, stride=stride, norm=norm, train=train)
        x = _block(ops, x, p[name]["layers_1"], st.get("layers_1"),
                   kind=kind, stride=1, norm=norm, train=train)
    return ops.conv(x, p["conv"])


# -- correlation ----------------------------------------------------------------

def corr_pyramid(ops, fmap1, fmap2, levels):
    """All-pairs volume / sqrt(C), then 2x2 average pooling of the target
    dims: a list of ``(B, Q, hl, wl)`` levels."""
    b, h, w, c = fmap1.shape
    f1 = _round(fmap1.reshape(b, h * w, c), ops.operand)
    f2 = _round(fmap2.reshape(b, h * w, c), ops.operand)
    vol = jnp.einsum("bqc,btc->bqt", f1, f2, precision=ops.matmul) / math.sqrt(c)
    vol = _round(vol.reshape(b, h * w, h, w), ops.corr)
    pyramid = [vol]
    for _ in range(levels - 1):
        hl, wl = vol.shape[2] // 2, vol.shape[3] // 2
        vol = vol[:, :, : 2 * hl, : 2 * wl].reshape(b, h * w, hl, 2, wl, 2)
        vol = _round(vol.mean(axis=(3, 5)), ops.corr)
        pyramid.append(vol)
    return pyramid


def corr_lookup(pyramid, coords, radius):
    """Bilinear (2r+1)^2 taps around ``coords / 2^l`` at every level, zeros
    outside. Bilinear interpolation is separable, so each level is
    ``taps[q, i, j] = sum_yx wx[q, i, x] wy[q, j, y] vol[q, y, x]`` with
    ``w[k] = max(0, 1 - |pos - k|)``; tap ``i`` offsets x, ``j`` offsets y
    (torchvision's channel order)."""
    b, h, w, _ = coords.shape
    s = 2 * radius + 1
    off = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    cent = coords.reshape(b, h * w, 2)
    feats = []
    for level, vol in enumerate(pyramid):
        hl, wl = vol.shape[2], vol.shape[3]
        px = cent[..., 0:1] / (2.0 ** level) + off          # (B, Q, S)
        py = cent[..., 1:2] / (2.0 ** level) + off
        wx = jax.nn.relu(1.0 - jnp.abs(px[..., None] - jnp.arange(wl, dtype=jnp.float32)))
        wy = jax.nn.relu(1.0 - jnp.abs(py[..., None] - jnp.arange(hl, dtype=jnp.float32)))
        rows = jnp.einsum("bqjy,bqyx->bqjx", wy, vol, precision=HI)
        taps = jnp.einsum("bqix,bqjx->bqij", wx, rows, precision=HI)
        feats.append(taps.reshape(b, h, w, s * s))
    return jnp.concatenate(feats, axis=-1)


# -- update block -----------------------------------------------------------------

def _motion_encoder(ops, p, flow, corr_feats):
    relu = jax.nn.relu
    c = relu(ops.conv(corr_feats, p["convcorr1"]["layers_0"]))
    if "convcorr2" in p:
        c = relu(ops.conv(c, p["convcorr2"]["layers_0"]))
    f = relu(ops.conv(flow, p["convflow1"]["layers_0"]))
    f = relu(ops.conv(f, p["convflow2"]["layers_0"]))
    joint = relu(ops.conv(jnp.concatenate([c, f], -1), p["conv"]["layers_0"]))
    return jnp.concatenate([joint, flow], -1)


def _conv_gru(ops, p, h, x, pad):
    hx = jnp.concatenate([h, x], -1)
    z = jax.nn.sigmoid(ops.conv(hx, p["convz"], padding=pad))
    r = jax.nn.sigmoid(ops.conv(hx, p["convr"], padding=pad))
    q = jnp.tanh(ops.conv(jnp.concatenate([r * h, x], -1), p["convq"], padding=pad))
    return (1.0 - z) * h + z * q


def _update(ops, arch, p, hidden, context, corr_feats, flow):
    motion = _motion_encoder(ops, p["motion_encoder"], flow, corr_feats)
    x = jnp.concatenate([context, motion], -1)
    for i, pad in enumerate(arch["gru_pads"]):
        hidden = _conv_gru(ops, p["recurrent_block"][f"convgru{i + 1}"],
                           hidden, x, tuple(pad))
    fh = p["flow_head"]
    y = jax.nn.relu(ops.conv(hidden, fh["conv1"]))
    return hidden, ops.conv(y, fh["conv2"], exact=True)  # fp32 head


# -- upsampling -----------------------------------------------------------------

def _upsample_convex(flow, mask, factor=8):
    n, h, w, c = flow.shape
    weights = jax.nn.softmax(mask.reshape(n, h, w, 9, factor, factor), axis=3)
    padded = jnp.pad(flow * factor, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = jnp.stack([padded[:, di:di + h, dj:dj + w, :]
                      for di in range(3) for dj in range(3)], axis=3)
    up = jnp.einsum("nhwkc,nhwkab->nhawbc", taps, weights, precision=HI)
    return up.reshape(n, h * factor, w * factor, c)


def _interp_matrix(n_in, n_out):
    src = jnp.arange(n_out, dtype=jnp.float32) * ((n_in - 1.0) / (n_out - 1.0))
    return jax.nn.relu(1.0 - jnp.abs(src[:, None] - jnp.arange(n_in, dtype=jnp.float32)))


def _upsample_bilinear(flow, factor=8):
    """align_corners=True bilinear resize, vectors scaled by ``factor``."""
    n, h, w, c = flow.shape
    up = jnp.einsum("oh,nhwc->nowc", _interp_matrix(h, h * factor), flow, precision=HI)
    up = jnp.einsum("ow,nhwc->nhoc", _interp_matrix(w, w * factor), up, precision=HI)
    return up * factor


def _upsample(ops, arch, params, flow, hidden):
    if not arch["use_mask_predictor"]:
        return _upsample_bilinear(flow)
    mp = params["mask_predictor"]
    m = jax.nn.relu(ops.conv(hidden, mp["convrelu"]["layers_0"]))
    mask = 0.25 * ops.conv(m, mp["conv"], padding=(0, 0), exact=True)
    return _upsample_convex(flow, mask)


# -- the model ------------------------------------------------------------------

def forward(arch: Dict[str, Any], variables, image1, image2, *, iters: int,
            train: bool = False, all_flows: bool = False,
            precision: str = "fp32", remat: bool = False):
    """Flow from ``image1`` to ``image2`` (``(B, H, W, 3)`` in [-1, 1],
    H and W multiples of 8). Returns the final ``(B, H, W, 2)`` flow, or
    with ``all_flows`` the ``(iters, B, H, W, 2)`` stack training needs.
    ``remat`` recomputes each iteration in the backward pass (same
    numbers, less memory)."""
    ops = _Ops(precision)
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    b = image1.shape[0]
    fmaps = encoder(
        ops, jnp.concatenate([image1, image2], 0), params["feature_encoder"],
        stats.get("feature_encoder"), kind=arch["feature_encoder_block"],
        norm=arch["feature_encoder_norm"], train=train,
    )
    fmap1, fmap2 = fmaps[:b], fmaps[b:]
    ctx = encoder(
        ops, image1, params["context_encoder"], stats.get("context_encoder"),
        kind=arch["context_encoder_block"], norm=arch["context_encoder_norm"],
        train=train,
    )
    hid = arch["gru_hidden"]
    hidden, context = jnp.tanh(ctx[..., :hid]), jax.nn.relu(ctx[..., hid:])
    pyramid = corr_pyramid(ops, fmap1, fmap2, arch["corr_levels"])

    h8, w8 = fmap1.shape[1], fmap1.shape[2]
    xs, ys = jnp.meshgrid(jnp.arange(w8, dtype=jnp.float32),
                          jnp.arange(h8, dtype=jnp.float32), indexing="xy")
    coords0 = jnp.broadcast_to(jnp.stack([xs, ys], -1)[None], (b, h8, w8, 2))

    def step(carry, _):
        coords1, hidden = carry
        coords1 = lax.stop_gradient(coords1)
        feats = corr_lookup(pyramid, coords1, arch["corr_radius"])
        hidden, delta = _update(ops, arch, params["update_block"], hidden,
                                context, feats, coords1 - coords0)
        coords1 = coords1 + delta
        out = (_upsample(ops, arch, params, coords1 - coords0, hidden)
               if all_flows else None)
        return (coords1, hidden), out

    if remat:
        step = jax.checkpoint(step)
    (coords1, hidden), flows = lax.scan(step, (coords0, hidden), None, length=iters)
    if all_flows:
        return flows
    return _upsample(ops, arch, params, coords1 - coords0, hidden)


# -- training: loss, gradients, AdamW -----------------------------------------------

def sequence_loss(flows, flow_gt, valid, *, gamma=0.8, max_flow=400.0):
    """sum_i gamma^(N-1-i) * mean over valid pixels of |f_i - gt|_1."""
    n = flows.shape[0]
    mask = (jnp.sqrt(jnp.sum(jnp.square(flow_gt), -1)) < max_flow) & (valid > 0.5)
    maskf = mask.astype(jnp.float32)
    per_iter = jnp.sum(jnp.abs(flows - flow_gt[None]).sum(-1) * maskf[None],
                       axis=(1, 2, 3)) / jnp.maximum(maskf.sum(), 1.0)
    weights = gamma ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)
    return jnp.sum(weights * per_iter)


def loss_and_grads(arch, variables, batch, *, iters, precision="fp32",
                   gamma=0.8, max_flow=400.0):
    def loss_fn(params):
        flows = forward(arch, {**variables, "params": params},
                        batch["image1"], batch["image2"], iters=iters,
                        train=True, all_flows=True, precision=precision,
                        remat=True)
        return sequence_loss(flows, batch["flow"], batch["valid"],
                             gamma=gamma, max_flow=max_flow)

    return jax.value_and_grad(loss_fn)(variables["params"])


def one_cycle_lr(step, *, max_lr, total_steps, pct_start=0.05,
                 div_factor=25.0, final_div_factor=1e4):
    """torch ``OneCycleLR(anneal_strategy='linear')``."""
    init = max_lr / div_factor
    warm = max(int(pct_start * total_steps), 1)
    if step < warm:
        return init + (max_lr - init) * step / warm
    rest = max(total_steps - warm, 1)
    frac = min((step - warm) / rest, 1.0)
    return max_lr + (init / final_div_factor - max_lr) * frac


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": 0}


def clip_by_global_norm(grads, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < clip, 1.0, clip / norm)
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_update(params, grads, state, *, lr, clip=1.0, b1=0.9, b2=0.999,
                 eps=1e-8, weight_decay=1e-4):
    """Global-norm clipping, then AdamW (decoupled decay). Returns the new
    parameters, the new state and the clipped gradient."""
    grads = clip_by_global_norm(grads, clip)
    t = state["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p),
        params, m, v,
    )
    return new, {"m": m, "v": v, "t": t}, grads


# -- parameter shapes, for whoever makes weights --------------------------------------

def param_shapes(arch) -> Dict[str, Any]:
    """The variable tree's shapes (``params`` and, with batch norm,
    ``batch_stats``) for ``arch`` — from the sizes alone."""
    params: Dict[str, Any] = {}
    stats: Dict[str, Any] = {}

    def conv(cin, cout, k):
        kh, kw = (k, k) if isinstance(k, int) else k
        return {"kernel": (kh, kw, cin, cout), "bias": (cout,)}

    def cna(cin, cout, k, norm):
        p = {"layers_0": conv(cin, cout, k)}
        s = None
        if norm == "batch":
            p["layers_1"] = {"scale": (cout,), "bias": (cout,)}
            s = {"layers_1": {"mean": (cout,), "var": (cout,)}}
        return p, s

    def block(cin, cout, kind, stride, norm):
        mid = cout // 4
        plan = ([("convnormrelu1", cin, cout, 3), ("convnormrelu2", cout, cout, 3)]
                if kind == "residual" else
                [("convnormrelu1", cin, mid, 1), ("convnormrelu2", mid, mid, 3),
                 ("convnormrelu3", mid, cout, 1)])
        if stride != 1:
            plan.append(("downsample", cin, cout, 1))
        p, s = {}, {}
        for name, a, o, k in plan:
            p[name], st = cna(a, o, k, norm)
            if st:
                s[name] = st
        return p, s

    def enc(widths, kind, norm):
        stem, w1, w2, w3, out = widths
        p, s = {}, {}
        p["convnormrelu"], st = cna(3, stem, 7, norm)
        if st:
            s["convnormrelu"] = st
        cin = stem
        for name, cout, stride in (("layer1", w1, 1), ("layer2", w2, 2), ("layer3", w3, 2)):
            p[name], s[name] = {}, {}
            for blk, (a, sd) in (("layers_0", (cin, stride)), ("layers_1", (cout, 1))):
                p[name][blk], st = block(a, cout, kind, sd, norm)
                if st:
                    s[name][blk] = st
            if not s[name]:
                del s[name]
            cin = cout
        p["conv"] = conv(w3, out, 1)
        return p, s

    for name in ("feature_encoder", "context_encoder"):
        p, s = enc(arch[f"{name}_widths"], arch[f"{name}_block"], arch[f"{name}_norm"])
        params[name] = p
        if s:
            stats[name] = s

    taps = arch["corr_levels"] * (2 * arch["corr_radius"] + 1) ** 2
    cw, fw = arch["motion_corr_widths"], arch["motion_flow_widths"]
    me = {"convcorr1": {"layers_0": conv(taps, cw[0], 1)},
          "convflow1": {"layers_0": conv(2, fw[0], 7)},
          "convflow2": {"layers_0": conv(fw[0], fw[1], 3)},
          "conv": {"layers_0": conv(cw[-1] + fw[1], arch["motion_out_channels"] - 2, 3)}}
    if len(cw) == 2:
        me["convcorr2"] = {"layers_0": conv(cw[0], cw[1], 3)}
    hid = arch["gru_hidden"]
    ctx_ch = arch["context_encoder_widths"][-1] - hid
    gin = hid + ctx_ch + arch["motion_out_channels"]
    rb = {f"convgru{i + 1}": {g: conv(gin, hid, tuple(k)) for g in ("convz", "convr", "convq")}
          for i, k in enumerate(arch["gru_kernels"])}
    params["update_block"] = {
        "motion_encoder": me, "recurrent_block": rb,
        "flow_head": {"conv1": conv(hid, arch["flow_head_hidden"], 3),
                      "conv2": conv(arch["flow_head_hidden"], 2, 3)},
    }
    if arch["use_mask_predictor"]:
        mh = arch["mask_predictor_hidden"]
        params["mask_predictor"] = {"convrelu": {"layers_0": conv(hid, mh, 3)},
                                    "conv": conv(mh, 8 * 8 * 9, 1)}
    out = {"params": params}
    if stats:
        out["batch_stats"] = stats
    return out
