"""The work the algorithm needs, from shapes alone: FLOPs (a multiply-add
is two) and the bytes that must cross HBM. Never what today's programs
happen to do — a count tied to one implementation goes stale at the next
kernel PR and reads over 100%."""

from __future__ import annotations


def conv_flops(h, w, cin, cout, kh, kw):
    """A conv with an ``h x w`` output."""
    return 2.0 * h * w * cin * cout * kh * kw


def _block_flops(kind, h, w, cin, cout, stride):
    ho, wo = h // stride, w // stride
    if kind == "residual":
        f = conv_flops(ho, wo, cin, cout, 3, 3) + conv_flops(ho, wo, cout, cout, 3, 3)
    else:
        mid = cout // 4
        f = (conv_flops(h, w, cin, mid, 1, 1) + conv_flops(ho, wo, mid, mid, 3, 3)
             + conv_flops(ho, wo, mid, cout, 1, 1))
    if stride != 1:
        f += conv_flops(ho, wo, cin, cout, 1, 1)
    return f, ho, wo


def encoder_flops(widths, kind, h, w):
    """One frame through one encoder (7x7/2 stem, 3 stages, 1x1 head)."""
    stem, w1, w2, w3, out = widths
    hh, ww = h // 2, w // 2
    total = conv_flops(hh, ww, 3, stem, 7, 7)
    cin = stem
    for cout, stride in ((w1, 1), (w2, 2), (w3, 2)):
        f, hh, ww = _block_flops(kind, hh, ww, cin, cout, stride)
        total += f
        f, hh, ww = _block_flops(kind, hh, ww, cout, cout, 1)
        total += f
        cin = cout
    return total + conv_flops(hh, ww, w3, out, 1, 1)


def taps(arch):
    return arch["corr_levels"] * (2 * arch["corr_radius"] + 1) ** 2


def lookup_flops(arch, q):
    """Lookup + projection for ``q`` query pixels: each tap interpolates
    four cells (4 multiply-adds), then ``taps x width`` projection."""
    t = taps(arch)
    return q * (t * 4 * 2.0 + 2.0 * t * arch["motion_corr_widths"][0])


def lookup_bytes(arch, q, corr_bytes, out_bytes):
    """What one lookup + projection must touch: per pixel and level the
    (2r+2)^2 cells its bilinear taps read, the projection's weights once,
    and the projected output."""
    r, levels = arch["corr_radius"], arch["corr_levels"]
    width = arch["motion_corr_widths"][0]
    return (q * levels * (2 * r + 2) ** 2 * corr_bytes
            + taps(arch) * width * out_bytes + q * width * out_bytes)


def update_flops(arch, h8, w8):
    """One refinement iteration at the 1/8 grid, lookup included."""
    q = h8 * w8
    cw, fw = arch["motion_corr_widths"], arch["motion_flow_widths"]
    f = lookup_flops(arch, q)
    if len(cw) == 2:
        f += conv_flops(h8, w8, cw[0], cw[1], 3, 3)
    f += conv_flops(h8, w8, 2, fw[0], 7, 7) + conv_flops(h8, w8, fw[0], fw[1], 3, 3)
    f += conv_flops(h8, w8, cw[-1] + fw[1], arch["motion_out_channels"] - 2, 3, 3)
    hid = arch["gru_hidden"]
    gin = arch["context_encoder_widths"][-1] + arch["motion_out_channels"]
    for kh, kw in arch["gru_kernels"]:
        f += 3 * conv_flops(h8, w8, gin, hid, kh, kw)
    f += conv_flops(h8, w8, hid, arch["flow_head_hidden"], 3, 3)
    f += conv_flops(h8, w8, arch["flow_head_hidden"], 2, 3, 3)
    return f


def upsample_flops(arch, h8, w8):
    if not arch["use_mask_predictor"]:
        return 2.0 * 2 * (8 * h8) * w8 * h8 + 2.0 * 2 * (8 * h8) * (8 * w8) * w8
    mh = arch["mask_predictor_hidden"]
    return (conv_flops(h8, w8, arch["gru_hidden"], mh, 3, 3)
            + conv_flops(h8, w8, mh, 576, 1, 1) + 2.0 * h8 * w8 * 64 * 9 * 2)


def pair_flops(arch, h, w, iters, *, train=False):
    """One pair's forward pass: two frames through the feature encoder and
    one through the context encoder (three encoder passes), the all-pairs
    volume, ``iters`` updates, and the upsample (once when serving, after
    every update when training). ``train`` adds the backward pass at twice
    the forward; recomputation is not counted."""
    h8, w8 = h // 8, w // 8
    q = h8 * w8
    f = 2 * encoder_flops(arch["feature_encoder_widths"], arch["feature_encoder_block"], h, w)
    f += encoder_flops(arch["context_encoder_widths"], arch["context_encoder_block"], h, w)
    f += 2.0 * q * q * arch["feature_encoder_widths"][-1]
    f += iters * update_flops(arch, h8, w8)
    f += (iters if train else 1) * upsample_flops(arch, h8, w8)
    return 3.0 * f if train else f
