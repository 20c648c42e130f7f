"""A stream cell's share of the chip's peak: frames encoded a second x the
FLOPs a frame's two encoder passes need, plus pairs completed a second x
the FLOPs a pair's volume, updates and upsample need
(``reduce/work_video.py``), over peak FLOP/s. ``frames`` names the window
counter that counts frames admitted through the session cache; where the
program has no such counter the metric is left out."""

from benchmarks.reduce import work_video


def read(obs, rate, frames, shape_key):
    cell, arch, win = obs["cell"], obs["config"]["arch"], obs["window"]
    pairs_per_s = win["rates"].get(rate)
    n_frames = win["counters"].get(frames)
    if not pairs_per_s or not n_frames or not win.get("window_s"):
        return None
    h, w = cell[shape_key]
    flops_per_s = (
        n_frames / win["window_s"] * work_video.frame_flops(arch, h, w)
        + pairs_per_s * work_video.refine_flops(arch, h, w, cell["iters"]))
    return 100.0 * flops_per_s / (obs["peaks"]["flops_per_s"] * cell["chips"])
