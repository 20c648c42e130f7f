"""The whole step's share of the chip's peak: the FLOPs one pair needs
(``reduce/work.py``, from the configuration's sizes and the cell's shapes)
times the pairs per second the window completed, over peak FLOP/s."""

from benchmarks.reduce import work


def read(obs, rate, shape_key, train=False):
    cell, arch = obs["cell"], obs["config"]["arch"]
    pairs_per_s = obs["window"]["rates"].get(rate)
    if not pairs_per_s:
        return None
    h, w = cell[shape_key]
    flops = work.pair_flops(arch, h, w, cell["iters"], train=train)
    return 100.0 * flops * pairs_per_s / (obs["peaks"]["flops_per_s"] * cell["chips"])
