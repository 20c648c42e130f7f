"""The fused lookup + projection kernel's share of its roofline: the least
time the chip could take for the work one ``pool_step`` needs of it
(every slot's pixels; the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s — the bytes bound on this chip) times the steps traced, over
the kernel's device time in the trace."""

from benchmarks.reduce import trace, work


def read(obs, kernel, step_module, corr_bytes=2, out_bytes=2):
    cell, arch, t = obs["cell"], obs["config"]["arch"], obs["trace"]
    steps, _ = trace.module_time(t, step_module)
    n, kernel_s = trace.op_time(t, kernel)
    if not steps or not n or kernel_s <= 0:
        return None
    bh, bw = cell["bucket"]
    q = cell["serve"]["pool_capacity"] * (bh // 8) * (bw // 8)
    least = max(work.lookup_flops(arch, q) / obs["peaks"]["flops_per_s"],
                work.lookup_bytes(arch, q, corr_bytes, out_bytes)
                / obs["peaks"]["bytes_per_s"])
    return 100.0 * steps * least / kernel_s
