"""Device time of one compiled program per execution, in ms: the durations
of its events on the trace's ``XLA Modules`` line over their count."""

from benchmarks.reduce import trace


def read(obs, module):
    n, total = trace.module_time(obs["trace"], module)
    return 1e3 * total / n if n else None
