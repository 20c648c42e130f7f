"""``scale * (1 - idle / total)`` or ``scale * num / den`` over the window's
counter deltas (``ServeEngine.stats()`` before and after)."""


def read(obs, num, den, scale=1.0, complement=False):
    c = obs["window"]["counters"]
    if not c.get(den):
        return None
    ratio = c.get(num, 0) / c[den]
    return scale * (1.0 - ratio if complement else ratio)
