"""Device idle time by what the host was in: the exact intersection of the
device's idle gaps (``Summary.gaps``, chip 0, inside the window) with the
profiler regions (``Summary.host``) whose name matches ``regions``, over
the traced window, in %. A gap that lies under two regions is split
between them by time (``trace.breakdown`` gives the whole gap to one);
``complement`` gives the idle time under no such region instead. Regions
that do not overlap each other — the pool scheduler's phases are flat —
make the groups and the complement add up to ``idle_share``."""

import re

FAMILY = re.compile("^serve/")  # the program's profiler regions


def merged(intervals):
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def overlap(a, b):
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(obs, regions, complement=False):
    t = obs["trace"]
    if t.window_s <= 0 or not any(FAMILY.search(e[0]) for e in t.host):
        return None  # profiler regions off, or a program without them
    rx = re.compile(regions)
    spans = merged((s, s + d) for name, s, d in t.host if rx.search(name))
    under = overlap(t.gaps, spans)  # the gaps come sorted and disjoint
    if complement:
        under = sum(e - s for s, e in t.gaps) - under
    return 100.0 * under / t.window_s
