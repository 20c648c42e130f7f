"""Video clips from ``--seed``: raw [0, 255] uint8 frames of one scene that
moves. A band-limited texture as ``inputs.frame_pair``'s (a coarse random
grid blown up by 6, 8 or 12, periodic), shifted 0-6 px a frame along a
smooth seeded path, with fresh sensor noise on every frame — so that
consecutive frames share their content, every pair has structure in its
correlation volume, and no two frames of a clip are the same array.
Sintel's clips are 20 to 50 frames long; so are these."""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.inputs import seeded_rng


def clip(rng, hw, n_frames: int, max_shift: int = 6) -> List[np.ndarray]:
    h, w = hw
    cell = int(rng.choice([6, 8, 12]))
    coarse = rng.uniform(0, 255, (h // cell + 2, w // cell + 2, 3))
    base = np.kron(coarse, np.ones((cell, cell, 1)))[:h, :w].astype(np.float32)
    # a smooth path: a velocity that turns slowly, at most max_shift px a
    # frame on each axis; positions rounded to whole px (the frames are
    # rolled, as frame_pair's second frame is)
    turn = rng.uniform(0.05, 0.25, 2)
    phase = rng.uniform(0, 2 * np.pi, 2)
    amp = rng.uniform(0.3, 1.0, 2) * max_shift
    t = np.arange(n_frames)
    vel = amp[None, :] * np.sin(turn[None, :] * t[:, None] + phase[None, :])
    pos = np.rint(np.cumsum(vel, axis=0)).astype(int)
    frames = []
    for dx, dy in pos:
        f = np.roll(base, (int(dy), int(dx)), axis=(0, 1))
        f = f + rng.standard_normal(f.shape, dtype=np.float32) * 3.0
        frames.append(np.clip(np.rint(f), 0, 255).astype(np.uint8))
    return frames


def clips(seed: int, n: int, hw, lengths=(20, 50)) -> List[List[np.ndarray]]:
    """``n`` distinct clips, lengths drawn uniformly from ``lengths``
    (both ends included)."""
    rng = seeded_rng(seed, 6)
    lens = rng.integers(lengths[0], lengths[1] + 1, n)
    return [clip(rng, hw, int(k)) for k in lens]
