"""Inputs from ``--seed``: raw [0, 255] frame pairs for serving and an
in-memory synthetic flow dataset for training. Band-limited texture and a
shifted, perturbed copy, so the correlation volume has structure."""

from __future__ import annotations

import numpy as np


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def frame_pair(rng, hw, max_shift=6):
    h, w = hw
    cell = int(rng.choice([6, 8, 12]))
    coarse = rng.uniform(0, 255, (h // cell + 2, w // cell + 2, 3))
    im1 = np.kron(coarse, np.ones((cell, cell, 1)))[:h, :w]
    im1 = im1 + rng.normal(0, 4.0, im1.shape)
    dy, dx = (int(v) for v in rng.integers(-max_shift, max_shift + 1, 2))
    im2 = np.roll(im1, (dy, dx), axis=(0, 1)) + rng.normal(0, 2.0, im1.shape)
    clip = lambda x: np.clip(x, 0, 255).astype(np.float32)
    return clip(im1), clip(im2), (dx, dy)


def serve_pairs(seed: int, n: int, hw):
    rng = seeded_rng(seed, 2)
    return [frame_pair(rng, hw)[:2] for _ in range(n)]


class SyntheticFlowDataset:
    """``n`` Sintel-sized uint8 pairs with a known constant-shift flow."""

    def __init__(self, seed: int, n: int, hw):
        rng = seeded_rng(seed, 3)
        self.samples = []
        for _ in range(n):
            im1, im2, (dx, dy) = frame_pair(rng, hw)
            flow = np.zeros(tuple(hw) + (2,), np.float32)
            flow[..., 0], flow[..., 1] = dx, dy
            self.samples.append({
                "image1": im1.astype(np.uint8), "image2": im2.astype(np.uint8),
                "flow": flow, "valid": np.ones(tuple(hw), bool),
            })

    def __len__(self):
        return len(self.samples)

    def __getitem__(self, i):
        return {k: v.copy() for k, v in self.samples[i].items()}
