"""Synthetic MNIST-like digits, made on the host from the seed.

Ten fixed stroke glyphs (the same for every seed, so the task is the same
task) on a 28x28 canvas; each sample is its class glyph shifted by up to two
pixels, scaled in intensity and given pixel noise, clipped to [0, 1] like
MNIST.  Labels are balanced.  Nothing is downloaded.
"""

from __future__ import annotations

import numpy as np

SIZE = 28


def _glyphs() -> np.ndarray:
    rng = np.random.default_rng(0xD161)
    ys, xs = np.mgrid[0:SIZE, 0:SIZE].astype(np.float32)
    out = np.zeros((10, SIZE, SIZE), np.float32)
    for c in range(10):
        img = out[c]
        for _ in range(3):
            p0, p1 = rng.uniform(6, 22, 2), rng.uniform(6, 22, 2)
            for t in np.linspace(0.0, 1.0, 24):
                cy, cx = p0 + t * (p1 - p0)
                d2 = (ys - cy) ** 2 + (xs - cx) ** 2
                np.maximum(img, np.exp(-d2 / 1.3), out=img)
    return out


def make(rng: np.random.Generator, n: int):
    """``n`` images (n, 28, 28, 1) float32 and labels (n,) int32."""
    glyphs = _glyphs()
    labels = rng.permutation(np.arange(n) % 10).astype(np.int32)
    shifts = rng.integers(-2, 3, size=(n, 2))
    images = glyphs[labels]
    for dy in range(-2, 3):
        for dx in range(-2, 3):
            sel = (shifts[:, 0] == dy) & (shifts[:, 1] == dx)
            images[sel] = np.roll(images[sel], (dy, dx), axis=(1, 2))
    images *= rng.uniform(0.7, 1.0, (n, 1, 1)).astype(np.float32)
    images += 0.05 * rng.standard_normal(images.shape, dtype=np.float32)
    np.clip(images, 0.0, 1.0, out=images)
    return images[..., None], labels
