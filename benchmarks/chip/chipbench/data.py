"""The benchmark's own data sources, passed to the adapters as ``data=``.

Each is a stateless ``batch(step, size)`` like the program's synthetic
sources: the same seed and step give the same rows, and every step's
rows differ from every other step's.  Each call is a ``bench.data``
span in a trace.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np


class TokenSource:
    """Uniform token ids over the configuration's vocabulary, with the
    next token as the label."""

    def __init__(self, seed: int, vocab: int, seq_len: int):
        self.seed, self.vocab, self.seq_len = seed, vocab, seq_len

    def batch(self, step: int, size: int) -> Dict[str, np.ndarray]:
        with jax.profiler.TraceAnnotation("bench.data"):
            rng = np.random.default_rng([self.seed, step])
            t = rng.integers(0, self.vocab, (size, self.seq_len + 1),
                             dtype=np.int32)
            return {"tokens": t[:, :-1], "labels": t[:, 1:]}


class ImageSource:
    """CIFAR-10-shaped images: ``n_images`` 8-bit images and labels
    drawn once from the seed, normalised per batch as a CIFAR input
    pipeline does, and visited in a seeded order that changes every
    pass."""

    MEAN, STD = 127.5, 64.0

    def __init__(self, seed: int, n_images: int, image_size: int,
                 channels: int, num_classes: int):
        rng = np.random.default_rng([seed, 0x1A6E])
        self.seed = seed
        self.images = rng.integers(0, 256, (n_images, image_size,
                                            image_size, channels),
                                   dtype=np.uint8)
        self.labels = rng.integers(0, num_classes, n_images,
                                   dtype=np.int32)

    def rows(self, step: int, size: int) -> np.ndarray:
        n = len(self.labels)
        per_pass = n // size
        order = np.random.default_rng([self.seed, step // per_pass]
                                      ).permutation(n)
        k = step % per_pass
        return order[k * size:(k + 1) * size]

    def batch(self, step: int, size: int) -> Dict[str, np.ndarray]:
        with jax.profiler.TraceAnnotation("bench.data"):
            idx = self.rows(step, size)
            x = (self.images[idx].astype(np.float32) - self.MEAN) / self.STD
            return {"images": x, "labels": self.labels[idx]}
