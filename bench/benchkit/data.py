"""Synthetic inputs, copied from the program's ``data/synthetic.py`` so
that a change to the program cannot move the benchmark's traffic.

The container has no network, so the paper's data sets (CERN jet
substructure tagging, MNIST) are stand-ins with the same shapes and class
counts.  Deterministic given the seed.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def jsc_synthetic(n: int, *, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """16 jet-substructure-like features, 5 classes.

    Class-conditional gaussian mixture pushed through a fixed random
    nonlinearity so classes are not linearly separable (mirrors the ~75%
    ceiling structure of the real task: overlapping classes)."""
    rng = np.random.default_rng(seed)
    gen = np.random.default_rng(1234)  # fixed task geometry across splits
    centers = gen.normal(0, 1.0, (5, 16))
    mix = gen.normal(0, 0.6, (16, 16))
    y = rng.integers(0, 5, n).astype(np.int32)
    x = centers[y] + rng.normal(0, 1.1, (n, 16))
    x = np.tanh(x @ mix) + 0.3 * x
    x = (x - x.mean(0)) / (x.std(0) + 1e-6)
    return x.astype(np.float32), y


def mnist_synthetic(n: int, *, seed: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """784-dim digit-like task, 10 classes.

    Ten fixed smooth prototype 28x28 images; samples = prototype shifted by
    +-2px + pixel noise + random per-sample contrast.  Hard enough that
    expressivity differences show, easy enough to train in seconds."""
    rng = np.random.default_rng(seed)
    gen = np.random.default_rng(4321)
    # smooth prototypes: superpositions of low-frequency 2D cosines
    xs = np.linspace(0, 1, 28)
    xx, yy = np.meshgrid(xs, xs)
    protos = []
    for c in range(10):
        img = np.zeros((28, 28))
        for _ in range(4):
            fx, fy = gen.uniform(1, 4, 2)
            px, py = gen.uniform(0, np.pi, 2)
            img += gen.uniform(0.4, 1.0) * np.cos(
                2 * np.pi * fx * xx + px) * np.cos(2 * np.pi * fy * yy + py)
        img = (img - img.min()) / (img.max() - img.min())
        protos.append(img)
    protos = np.stack(protos)

    y = rng.integers(0, 10, n).astype(np.int32)
    imgs = protos[y]
    sx = rng.integers(-2, 3, n)
    sy = rng.integers(-2, 3, n)
    out = np.empty((n, 28, 28), np.float32)
    for i in range(n):
        out[i] = np.roll(np.roll(imgs[i], sx[i], 0), sy[i], 1)
    out *= rng.uniform(0.8, 1.2, (n, 1, 1))
    out += rng.normal(0, 0.15, out.shape)
    return out.reshape(n, 784).astype(np.float32), y
