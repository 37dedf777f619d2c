"""Seeded data generators of the benchmark's configurations.

Copies, kept with the benchmark so that a change to the program cannot
move the yardstick: ``load_pavia_like`` (``repro.data.synth``) and
``normalize`` (``repro.data.pipeline``); the split is
``bench.generators.stratified_split``. New: a generator of the LIBSVM
ijcnn1 shape (``ijcnn1_like``). Everything is NumPy and a function of
its seed alone.
"""
from __future__ import annotations

import numpy as np


def load_pavia_like(n_per_class: int = 800, *, n_classes: int = 9,
                    n_bands: int = 102, seed: int = 7,
                    noise: float = 0.15) -> tuple[np.ndarray, np.ndarray]:
    """Hyperspectral-like: each class is a smooth spectral signature."""
    rng = np.random.default_rng(seed)
    wav = np.linspace(0.0, 1.0, n_bands)
    xs, ys = [], []
    for c in range(n_classes):
        # smooth class signature: low-order Fourier mixture
        coef = rng.normal(size=(6,))
        phase = rng.uniform(0, 2 * np.pi, size=(6,))
        sig = sum(coef[k] * np.sin(2 * np.pi * (k + 1) * wav + phase[k])
                  for k in range(6))
        sig = sig + rng.uniform(1.0, 3.0)  # reflectance offset
        # per-pixel: signature * illumination + correlated band noise
        illum = rng.uniform(0.7, 1.3, size=(n_per_class, 1))
        band_noise = rng.normal(scale=noise, size=(n_per_class, n_bands))
        # correlate the noise along the band axis (moving average)
        kern = np.ones(7) / 7.0
        band_noise = np.apply_along_axis(
            lambda v: np.convolve(v, kern, mode="same"), 1, band_noise)
        xs.append((sig[None, :] * illum + band_noise).astype(np.float32))
        ys.append(np.full(n_per_class, c, np.int64))
    x = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]


def normalize(x: np.ndarray) -> np.ndarray:
    """Zero mean, unit variance per feature."""
    x = np.asarray(x, np.float32)
    mu = x.mean(0, keepdims=True)
    sd = x.std(0, keepdims=True)
    return (x - mu) / np.maximum(sd, 1e-8)


def ijcnn1_like(n: int = 49_990, *, d: int = 22, pos_frac: float = 0.0971,
                n_clusters: int = 6, spread: float = 1.0,
                overlap: float = 0.6, seed: int = 0
                ) -> tuple[np.ndarray, np.ndarray]:
    """Rows of the LIBSVM ijcnn1 shape: ``n`` rows, ``d`` features scaled
    to [-1, 1], a ``pos_frac`` share of positives, labels in {0, 1}.

    Each class is a mixture of ``n_clusters`` Gaussian clusters with
    random correlated covariances; every positive cluster sits next to a
    negative one, displaced by ``overlap`` of the cluster spread, so the
    classes overlap and many rows end up on or inside the margin. The
    exact number of positives is ``round(n * pos_frac)`` on every seed.
    """
    rng = np.random.default_rng(seed)
    n_pos = int(round(n * pos_frac))
    counts = {0: n - n_pos, 1: n_pos}
    centers = rng.normal(scale=2.0 * spread, size=(n_clusters, d))
    xs, ys = [], []
    for label, count in counts.items():
        which = rng.integers(0, n_clusters, size=count)
        shift = (overlap * spread * rng.normal(size=(n_clusters, d))
                 if label == 1 else np.zeros((n_clusters, d)))
        mix = rng.normal(scale=spread / np.sqrt(d),
                         size=(n_clusters, d, d)) + np.eye(d) * 0.5
        z = rng.normal(size=(count, d))
        rows = np.einsum("nd,nde->ne", z, mix[which]) + (
            centers + shift)[which]
        xs.append(rows)
        ys.append(np.full(count, label, np.int64))
    x = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    lo, hi = x.min(0, keepdims=True), x.max(0, keepdims=True)
    x = 2.0 * (x - lo) / np.maximum(hi - lo, 1e-12) - 1.0
    perm = rng.permutation(n)
    return x[perm].astype(np.float32), y[perm]
