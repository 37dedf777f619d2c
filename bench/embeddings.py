"""Seeded sentence-embedding-like data of the BANKING77 shape.

BANKING77 (Casanueva et al. 2020, arXiv:2003.04807) holds 13,083
online-banking queries in 77 intents: 10,003 for training, 3,080 for
testing, 40 a class. No encoder runs here: the rows stand for the
pooled, L2-normalised output of a 768-wide sentence encoder and are
drawn from the seed alone, with NumPy only.

The geometry (``banking77_like``):

* every row has unit norm, as a normalised embedding has;
* the class centroids share one direction: ``cone`` of each unit
  centroid lies along it, the rest across it (sentence embeddings are
  anisotropic), so any two intents' centroids have a cosine of at
  least about ``cone**2``;
* the intents fall into ``n_topics`` topics (intent ``c`` in topic
  ``c % n_topics``); across the common direction an intent's centroid
  is its topic's direction, weighted ``topic``, plus one of its own, so
  intents of a topic crowd together as BANKING77's card, transfer or
  top-up intents do;
* a query leans towards another intent of its topic, by a share drawn
  half-normal with scale ``mix`` (queries that could be either intent;
  past one half it lies nearer the other), and carries isotropic
  Gaussian noise of norm about ``noise``; then it is normalised.

The training class sizes are an imbalance drawn once from a fixed
seed (``SIZES_SEED``) and summing to ``n_train``, the same on every
seed, so that every seed's one-vs-one tasks have the same sizes and
compile to the same fit programs. The test set holds ``n_test`` rows of
every class.
"""
from __future__ import annotations

import numpy as np

SIZES_SEED = 77


def class_sizes(n_classes: int, n_rows: int) -> np.ndarray:
    """``n_classes`` training sizes summing to ``n_rows``, each within
    half and one and a half of the mean, drawn from ``SIZES_SEED``."""
    w = np.random.default_rng(SIZES_SEED).uniform(0.5, 1.5, n_classes)
    share = w / w.sum() * n_rows
    sizes = np.floor(share).astype(np.int64)
    sizes[np.argsort(sizes - share)[:n_rows - sizes.sum()]] += 1
    return sizes


def _unit(v: np.ndarray) -> np.ndarray:
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def banking77_like(seed: int, *, n_classes: int = 77, d: int = 768,
                   n_train: int = 10_003, n_test: int = 40,
                   cone: float = 0.7, n_topics: int = 11,
                   topic: float = 1.0, mix: float = 0.3,
                   noise: float = 0.8):
    """(x_train, y_train, x_test, y_test): float32 unit rows, int64
    labels ``0..n_classes-1``; ``n_train`` training rows in the sizes of
    ``class_sizes`` and ``n_test`` test rows a class, each set shuffled."""
    if n_classes % n_topics or n_classes // n_topics < 2:
        raise ValueError(f"{n_classes} classes do not fall into topics of "
                         f"two or more of {n_topics} topics")
    rng = np.random.default_rng(seed)
    axis = _unit(rng.normal(size=d))

    def across(v):
        return _unit(v - np.outer(v @ axis, axis))

    topics = across(rng.normal(size=(n_topics, d)))
    of = np.arange(n_classes) % n_topics
    own = across(topic * topics[of] + across(rng.normal(size=(n_classes, d))))
    centroids = cone * axis + np.sqrt(1.0 - cone * cone) * own

    def draw(sizes):
        y = np.repeat(np.arange(n_classes), sizes)
        # a query leans towards another intent of its topic
        near = (y + n_topics * rng.integers(1, n_classes // n_topics,
                                            len(y))) % n_classes
        lean = np.abs(rng.normal(scale=mix, size=(len(y), 1)))
        g = rng.normal(scale=noise / np.sqrt(d), size=(len(y), d))
        x = _unit((1 - lean) * centroids[y] + lean * centroids[near] + g)
        perm = rng.permutation(len(y))
        return x[perm].astype(np.float32), y[perm]

    x_train, y_train = draw(class_sizes(n_classes, n_train))
    x_test, y_test = draw(np.full(n_classes, n_test))
    return x_train, y_train, x_test, y_test
