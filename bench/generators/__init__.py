"""Traffic generators: one module per kind of mix.

A generator's ``run(ctx)`` does the cell's set-up, calls
``ctx.window_open()`` when set-up is done, drives the system for
``ctx.seconds``, closes the window with ``ctx.window_close()``, checks
what the window produced against the reference, and returns a
``Outcome``. ``datasets`` builds a configuration's data from a seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import data


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: dict                 # end-to-end name -> value
    checks: list                  # (name, value, limit): value <= limit
    layer: dict                   # what the per-layer readers read
    notes: dict = dataclasses.field(default_factory=dict)


def kernel(cfg: dict) -> dict:
    """The reference's kernel, as the configuration states it."""
    return {"name": cfg["svc"]["kernel"], "gamma": float(cfg["svc"]["gamma"])}


def sub_seed(seed: int, *path: int) -> int:
    """A 63-bit seed derived from the run's seed and a path."""
    ss = np.random.SeedSequence([abs(int(seed)), int(seed < 0), *path])
    return int(ss.generate_state(2, np.uint64)[0] >> np.uint64(1))


def stratified_split(x, y, test_frac: float, seed: int):
    """Seeded split that holds out ``test_frac`` of every class, so that
    every seed gives tasks of the same sizes."""
    rng = np.random.default_rng(seed)
    tr, te = [], []
    for c in np.unique(y):
        idx = rng.permutation(np.flatnonzero(y == c))
        k = int(round(len(idx) * test_frac))
        te.append(idx[:k])
        tr.append(idx[k:])
    tr, te = np.sort(np.concatenate(tr)), np.sort(np.concatenate(te))
    return x[tr], y[tr], x[te], y[te]


def dataset(cfg: dict, seed: int):
    """(x_train, y_train, x_test, y_test) of a configuration."""
    shape = cfg["data"]
    kind = shape["generator"]
    if kind == "pavia_like":
        x, y = data.load_pavia_like(
            shape["n_per_class"], n_classes=shape["n_classes"],
            n_bands=shape["n_features"], noise=shape["noise"], seed=seed)
        x = data.normalize(x)
    elif kind == "ijcnn1_like":
        x, y = data.ijcnn1_like(
            shape["n_rows"], d=shape["n_features"],
            pos_frac=shape["pos_frac"], n_clusters=shape["n_clusters"],
            spread=shape["spread"], overlap=shape["overlap"], seed=seed)
    else:
        raise ValueError(f"unknown data generator {kind!r}")
    test_frac = shape.get("test_frac", 0.0)
    if test_frac <= 0:
        return x, y, x[:0], y[:0]
    return stratified_split(x, y, test_frac, sub_seed(seed, 1))
