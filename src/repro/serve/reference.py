"""Plain float64 NumPy reference for served decision values.

``decision_values(model, z)`` recomputes ``K(z, SV_t) · coef_t + b_t``
for every task of a packed SV-bank model in float64, with nothing from
the serving path (no jit, no engine, no Pallas kernel): the oracle that
served decisions are held to, on the CPU in the tests and on the chip in
``chip_smoke.py``.

A served f32 decision may differ from it by rounding in the kernel
values and in the f32 accumulation over the support set, which grows
with the coefficient mass. ``tolerance`` states the bound the served
path must meet: ``|served - reference| <= RTOL * (1 + ||coef_t||_1)``
per task.
"""
from __future__ import annotations

import numpy as np

from repro.serve.artifact import PackedModel

# f32 kernel values carry ~1e-7 relative error; summed over the support
# set with |coef| weights the decision error stays below this fraction
# of the task's coefficient mass
RTOL = 1e-5


def gram64(kernel, z: np.ndarray, sv: np.ndarray) -> np.ndarray:
    """float64 kernel matrix K(z, sv) for ``KernelParams`` ``kernel``."""
    z = np.asarray(z, np.float64)
    sv = np.asarray(sv, np.float64)
    dot = z @ sv.T
    if kernel.name == "linear":
        return dot
    if kernel.name == "poly":
        return (kernel.gamma * dot + kernel.coef0) ** kernel.degree
    if kernel.name == "sigmoid":
        return np.tanh(kernel.gamma * dot + kernel.coef0)
    if kernel.name == "rbf":
        d2 = ((z * z).sum(1)[:, None] + (sv * sv).sum(1)[None, :]
              - 2.0 * dot)
        return np.exp(-kernel.gamma * np.maximum(d2, 0.0))
    raise ValueError(f"unknown kernel {kernel.name!r}")


def decision_values(model: PackedModel, z) -> np.ndarray:
    """(n_tasks, nt) float64 decisions of an SV-bank pack for rows ``z``."""
    if model.feature_map is not None:
        raise ValueError("the reference covers SV-bank packs; a low-rank "
                         "pack has no support vectors")
    z = np.asarray(z, np.float64)
    out = np.empty((model.n_tasks, z.shape[0]), np.float64)
    for g in model.buckets:
        sv_x = np.asarray(g.sv_x, np.float64)
        coef = np.asarray(g.sv_coef, np.float64)
        for j, t in enumerate(g.task_ids):
            k = int(g.sv_counts[j])
            out[t] = (gram64(model.kernel, z, sv_x[j, :k]) @ coef[j, :k]
                      + float(g.b[j]))
    return out


def tolerance(model: PackedModel) -> np.ndarray:
    """(n_tasks, 1) absolute bound on |served - reference| per task."""
    mass = np.zeros((model.n_tasks, 1), np.float64)
    for g in model.buckets:
        mass[g.task_ids, 0] = np.abs(
            np.asarray(g.sv_coef, np.float64)).sum(axis=1)
    return RTOL * (1.0 + mass)
