"""The system under test, through its public entry points only.

This is the one module of the benchmark that imports the program:
``SVC`` to fit, ``serve.pack`` for the fitted model as served, and
``Predictor`` with ``ServingService`` to serve it. ``control=True`` turns
on the program's own lower-precision path, the bfloat16 Gram
(``EngineConfig(gram_dtype="bf16")``): the control that the comparison
with the reference has to fail.
"""
from __future__ import annotations

import numpy as np


def svc(cfg: dict, params: dict, *, mesh_devices=None, control=False):
    """An unfitted ``SVC`` with the configuration's public arguments;
    ``shard="data"`` in ``params`` shards the samples over a mesh of
    ``mesh_devices``."""
    from repro.core.kernel_engine import EngineConfig
    from repro.core.svm import SVC
    kw = dict(cfg["svc"])
    if control:
        kw["engine"] = EngineConfig(backend=kw.get("engine", "auto"),
                                    gram_dtype="bf16")
    if params.get("shard") == "data":
        from repro.launch.mesh import make_shard_mesh
        kw.update(mesh=make_shard_mesh(len(mesh_devices)),
                  worker_axes=("shards",), shard="data")
    return SVC(**kw)


def pack(clf):
    """The fitted model as the program serves it (``serve.pack``): one
    object for the banks under test and the predictor alike."""
    from repro import serve
    return serve.pack(clf)


def banks(packed) -> dict:
    """Host copies of what the packed model serves: its class table,
    vote routing and per bucket (task_ids, sv_x, coef, b, sv_counts)."""
    return {"classes": np.asarray(packed.classes),
            "pairs": np.asarray(packed.pairs),
            "n_tasks": packed.n_tasks, "n_features": packed.n_features,
            "banks": [(np.asarray(g.task_ids), np.asarray(g.sv_x),
                       np.asarray(g.sv_coef), np.asarray(g.b),
                       np.asarray(g.sv_counts)) for g in packed.buckets]}


def predictor(packed, *, control=False, ladder=()):
    """The default ``Predictor`` over the packed model, warmed on the
    batch ladder ``ladder`` (decide and decode programs)."""
    from repro import serve
    engine = "auto"
    if control:
        from repro.core.kernel_engine import EngineConfig
        engine = EngineConfig(backend="auto", gram_dtype="bf16")
    return serve.Predictor(packed, engine=engine).warmup(ladder)


def service(pred, window_ms: float):
    from repro import serve
    return serve.ServingService(pred, window_ms=window_ms)
