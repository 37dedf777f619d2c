"""Back-to-back certified fits over datasets drawn from the seed.

Set-up draws ``datasets`` datasets of the configuration from the seed
and fits the first once, which compiles (or loads from the compile
cache) every program the window runs: every dataset has the same
shapes. The window then fits them in turn, back to back, through
``SVC.fit`` until ``seconds`` have passed; a fit that starts in the
window is finished and counted. After the window every fit is checked
against the float64 reference (``bench.reference``): each task's KKT
violation, from the model's own banks, at most the configuration's
``tol``; the gap between the model's bias and the bias its multipliers
imply; every support vector a training row of its task; the class
table and vote routing those the labels imply. The kernel is the
configuration's. A fit whose check fails counts as failed.

End-to-end: ``fit_s``, the wall time of the fits over their number.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference, spans, system
from bench.generators import Outcome, dataset, kernel, sub_seed


def certify(cfg: dict, model: dict, x, y) -> dict:
    """Worst KKT violation and bias gap over the tasks of one fit, the
    tasks whose bank holds a vector that is no training row of theirs,
    and the model's routing faults, all by the reference's own kernel
    and routing."""
    classes, pairs = reference.routing(y)
    r = reference.certify_model(kernel(cfg), cfg["svc"]["C"], x, y,
                                classes, pairs, model["banks"])
    r["routing_faults"] = reference.routing_faults(classes, pairs, model)
    return r


def run(ctx) -> Outcome:
    cfg, params = ctx.config, ctx.params
    sets = [dataset(cfg, sub_seed(ctx.seed, k))[:2]
            for k in range(int(params["datasets"]))]
    make = lambda: system.svc(cfg, params, mesh_devices=ctx.devices,
                              control=ctx.control)
    with spans.span("fit"):
        make().fit(*sets[0])

    fits = []
    ctx.window_open()
    start = time.perf_counter()
    while time.perf_counter() - start < ctx.seconds:
        k = len(fits) % len(sets)
        traced = ctx.tracing()
        t0 = time.perf_counter()
        with spans.span("fit"):
            clf = make().fit(*sets[k])
        wall = time.perf_counter() - t0
        fits.append({"dataset": k, "wall_s": wall, "n_iter": clf.n_iter_,
                     "converged": bool(clf.converged_), "traced": traced,
                     "model": system.banks(system.pack(clf))})
        del clf
        if traced and time.perf_counter() - start >= params.get(
                "trace_seconds", ctx.seconds):
            ctx.stop_tracing()
    ctx.window_close()

    tol = cfg["svc"]["tol"]
    worst = {"kkt": 0.0, "bias_gap": 0.0, "sv_faults": 0,
             "routing_faults": 0}
    failed = 0
    with spans.span("certify"):
        for fit in fits:
            r = certify(cfg, fit.pop("model"), *sets[fit["dataset"]])
            fit.update(r)
            failed += int(not fit["converged"] or not r["kkt"] <= tol
                          or r["sv_faults"] or r["routing_faults"])
            for k in worst:
                worst[k] = max(worst[k], r[k])
    first = {}
    for fit in fits:
        first.setdefault(fit["dataset"], fit["n_iter"])
    return Outcome(
        attempted=len(fits), failed=failed,
        metrics={"fit_s": sum(f["wall_s"] for f in fits) / len(fits)},
        checks=[("kkt", worst["kkt"], tol),
                ("bias_gap", worst["bias_gap"], ctx.limits["bias_gap"]),
                ("sv_faults", worst["sv_faults"], 0),
                ("routing_faults", worst["routing_faults"], 0),
                ("fits_failed", failed, 0)],
        layer={"kind": "train",
               "pair_updates": float(np.mean(list(first.values()))),
               "traced_updates": sum(f["n_iter"] for f in fits
                                     if f["traced"]),
               "traced_fits": sum(f["traced"] for f in fits)},
        notes={"per_dataset": {
            k: {"fits": sum(f["dataset"] == k for f in fits),
                "n_iter": n, "kkt": max(f["kkt"] for f in fits
                                         if f["dataset"] == k)}
            for k, n in first.items()}})
