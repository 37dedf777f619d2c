"""Open-loop arrivals against ``ServingService``, checked against the
shared-row reference: for one-vs-one models whose banks are wide.

Everything the window does is ``open_loop``'s, from its own pieces
(``schedule``, ``Pacer``, ``replay``): the same set-up (fit, pack, the
default ``Predictor`` warmed on the whole batch ladder, the service,
the pacer process), the same conditioned Poisson schedule and the same
end-to-end metrics. What differs:

* the data: ``bench.embeddings.banking77_like`` (the configuration's
  ``data`` names it), held-out rows as the request pool;
* the reference: ``bench.reference_union``, which computes one kernel
  row per distinct support vector, where ``open_loop``'s would compute
  one per bank entry (about 36 times as many for 77 classes);
* labels: a served label that differs from the reference's vote counts
  in ``labels_mismatched`` only where no move of the values within
  their tolerance can change that vote; other differences are noted as
  ``labels_ambiguous``;
* the layer dict also gives ``n_sv_union``, the number of distinct
  support-vector rows among all banks, for the readers that count the
  decide's work once per distinct row.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import embeddings, reference, reference_union, spans, system
from bench.generators import Outcome, kernel, sub_seed
from bench.generators.open_loop import RESULT_WAIT_S, Pacer, replay, schedule


def dataset(cfg: dict, seed: int):
    """(x_train, y_train, x_test, y_test) of the configuration."""
    shape = cfg["data"]
    if shape["generator"] != "banking77_like":
        raise ValueError(f"open_loop_union serves banking77_like data, not "
                         f"{shape['generator']!r}")
    return embeddings.banking77_like(
        seed, n_classes=shape["n_classes"], d=shape["n_features"],
        n_train=shape["n_train"], n_test=shape["n_test_per_class"],
        cone=shape["cone"], n_topics=shape["n_topics"],
        topic=shape["topic"], mix=shape["mix"], noise=shape["noise"])


def run(ctx) -> Outcome:
    cfg, params = ctx.config, ctx.params
    xtr, ytr, pool, y_pool = dataset(cfg, sub_seed(ctx.seed, 0))
    packed = system.pack(system.svc(cfg, params, control=ctx.control)
                         .fit(xtr, ytr))
    model = system.banks(packed)
    ladder = tuple(2 ** i for i in range(int(params["ladder_log2"]) + 1))
    pred = system.predictor(packed, control=ctx.control, ladder=ladder)
    del packed
    arrivals, rows, starts, ops = schedule(params, ctx.seconds, len(pool),
                                           ctx.seed)
    pool = np.ascontiguousarray(pool, np.float32)
    svc = system.service(pred, params["window_ms"])
    pacer = Pacer(arrivals)
    traced = {}

    def end_trace():
        traced.update(svc.stats)
        ctx.stop_tracing()

    marks = ([(params.get("trace_seconds", ctx.seconds), end_trace)]
             if ctx.trace else [])
    freeze = bool(params.get("gc_freeze", False))
    try:
        if freeze:
            gc.collect()
        ctx.window_open()
        if freeze:
            gc.freeze()
        try:
            futs, done, late = replay(svc.submit, pacer, arrivals, rows,
                                      starts, ops, pool, ctx.seconds, marks)
        finally:
            if freeze:
                gc.unfreeze()
        stats = svc.stats
        ctx.window_close()
        answers = []
        for f in futs:
            try:
                answers.append(f.result(timeout=RESULT_WAIT_S))
            except Exception:                       # noqa: BLE001
                answers.append(None)
    finally:
        svc.close()
        pacer.close()

    with spans.span("certify"):
        classes, pairs = reference.routing(ytr)
        fit = reference.certify_model(kernel(cfg), cfg["svc"]["C"], xtr,
                                      ytr, classes, pairs, model["banks"])
        routing_faults = reference.routing_faults(classes, pairs, model)
        union = reference_union.union_rows(model["banks"])
        got = check(kernel(cfg), classes, pairs, model, union, pool, rows,
                    starts, ops, answers, params["rtol"])
    missing = sum(a is None for a in answers)
    lat = done - arrivals
    lat[np.isnan(lat)] = np.inf
    in_window = np.isfinite(done) & (done <= ctx.seconds)
    ok = np.array([a is not None for a in answers])
    fifth = max(1, len(lat) // 5)
    n_sv = [int(c) for g in model["banks"] for c in g[4]]
    task_rows = sum(int(np.sum((ytr == classes[p]) | (ytr == classes[q])))
                    for p, q in pairs)
    return Outcome(
        attempted=len(arrivals), failed=missing,
        metrics={"serve_p99_ms": float(np.percentile(lat, 99)) * 1e3,
                 "serve_rows_per_s": float(rows[in_window & ok].sum())
                 / ctx.seconds},
        checks=[("value_err_over_tol", got["err"],
                 ctx.limits["value_err_over_tol"]),
                ("labels_mismatched", got["mismatched"], 0),
                ("requests_missing", missing, 0),
                ("routing_faults", routing_faults, 0),
                ("sv_faults", fit["sv_faults"], 0),
                ("bias_gap", fit["bias_gap"], ctx.limits["bias_gap"])],
        layer={"kind": "serve", "stats": stats,
               "traced_stats": traced or stats, "n_sv": n_sv,
               "d": model["n_features"], "n_sv_union": len(union[0])},
        notes={"p50_ms": float(np.percentile(lat, 50)) * 1e3,
               "p99_ms": float(np.percentile(lat, 99)) * 1e3,
               "lag_growth_ms": float(np.median(lat[-fifth:])
                                      - np.median(lat[:fifth])) * 1e3,
               "rows_per_batch": stats["rows_per_batch"],
               "submit_late_p99_ms": float(np.nanpercentile(late, 99)) * 1e3,
               "pacer_late_p99_ms": float(pacer.late[0]) * 1e3,
               "pacer_late_max_ms": float(pacer.late[1]) * 1e3,
               "pacer_stalls": float(pacer.late[3]),
               "fit_kkt": fit["kkt"],
               "labels_ambiguous": got["ambiguous"],
               "rows_uncertain": got["uncertain"],
               "test_accuracy": float(np.mean(classes[got["won"]]
                                              == y_pool)),
               "n_support": int(sum(n_sv)), "n_sv_union": len(union[0]),
               "sv_share": sum(n_sv) / task_rows,
               "bank_bytes": int(sum(g[1].nbytes for g in model["banks"])),
               "requests": len(arrivals), "rows": int(rows.sum()),
               "batches": stats["n_batches"]})


def check(kern, classes, pairs, model, union, pool, rows, starts, ops,
          answers, rtol) -> dict:
    """Over every answered request: the worst |served - reference| over
    its tolerance; served labels that differ from the reference's vote
    where that vote is certain (``mismatched``) and where it is not
    (``ambiguous``); the pool rows whose vote is not certain
    (``uncertain``); the reference's vote of every pool row (``won``)."""
    banks, n_tasks = model["banks"], len(pairs)
    tol = reference.tolerance(banks, n_tasks, rtol)
    want = reference_union.decision_values(kern, banks, pool, n_tasks,
                                           union=union)
    won, certain = reference_union.certain_labels(
        np.nan_to_num(want), tol, pairs, len(classes))
    labels = classes[won]
    err, mismatched, ambiguous = 0.0, 0, 0
    for i, ans in enumerate(answers):
        if ans is None:
            continue
        sl = slice(starts[i], starts[i] + rows[i])
        if ops[i] == "values":
            got = np.asarray(ans, np.float64).reshape(-1, rows[i])
            if got.shape != want[:, sl].shape:
                err = np.inf
                continue
            gap = np.abs(got - want[:, sl]) / tol
            err = max(err, float(np.where(np.isnan(gap), np.inf, gap).max()))
        else:
            wrong = np.asarray(ans) != labels[sl]
            mismatched += int(np.sum(wrong & certain[sl]))
            ambiguous += int(np.sum(wrong & ~certain[sl]))
    return {"err": err, "mismatched": mismatched, "ambiguous": ambiguous,
            "uncertain": int(np.sum(~certain)), "won": won}
