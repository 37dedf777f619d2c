#!/usr/bin/env python3
"""Bring-up smoke: the SVM trainer and server end to end on a TPU.

    python chip_smoke.py              # one chip: three fits + serving
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths
                                      # against their one-chip fits

One chip (the default) runs, through the public entry points:

* ``multiclass`` — the paper's Table IV job: Pavia-like data at its
  published width (102 bands, 9 classes, 2000 rows per class), a seeded
  80/20 split, ``SVC(kernel="rbf").fit`` on the default engine: 36
  one-vs-one tasks of about 3,200 rows, each on its dense Gram. Every
  task must pass the KKT certificate (``smo.kkt_violation`` on a
  float64 recomputation of f from the returned alphas) at the solver
  tolerance.
* ``pallas_binary`` — one class pair at its full size (4,000 rows, no
  split) on the Pallas Gram (``EngineConfig(backend="pallas")``), same
  certificate.
* ``rff`` — ``SVC(engine="rff", rank=1024)`` at n = 65,536, d = 102:
  the fused ``rff_features`` kernel and the DCD solver, certified with
  the equality multiplier pinned at r = 0 against the approximate Gram;
  the epoch count is bounded (``RFF_MAX_EPOCHS``) and printed.
* ``serve`` — the multiclass model packed, ``Predictor(engine="pallas")``
  warmed on the padding ladder, then 144 requests of 1, 8 and 32 rows
  through ``ServingService`` under ``CompileGuard(budget=0)``. Every
  decision is held to the float64 reference (``serve.reference``) and
  every label to the labels decoded from it; the lowered decide program
  must contain the Pallas kernel (``tpu_custom_call``).

``--chips 4`` runs only the multi-chip paths and what each is compared
with: ``shard="data"`` on an n = 32,768 binary problem and the 36-task
OvO fit over a 4-worker mesh, each against the one-chip fit of the same
data (same support set, |db| <= 1e-2, identical predictions, both
certified), printing each device's peak memory.

Each phase prints one JSON line; its ``*_s`` fields are smoke timings on
the host clock, compilation included — not metrics. The last line is
``{"ok": true, "device": {...}}``: the platform and kind as JAX reports
them, and the number of chips the phases used (1, or 4 with
``--chips 4``). Without a TPU, without the repo's ``src/`` beside the
script, or when any phase fails, the script exits non-zero and prints
no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

RFF_MAX_EPOCHS = 1000
# the padding ladder the replay can reach: merged windows of up to
# max_batch rows plus one request decode at the next pow2 above it
SERVE_MAX_BATCH = 256
WARMUP_SIZES = tuple(2 ** i for i in range(10))          # 1 .. 512
REQUEST_ROWS = (1, 8, 32)
N_REQUESTS = 72                                          # per op


class PhaseFailed(RuntimeError):
    pass


def emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


def check(phase: str, ok: bool, record: dict) -> None:
    record = dict(record, phase=phase, passed=bool(ok))
    emit(record)
    if not ok:
        raise PhaseFailed(f"phase {phase} failed its check: {record}")


# ------------------------------------------------------------ certificates
def svc_violation(kernel, x, yy, alpha, C) -> float:
    """f = K (alpha y) - y in float64 from the returned alphas (only the
    alpha > 0 columns contribute), then the KKT violation."""
    from repro.core import smo
    from repro.serve import reference
    sv = alpha > 0
    f = (reference.gram64(kernel, x, x[sv])
         @ (np.asarray(alpha, np.float64)[sv] * yy[sv]) - yy)
    return float(smo.kkt_violation(alpha, yy, f, 0.0, C))


def signs(y, classes) -> np.ndarray:
    return np.where(y == classes[1], 1.0, -1.0)


def multiclass_violations(clf) -> np.ndarray:
    out = []
    for t, task in enumerate(clf._taskset.tasks):
        alpha = clf._fit.alpha[t, :task.size]
        out.append(svc_violation(clf.kernel_params, task.x,
                                 np.asarray(task.y, np.float64), alpha,
                                 clf.smo_cfg.C))
    return np.asarray(out)


def pavia(n_per_class: int, n_classes: int, seed: int):
    from repro.data import load_pavia_like, normalize
    x, y = load_pavia_like(n_per_class=n_per_class, n_classes=n_classes,
                           seed=seed)
    return normalize(x), y


# ------------------------------------------------------------ one chip
def phase_multiclass(seed: int):
    from repro.core.svm import SVC
    from repro.data import train_test_split
    x, y = pavia(2000, 9, seed)
    xtr, ytr, xte, yte = train_test_split(x, y, test_frac=0.2, seed=seed)
    t0 = time.perf_counter()
    clf = SVC(kernel="rbf").fit(xtr, ytr)
    fit_s = time.perf_counter() - t0
    viol = multiclass_violations(clf)
    acc = clf.score(xte, yte)
    check("multiclass", clf.converged_ and viol.max() <= clf.smo_cfg.tol,
          {"n_train": len(ytr), "d": x.shape[1], "n_tasks":
           clf._taskset.n_tasks, "task_rows_max": int(clf._taskset.sizes
                                                      .max()),
           "kkt_max": float(viol.max()), "tol": clf.smo_cfg.tol,
           "pair_updates_max": clf.n_iter_, "n_support_total":
           int(clf.n_support_.sum()), "heldout_accuracy": acc,
           "fit_s": fit_s})
    return x, y, clf, xte


def phase_pallas_binary(x, y):
    from repro.core.kernel_engine import EngineConfig
    from repro.core.svm import SVC
    pair = (y == 0) | (y == 1)
    xb, yb = x[pair], y[pair]
    t0 = time.perf_counter()
    clf = SVC(kernel="rbf", engine=EngineConfig(backend="pallas")).fit(
        xb, yb)
    fit_s = time.perf_counter() - t0
    viol = svc_violation(clf.kernel_params, xb, signs(yb, clf.classes_),
                         clf.alpha_, clf.smo_cfg.C)
    check("pallas_binary", clf.converged_ and viol <= clf.smo_cfg.tol,
          {"n": len(yb), "d": xb.shape[1], "kkt": viol,
           "tol": clf.smo_cfg.tol, "pair_updates": clf.n_iter_,
           "n_support": clf.n_support_, "fit_s": fit_s})


def phase_rff(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import smo
    from repro.core.svm import SVC
    x, y = pavia(32768, 2, seed + 1)
    t0 = time.perf_counter()
    clf = SVC(kernel="rbf", engine="rff", rank=1024,
              max_iter=RFF_MAX_EPOCHS).fit(x, y)
    fit_s = time.perf_counter() - t0
    fmap = clf._feature_map
    fused = "tpu_custom_call" in jax.jit(fmap.transform).lower(
        jnp.asarray(x[:256])).as_text()
    phi = np.asarray(fmap.transform(jnp.asarray(x)), np.float64)
    phib = np.concatenate(
        [phi, np.full((len(y), 1), clf.dcd_cfg.bias)], axis=1)
    yy = signs(y, clf.classes_)
    alpha = np.asarray(clf.alpha_, np.float64)
    f = phib @ (phib.T @ (alpha * yy)) - yy
    viol = float(smo.kkt_violation(alpha, yy, f, 0.0, clf.smo_cfg.C,
                                   r=0.0))
    check("rff", clf.converged_ and fused and viol <= clf.smo_cfg.tol,
          {"n": len(y), "d": x.shape[1], "rank": fmap.rank,
           "fused_kernel": fused, "kkt_r0": viol, "tol": clf.smo_cfg.tol,
           "epochs": clf.n_iter_, "max_epochs": RFF_MAX_EPOCHS,
           "fit_s": fit_s})


def phase_serve(clf, xte, seed: int):
    import jax.numpy as jnp
    from repro import serve
    from repro.analysis.compile_guard import CompileGuard
    from repro.serve import reference
    packed = serve.pack(clf)
    t0 = time.perf_counter()
    pred = serve.Predictor(packed, engine="pallas",
                           max_batch=SERVE_MAX_BATCH).warmup(WARMUP_SIZES)
    warm_s = time.perf_counter() - t0
    g = packed.buckets[0]
    lowered = pred._decide.lower(
        jnp.asarray(g.sv_x), jnp.asarray(g.sv_coef), jnp.asarray(g.b),
        jnp.zeros((SERVE_MAX_BATCH, packed.n_features), jnp.float32))
    kernel_in_program = "tpu_custom_call" in lowered.as_text()

    rng = np.random.default_rng(seed)
    batches = [xte[rng.choice(len(xte), REQUEST_ROWS[i % 3],
                              replace=False)] for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    with CompileGuard(budget=0, note="serving replay") as guard:
        with serve.ServingService(pred, window_ms=2.0) as svc:
            futs = [(svc.submit(z, op="values"), svc.submit(z, op="predict"))
                    for z in batches]
            got = [(fv.result(timeout=600), fp.result(timeout=600))
                   for fv, fp in futs]
        stats = svc.stats
    replay_s = time.perf_counter() - t0

    tol = reference.tolerance(packed)
    worst, labels_equal = 0.0, True
    for z, (df, labels) in zip(batches, got):
        want = reference.decision_values(packed, z)
        worst = max(worst, float((np.abs(df - want) / tol).max()))
        want_labels = pred.decode(want.astype(np.float32), "predict")
        labels_equal &= bool(np.array_equal(labels, want_labels))
    check("serve", worst <= 1.0 and labels_equal and kernel_in_program
          and guard.count == 0,
          {"n_requests": stats["n_requests"], "n_rows": stats["n_rows"],
           "n_batches": stats["n_batches"],
           "rows_per_batch": stats["rows_per_batch"],
           "n_tasks": packed.n_tasks, "n_banks": len(packed.buckets),
           "decision_err_over_tol_max": worst,
           "decision_rtol": reference.RTOL, "labels_equal": labels_equal,
           "kernel_in_program": kernel_in_program,
           "replay_compiles": guard.count, "warmup_s": warm_s,
           "replay_s": replay_s})


# ----------------------------------------------------------- four chips
def peak_bytes() -> list:
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def placement(arr) -> dict:
    return {"spec": str(arr.sharding.spec),
            "devices": sorted(d.id for d in arr.sharding.device_set)}


def compare_fits(one, many, x) -> dict:
    return {"db": abs(one.b_ - many.b_),
            "same_support": bool(np.array_equal(one.support_,
                                                many.support_)),
            "same_predictions": bool(np.array_equal(one.predict(x),
                                                    many.predict(x)))}


def phase_shard_data(seed: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import smo
    from repro.core.svm import SVC
    from repro.launch.mesh import make_shard_mesh
    x, y = pavia(16384, 2, seed + 2)
    # the four-chip fit first, so the peaks below are its own
    mesh = make_shard_mesh(4)
    t0 = time.perf_counter()
    many = SVC(kernel="rbf", mesh=mesh, worker_axes=("shards",),
               shard="data").fit(x, y)
    many_s = time.perf_counter() - t0
    yy = signs(y, many.classes_)
    # the same compiled program once more, on inputs placed sample-
    # sharded, for the placement of its input and output
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("shards")))
    r = smo.sharded_binary_smo(xs, jnp.asarray(yy, jnp.float32),
                               mesh=mesh, axis="shards", cfg=many.smo_cfg,
                               kernel=many.kernel_params)
    peaks = peak_bytes()
    t0 = time.perf_counter()
    one = SVC(kernel="rbf").fit(x, y)
    one_s = time.perf_counter() - t0
    viol = [svc_violation(m.kernel_params, x, yy, m.alpha_, m.smo_cfg.C)
            for m in (one, many)]
    cmp = compare_fits(one, many, x)
    check("shard_data", cmp["same_support"] and cmp["db"] <= 1e-2
          and cmp["same_predictions"] and max(viol) <= one.smo_cfg.tol
          and one.converged_ and many.converged_,
          dict(cmp, n=len(y), d=x.shape[1], kkt_one_chip=viol[0],
               kkt_four_chips=viol[1], tol=one.smo_cfg.tol,
               n_support=one.n_support_, x_placement=placement(xs),
               alpha_placement=placement(r.alpha),
               peak_bytes_per_device_after_four_chip_fit=peaks,
               one_chip_fit_s=one_s, four_chip_fit_s=many_s))


def phase_ovo_mesh(seed: int):
    from repro.core.svm import SVC
    from repro.data import train_test_split
    from repro.launch.mesh import make_local_mesh
    x, y = pavia(2000, 9, seed)
    xtr, ytr, xte, yte = train_test_split(x, y, test_frac=0.2, seed=seed)
    mesh = make_local_mesh(4)
    t0 = time.perf_counter()
    many = SVC(kernel="rbf", mesh=mesh, worker_axes=("workers",)).fit(
        xtr, ytr)
    many_s = time.perf_counter() - t0
    # process-wide peaks: this phase runs after shard_data
    peaks = peak_bytes()
    t0 = time.perf_counter()
    one = SVC(kernel="rbf").fit(xtr, ytr)
    one_s = time.perf_counter() - t0
    viol = [multiclass_violations(m).max() for m in (one, many)]
    thr = 1e-8 * one.smo_cfg.C
    same_support = all(
        np.array_equal(one._fit.alpha[t] > thr, many._fit.alpha[t] > thr)
        for t in range(one._taskset.n_tasks))
    db = float(np.abs(one._fit.b - many._fit.b).max())
    same_pred = bool(np.array_equal(one.predict(xte), many.predict(xte)))
    check("ovo_mesh", same_support and db <= 1e-2 and same_pred
          and max(viol) <= one.smo_cfg.tol and one.converged_
          and many.converged_,
          {"n_train": len(ytr), "n_tasks": one._taskset.n_tasks,
           "workers": int(mesh.shape["workers"]),
           "mesh_devices": sorted(d.id for d in mesh.devices.flat),
           "same_support": same_support, "db_max": db,
           "same_predictions": same_pred, "kkt_max_one_chip": viol[0],
           "kkt_max_four_chips": viol[1], "tol": one.smo_cfg.tol,
           "peak_bytes_per_device_after_four_chip_fit": peaks,
           "one_chip_fit_s": one_s,
           "four_chip_fit_s": many_s})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated dataset")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from "
              f"a checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable(ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports "
              f"{devices[0].platform!r}); refusing to run elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro.kernels import autotune
    autotune.set_cache_path(str(ROOT / ".autotune.json"))

    if args.chips == 4:
        phase_shard_data(args.seed)
        phase_ovo_mesh(args.seed)
    else:
        x, y, clf, xte = phase_multiclass(args.seed)
        phase_pallas_binary(x, y)
        phase_serve(clf, xte, args.seed)
        phase_rff(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
