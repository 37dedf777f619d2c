"""Multiclass OvO on the Pavia-like hyperspectral dataset (paper Fig. 4 /
Table IV): 9 classes -> 36 independent binary SMO problems distributed
over mesh workers via shard_map (the MPI layer).

    PYTHONPATH=src python examples/multiclass_pavia.py [n_workers]

Uses forced host devices to emulate n_workers "MPI ranks" on CPU.
"""
import os
import sys

N_WORKERS = int(sys.argv[1]) if len(sys.argv) > 1 else 4
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_WORKERS} "
    + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, "src")

import time

import numpy as np

from repro.core import dist, kernels as K, multiclass as MC, ovo
from repro.core.svm import SVC
from repro.launch.mesh import make_local_mesh
from repro.data import (load_pavia_like, make_imbalanced_blobs, normalize,
                        train_test_split)


def main():
    x, y = load_pavia_like(n_per_class=120)
    x = normalize(x)
    xtr, ytr, xte, yte = train_test_split(x, y, test_frac=0.2, seed=0)

    mesh = make_local_mesh(N_WORKERS)
    c_tasks = ovo.n_binary_tasks(9)
    print(f"9 classes -> {c_tasks} binary tasks over {N_WORKERS} workers "
          f"(N = C/P = {-(-c_tasks // N_WORKERS)} tasks/worker)")

    t0 = time.perf_counter()
    clf = SVC(solver="smo", mesh=mesh, worker_axes=("workers",)).fit(
        xtr, ytr)
    dt = time.perf_counter() - t0
    print(f"distributed OvO-SMO: fit {dt:.2f}s | "
          f"train acc {clf.score(xtr, ytr):.3f} | "
          f"test acc {clf.score(xte, yte):.3f} | "
          f"converged={clf.converged_}")

    # the paper's baseline: sequential GD ("Multi-Tensorflow")
    t0 = time.perf_counter()
    clf_gd = SVC(solver="gd", gd_steps=800).fit(xtr, ytr)
    dt_gd = time.perf_counter() - t0
    print(f"sequential GD (Multi-TF baseline): fit {dt_gd:.2f}s | "
          f"test acc {clf_gd.score(xte, yte):.3f}")
    print(f"speedup: {dt_gd / dt:.1f}x  <- paper Table IV axis "
          f"(NOTE: on this host all {N_WORKERS} emulated workers share "
          f"ONE cpu core and times include jit compile; "
          f"benchmarks/bench_multiclass.py measures the solvers "
          f"post-warmup)")


def imbalanced_demo():
    """The strategy layer on an IMBALANCED problem: the size-bucketed
    scheduler solves each shape bucket at its own width instead of
    padding every task to the widest class pair."""
    x, y = make_imbalanced_blobs((300, 200, 100, 50, 25), 24, sep=3.0)
    x = normalize(x)
    ts = MC.get_strategy("ovo").build_taskset(x, y)
    for name, cfg in (("padded  ", MC.ScheduleConfig(bucket_by="none")),
                      ("bucketed", MC.ScheduleConfig())):
        sched = MC.build_schedule(ts.sizes, cfg)
        stats = MC.schedule_stats(ts.sizes, sched)
        print(f"{name}: buckets={stats['bucket_widths']} "
              f"padded-FLOP fraction={stats['padded_flop_fraction']:.2f}")
    for strategy in ("ovo", "ovr"):
        clf = SVC(solver="smo", strategy=strategy).fit(x, y)
        print(f"strategy={strategy}: train acc {clf.score(x, y):.3f} "
              f"({clf._taskset.n_tasks} tasks)")


if __name__ == "__main__":
    main()
    imbalanced_demo()
