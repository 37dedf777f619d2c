"""Paper Table IV: multiclass (9-way, one-vs-one) training time.

  MPI-CUDA          -> vmapped/sharded parallel SMO over all 36 tasks
  Multi-Tensorflow  -> sequential GD, one "session" per task

Also reports the distributed (shard_map) variant — the actual MPI
analogue — and its scaling vs worker count, in this process over the
first w visible devices, plus ``bucketed()``: padded vs size-bucketed
scheduler wall time and padded-FLOP fraction on an imbalanced dataset
(JSON lines via ``common.emit_json``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, emit_json, timeit
from repro.core import dist, kernels as K, multiclass as MC, ovo
from repro.data import load_pavia_like, make_imbalanced_blobs, normalize
from repro.data.pipeline import subsample_per_class
from repro.launch.mesh import make_local_mesh

GD_STEPS = 2000


def main():
    print("# Table IV: Pavia-like 9-class OvO, N samples/class")
    x_all, y_all = load_pavia_like(n_per_class=800)
    x_all = normalize(x_all)

    for n in (200, 400, 600, 800):
        xs, ys = subsample_per_class(x_all, y_all, n, seed=0)
        kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(xs))
        tasks = ovo.build_tasks(xs, ys)

        t_par = timeit(
            lambda: dist.vmapped_ovo_fit(tasks, solver="smo",
                                         kernel=kp).alpha,
            warmup=1, iters=1)
        t_seq = timeit(
            lambda: dist.sequential_ovo_fit(
                tasks, solver="gd",
                gd_cfg=__import__("repro.core.gd",
                                  fromlist=["GDConfig"]).GDConfig(
                    lr=0.01, steps=GD_STEPS),
                kernel=kp).alpha,
            warmup=0, iters=1)
        emit(f"pavia_multi_{n}_parallel_smo", t_par,
             f"speedup={t_seq / t_par:.1f}x")
        emit(f"pavia_multi_{n}_sequential_gd", t_seq,
             f"tasks={ovo.n_binary_tasks(9)}")


def scaling(workers=(1, 2, 4)):
    """Worker-scaling of the shard_map MPI layer: 36 tasks over a mesh
    of the first w visible devices, all in this process (a child
    process could not take a chip this one holds). Worker counts above
    the visible device count are skipped; on a CPU host force devices
    with XLA_FLAGS=--xla_force_host_platform_device_count=N before JAX
    starts. Forced host 'devices' share the same CPU, so there wall time
    does NOT drop — the check is that the distribution overhead stays
    ~0 (the paper's 'communication only at the ends')."""
    print("# MPI-layer scaling (36 tasks over P workers, shard_map)")
    x, y = load_pavia_like(n_per_class=100)
    x = normalize(x)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    taskset = MC.get_strategy("ovo").build_taskset(x, y)
    base = None
    for w in workers:
        if w > len(jax.devices()):
            print(f"# skip {w} workers: {len(jax.devices())} devices")
            continue
        mesh = make_local_mesh(w)
        sched = MC.build_schedule(taskset.sizes,
                                  MC.ScheduleConfig(n_workers=w))
        t = timeit(lambda: dist.fit_taskset(taskset, sched, mesh=mesh,
                                            kernel=kp).alpha,
                   warmup=1, iters=1)
        base = base or t
        emit(f"dist_ovo_workers_{w}", t, f"rel={t / base:.2f}")


def bucketed(quick: bool = False):
    """Padded vs size-bucketed scheduler on an IMBALANCED multiclass
    problem — the tentpole number of the strategy layer. Emits one JSON
    line per configuration: wall seconds + padded-FLOP fraction."""
    print("# bucketed vs padded scheduler, imbalanced 6-class OvO")
    class_sizes = (150, 120, 60, 30, 20, 12) if quick else \
                  (600, 400, 200, 100, 50, 25)
    x, y = make_imbalanced_blobs(class_sizes, 32, sep=3.0, seed=11)
    x = normalize(x)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    taskset = MC.get_strategy("ovo").build_taskset(x, y)

    for name, cfg in (("padded", MC.ScheduleConfig(bucket_by="none")),
                      ("bucketed", MC.ScheduleConfig(bucket_by="pow2"))):
        sched = MC.build_schedule(taskset.sizes, cfg)
        stats = MC.schedule_stats(taskset.sizes, sched)
        secs = timeit(
            lambda: dist.fit_taskset(taskset, sched, solver="smo",
                                     kernel=kp).alpha,
            warmup=1)  # 3-iteration median — single-shot timing is noisy
                       # enough to invert the padded/bucketed comparison
        emit_json({
            "bench": "multiclass_scheduler",
            "schedule": name,
            "class_sizes": list(class_sizes),
            "n_tasks": stats["n_tasks"],
            "n_buckets": stats["n_buckets"],
            "bucket_widths": stats["bucket_widths"],
            "padded_flop_fraction": round(stats["padded_flop_fraction"],
                                          4),
            "wall_seconds": round(secs, 4),
        })


if __name__ == "__main__":
    main()
    scaling()
    bucketed()
