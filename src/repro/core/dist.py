"""The "MPI layer": distributing independent OvO tasks over the mesh.

Paper Fig. 4 (``MPI-CUDA_multiSMO``): C = m(m-1)/2 binary problems are
statically partitioned over P workers, N = C/P problems each; every worker
runs the same binary-SMO program on its slice (SPMD); communication is
only the initial broadcast of data and the final gather of alphas.

JAX-native mapping:

  MPI rank            ->  a slice of the mesh worker axis / axes
  static partition    ->  task-axis sharding of (x, y, mask) via shard_map
  SPMD binary SMO     ->  vmap(binary_smo) inside the shard_map body
  MPI_Bcast / Gather  ->  in/out shardings (device_put in, addressable
                          gather out); NO collectives inside the solver
                          loop, exactly the paper's comm profile.

``fit_taskset`` is the general entry point: it consumes a strategy-built
``repro.core.multiclass.TaskSet`` plus a size-bucketed ``Schedule`` and
runs ONE vmapped / shard_mapped solver program PER BUCKET, each at its
own padded width — on imbalanced datasets this replaces the old
pad-everything-to-the-widest-pair layout whose FLOPs were mostly zeros.
Worker placement inside each bucket follows the schedule's greedy LPT
grid rather than blind ``C/P`` striping.

``shard`` adds the second parallelism axis from the paper — data-parallel
WITHIN one QP: ``shard="data"`` runs every task through
``smo.sharded_binary_smo`` (samples sharded over the mesh, collective
working-set selection), and ``shard="auto"`` picks per bucket — wide
buckets with fewer tasks than workers go data-parallel, the rest stay
task-parallel. The hybrid is what lets a 3-class problem with one huge
pair use all 8 devices instead of 3.

``vmapped_ovo_fit`` / ``distributed_ovo_fit`` survive as shims over
``fit_taskset``: they convert the legacy padded ``OvOTasks`` stack into
a TaskSet and run it under a single-bucket ``bucket_by="none"`` schedule
at the original padded width, preserving the old numerics exactly.

``sequential_ovo_fit`` is the "Multi-Tensorflow" side: one GD session per
task, executed one after another (the paper runs multiple TF sessions
sequentially).

Every fit entry point threads an optional ``engine`` (an ``EngineConfig``
or backend name from ``repro.core.kernel_engine``) down to the binary
solvers, so the per-task Gram strategy — dense, chunked + LRU row cache,
or Pallas-tiled — is chosen once at the top.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gd as gd_mod
from repro.core import kernel_engine as KE
from repro.core import kernels as K
from repro.core import multiclass as MC
from repro.core import smo as smo_mod
from repro.core.ovo import OvOTasks

# fit_taskset(shard="auto") sends a bucket data-parallel only when its
# tasks are wide enough to amortize the per-iteration collectives AND too
# few to keep every worker busy under task parallelism
DATA_PARALLEL_MIN_WIDTH = 2048


def _batched_engine(engine):
    """Strip the LRU row cache for vmapped/sharded dispatch: a batched
    ``lax.cond`` executes both branches, so a cache lookup recomputes the
    kernel row regardless of hit while still paying the (slots, n)
    buffer scatter per task — strictly worse than no cache."""
    if engine is None:
        return None
    if isinstance(engine, str):
        engine = KE.EngineConfig(backend=engine)
    if isinstance(engine, KE.EngineConfig) and engine.cache_slots:
        return dataclasses.replace(engine, cache_slots=0)
    return engine


class OvOFit(NamedTuple):
    alpha: jax.Array      # (C, n_task)
    b: jax.Array          # (C,)
    n_iter: jax.Array     # (C,)
    converged: jax.Array  # (C,) bool (always True for GD: fixed steps)


def resolve_worker_count(mesh: Optional[Mesh],
                         worker_axes: tuple[str, ...]) -> int:
    """Worker count of a task-parallel layout: the product of the mesh
    extents over ``worker_axes`` (1 without a mesh). Validates the axis
    names up front — ``mesh.shape[axis]`` raises a bare ``KeyError``
    otherwise, which used to surface from ``shard="auto"`` as an opaque
    crash. Shared by ``fit_taskset`` and the ``SVC``/``SVR`` routing so
    the entry points cannot drift."""
    if mesh is None:
        return 1
    missing = tuple(a for a in worker_axes if a not in mesh.shape)
    if missing:
        raise ValueError(
            f"worker axes {missing} are not axes of the mesh "
            f"(mesh axes: {tuple(mesh.shape)}); pass worker_axes "
            f"matching the mesh (make_shard_mesh's default axis is "
            f"'shards')")
    return int(np.prod([mesh.shape[a] for a in worker_axes]))


def _fit_many_smo(x, y, mask, a0=None, *, cfg: smo_mod.SMOConfig,
                  kernel: K.KernelParams,
                  engine: Optional[KE.EngineConfig | str] = None) -> OvOFit:
    """vmap of the binary solver over a stacked task axis; ``a0`` is an
    optional stacked per-task warm start (cascade outer rounds)."""
    engine = _batched_engine(engine)
    if cfg.shrink_every:
        # adaptive shrinking targets the scalar-jit path: under vmap the
        # un-shrink lax.cond lowers to select and would run its chunked
        # matvec at EVERY convergence check of EVERY task (see the
        # kernel_engine module docs) — force it off for batched dispatch
        cfg = dataclasses.replace(cfg, shrink_every=0)

    def one(xt, yt, mt, a0t=None):
        r = smo_mod.binary_smo(xt, yt, mt, cfg=cfg, kernel=kernel,
                               engine=engine, alpha0=a0t)
        return OvOFit(r.alpha, r.b, r.n_iter, r.converged)
    if a0 is None:
        return jax.vmap(one)(x, y, mask)
    return jax.vmap(one)(x, y, mask, a0)


def _fit_many_svr(x, y, mask, a0=None, *, epsilon: float,
                  cfg: smo_mod.SMOConfig, kernel: K.KernelParams,
                  engine: Optional[KE.EngineConfig | str] = None) -> OvOFit:
    """vmap of the doubled epsilon-SVR solver over a stacked task axis.
    ``y`` holds real-valued targets; ``OvOFit.alpha`` carries the
    per-sample regression coefficients beta = alpha - alpha* (the raw
    doubled multipliers stay internal). ``a0`` is a stacked per-task
    BETA warm start, split into its canonical doubled decomposition."""
    engine = _batched_engine(engine)
    if cfg.shrink_every:
        cfg = dataclasses.replace(cfg, shrink_every=0)

    def one(xt, yt, mt, b0=None):
        a02 = None
        if b0 is not None:
            # traced under the bucketed _fit_many jit: b0 has scheduler
            # bucket width, not request width
            a02 = jnp.concatenate([jnp.maximum(b0, 0.0),  # repro: noqa[R001] -- traced inside the bucketed _fit_many jit; shapes are bucket widths
                                   jnp.maximum(-b0, 0.0)])  # repro: noqa[R001] -- traced inside the bucketed _fit_many jit; shapes are bucket widths
        r = smo_mod.svr_smo(xt, yt, mt, epsilon=epsilon, cfg=cfg,
                            kernel=kernel, engine=engine, alpha0=a02)
        return OvOFit(r.beta, r.b, r.n_iter, r.converged)
    if a0 is None:
        return jax.vmap(one)(x, y, mask)
    return jax.vmap(one)(x, y, mask, a0)


def _fit_many_gd(x, y, mask, *, cfg: gd_mod.GDConfig,
                 kernel: K.KernelParams,
                 engine: Optional[KE.EngineConfig | str] = None) -> OvOFit:
    def one(xt, yt, mt):
        r = gd_mod.binary_gd(xt, yt, mt, cfg=cfg, kernel=kernel,
                             engine=engine)
        return OvOFit(r.alpha, r.b, r.n_iter,
                      jnp.asarray(True))
    return jax.vmap(one)(x, y, mask)


@partial(jax.jit, static_argnames=("solver", "smo_cfg", "gd_cfg",
                                   "kernel", "engine", "svr_epsilon"))
def _fit_many(x, y, mask, a0=None, *, solver, smo_cfg, gd_cfg, kernel,
              engine, svr_epsilon=None):
    """Jitted stacked fit with all configs static: one compiled program
    per (config, bucket SHAPE) pair, shared across fit_taskset calls —
    a fresh ``jax.jit(partial(...))`` per call would retrace every
    bucket on every fit. ``svr_epsilon`` switches the tasks to the
    doubled epsilon-SVR spec (``y`` = targets, alpha out = beta)."""
    if svr_epsilon is not None:
        return _fit_many_svr(x, y, mask, a0, epsilon=svr_epsilon,
                             cfg=smo_cfg, kernel=kernel, engine=engine)
    if solver == "smo":
        return _fit_many_smo(x, y, mask, a0, cfg=smo_cfg, kernel=kernel,
                             engine=engine)
    return _fit_many_gd(x, y, mask, cfg=gd_cfg, kernel=kernel,
                        engine=engine)


@lru_cache(maxsize=64)
def _sharded_fit_many(mesh, worker_axes, solver, smo_cfg, gd_cfg, kernel,
                      engine, svr_epsilon=None, warm=False):
    """shard_map-wrapped jitted fit, cached per (mesh, config): jit keys
    its trace cache on the callable object, so rebuilding the wrapper
    inside the bucket loop would recompile every bucket on every call.
    ``warm`` switches to the 4-input (x, y, mask, alpha0) wrapper — the
    in_specs tuple must match the argument count."""
    fit_local = partial(_fit_many, solver=solver, smo_cfg=smo_cfg,
                        gd_cfg=gd_cfg, kernel=kernel, engine=engine,
                        svr_epsilon=svr_epsilon)
    spec = P(worker_axes)
    n_in = 4 if warm else 3
    return jax.jit(jax.shard_map(fit_local, mesh=mesh,
                                 in_specs=(spec,) * n_in,
                                 out_specs=OvOFit(spec, spec, spec, spec),
                                 check_vma=False))


class TaskSetFit(NamedTuple):
    """Host-side results for a fitted TaskSet. Row ``t`` of ``alpha`` is
    valid up to ``sizes[t]`` (tasks were solved at their bucket width;
    storage pads to the widest task — cheap, it's only (C, max_k))."""

    alpha: np.ndarray      # (C, max_k) float32
    b: np.ndarray          # (C,) float32
    n_iter: np.ndarray     # (C,) int
    converged: np.ndarray  # (C,) bool
    sizes: np.ndarray      # (C,) int true task lengths


def _bucket_arrays(taskset: MC.TaskSet, bucket: MC.Bucket,
                   alpha0: Optional[np.ndarray] = None):
    """Stack one bucket's tasks into (P * slots, width, d) solver inputs,
    rows ordered so a worker-axis shard gives worker p exactly the tasks
    the LPT layout assigned it. Dummy slots (-1) are fully masked.
    ``alpha0`` is a (C, max_k) per-task warm-start matrix (TaskSetFit
    layout); the stacked (slots, width) warm starts come back as the
    fourth element (None when no warm start was given)."""
    ids = bucket.task_ids.reshape(-1)
    d = taskset.tasks[0].x.shape[1]
    xt = np.zeros((len(ids), bucket.width, d), np.float32)
    yt = np.zeros((len(ids), bucket.width), np.float32)
    mk = np.zeros((len(ids), bucket.width), bool)
    a0 = (None if alpha0 is None
          else np.zeros((len(ids), bucket.width), np.float32))
    for s, t in enumerate(ids):
        if t < 0:
            continue
        task = taskset.tasks[t]
        k = task.size
        xt[s, :k] = task.x
        yt[s, :k] = task.y
        mk[s, :k] = True
        if a0 is not None:
            a0[s, :k] = alpha0[t, :k]
    return xt, yt, mk, a0


def _data_parallel_bucket(taskset: MC.TaskSet, bucket: MC.Bucket, *,
                          mesh: Mesh, axis: str,
                          smo_cfg: smo_mod.SMOConfig,
                          kernel: K.KernelParams, engine):
    """Solve one bucket's tasks SEQUENTIALLY, each task sample-sharded
    over the whole mesh axis (``smo.sharded_binary_smo``). Every task is
    padded to the bucket width, so the bucket shares one compiled
    program. Returns results in ``_bucket_arrays`` slot order (dummy
    slots collapse: the grid is flattened to real task ids only)."""
    ids = [int(t) for t in bucket.task_ids.reshape(-1) if t >= 0]
    outs = {}
    for t in ids:
        task = taskset.tasks[t]
        k = task.size
        xt = np.zeros((bucket.width, task.x.shape[1]), np.float32)
        yt = np.zeros((bucket.width,), np.float32)
        mk = np.zeros((bucket.width,), bool)
        xt[:k], yt[:k], mk[:k] = task.x, task.y, True
        r = smo_mod.sharded_binary_smo(
            jnp.asarray(xt), jnp.asarray(yt), jnp.asarray(mk),
            mesh=mesh, axis=axis, cfg=smo_cfg, kernel=kernel,
            engine=engine)
        outs[t] = r
    return outs


def validate_data_shard(mesh, worker_axes, solver: str) -> None:
    """Hard requirements of the sample-sharded (``shard="data"``) path —
    shared by ``fit_taskset`` and ``SVC`` so the two entry points cannot
    drift. An explicit data request that can't be honored must raise,
    never silently degrade to a single-device task-parallel fit."""
    if mesh is None:
        raise ValueError("shard='data' needs a mesh to shard the sample "
                         "axis over (e.g. launch.mesh.make_shard_mesh)")
    if solver != "smo":
        raise ValueError("shard='data' requires solver='smo' (the GD "
                         "baseline has no sharded path)")
    if len(worker_axes) != 1:
        raise ValueError("shard='data' shards the sample axis over "
                         "exactly one mesh axis; got "
                         f"worker_axes={worker_axes}")
    if worker_axes[0] not in mesh.shape:
        raise ValueError(
            f"worker axis {worker_axes[0]!r} is not an axis of the mesh "
            f"(axes: {tuple(mesh.shape)}); pass worker_axes matching the "
            f"mesh (make_shard_mesh's default axis is 'shards')")


def _wants_data_parallel(shard: str, bucket: MC.Bucket, n_real: int,
                         n_workers: int, solver: str, mesh,
                         worker_axes, data_min_width: int) -> bool:
    """Per-bucket parallelism mode. Explicit ``shard="data"`` validates
    hard; ``"auto"`` goes data-parallel only where it wins — wide tasks
    (collectives amortized over O(width) row work) that are too few to
    fill the worker grid — and silently stays task-parallel elsewhere."""
    if shard == "data":
        validate_data_shard(mesh, worker_axes, solver)
        return True
    if shard == "task" or mesh is None or n_workers <= 1:
        return False
    # auto: hybrid per bucket
    return (solver == "smo" and len(worker_axes) == 1
            and bucket.width >= data_min_width and n_real < n_workers)


def fit_taskset(taskset: MC.TaskSet,
                schedule: Optional[MC.Schedule] = None,
                *,
                mesh: Optional[Mesh] = None,
                worker_axes: tuple[str, ...] = ("workers",),
                solver: str = "smo",
                smo_cfg: smo_mod.SMOConfig = smo_mod.SMOConfig(),
                gd_cfg: gd_mod.GDConfig = gd_mod.GDConfig(),
                kernel: K.KernelParams = K.KernelParams(),
                engine: Optional[KE.EngineConfig | str] = None,
                schedule_cfg: Optional[MC.ScheduleConfig] = None,
                shard: str = "task",
                data_min_width: int = DATA_PARALLEL_MIN_WIDTH,
                alpha0: Optional[np.ndarray] = None,
                svr_epsilon: Optional[float] = None
                ) -> TaskSetFit:
    """Fit every binary task of ``taskset``, one solver program per
    schedule bucket.

    Without ``mesh`` each bucket is vmapped on the local device; with a
    mesh the bucket's slot axis is sharded over ``worker_axes`` via
    shard_map (each worker receives the contiguous run of slots the LPT
    layout placed on it). ``schedule`` defaults to a fresh pow2-bucketed
    build; pass ``schedule_cfg`` to tune bucketing without prebuilding.

    ``shard`` picks the parallelism AXIS per bucket:

    * ``"task"`` (default) — independent tasks across workers, the
      paper's MPI_multiSMO layout.
    * ``"data"`` — every task solved one after another, its SAMPLE axis
      sharded over the whole mesh (``smo.sharded_binary_smo``); for few
      huge tasks that task parallelism can't balance (requires
      ``solver="smo"`` and a single worker axis).
    * ``"auto"`` — hybrid: a bucket goes data-parallel when its width is
      >= ``data_min_width`` AND it has fewer real tasks than workers
      (i.e. task parallelism would leave devices idle); small/plentiful
      buckets stay vmapped task-parallel.

    ``alpha0`` is an optional (C, max_k) per-task warm-start matrix in
    the ``TaskSetFit.alpha`` layout (the cascade feeds a previous
    round's solution back in); ``svr_epsilon`` switches every task to
    the doubled epsilon-SVR spec (task ``y`` = real targets, returned
    ``alpha`` = per-sample beta). Both are task-parallel SMO features:
    they require ``solver="smo"`` and never route data-parallel.
    """
    n_workers = resolve_worker_count(mesh, tuple(worker_axes))
    if (alpha0 is not None or svr_epsilon is not None):
        if solver != "smo":
            raise ValueError(
                "alpha0 warm starts / svr_epsilon tasks require "
                f"solver='smo' (got solver={solver!r})")
        if shard == "data":
            raise ValueError(
                "alpha0/svr_epsilon run on the task-parallel vmapped "
                "path only; shard='data' (sharded_binary_smo) has no "
                "warm-start or SVR-taskset support — use shard='task' "
                "or 'auto'")
    if schedule is None:
        cfg = schedule_cfg if schedule_cfg is not None else MC.ScheduleConfig()
        cfg = dataclasses.replace(cfg, n_workers=n_workers)
        schedule = MC.build_schedule(taskset.sizes, cfg)
    if schedule.n_workers != n_workers:
        raise ValueError(
            f"schedule laid out for {schedule.n_workers} workers but the "
            f"mesh provides {n_workers}")

    if solver not in ("smo", "gd"):
        raise ValueError(f"unknown solver {solver!r}")
    if shard not in ("task", "data", "auto"):
        raise ValueError(f"unknown shard mode {shard!r}; expected "
                         "'task', 'data' or 'auto'")
    if isinstance(engine, str):
        engine = KE.EngineConfig(backend=engine)
    cfgs = dict(solver=solver, smo_cfg=smo_cfg, gd_cfg=gd_cfg,
                kernel=kernel, engine=engine, svr_epsilon=svr_epsilon)

    sizes = taskset.sizes
    c = taskset.n_tasks
    alpha = np.zeros((c, int(sizes.max())), np.float32)
    b = np.zeros(c, np.float32)
    n_iter = np.zeros(c, np.int64)
    converged = np.zeros(c, bool)

    warmless = alpha0 is None and svr_epsilon is None
    for bucket in schedule.buckets:
        real_ids = bucket.task_ids.reshape(-1)
        real_ids = real_ids[real_ids >= 0]
        if warmless and _wants_data_parallel(
                shard, bucket, len(real_ids), n_workers, solver, mesh,
                worker_axes, data_min_width):
            outs = _data_parallel_bucket(
                taskset, bucket, mesh=mesh, axis=worker_axes[0],
                smo_cfg=smo_cfg, kernel=kernel, engine=engine)
            for t, r in outs.items():
                k = int(sizes[t])
                alpha[t, :k] = np.asarray(r.alpha)[:k]
                b[t] = float(r.b)
                n_iter[t] = int(r.n_iter)
                converged[t] = bool(r.converged)
            continue
        xt, yt, mk, a0 = _bucket_arrays(taskset, bucket, alpha0)
        if mesh is None:
            out = _fit_many(jnp.asarray(xt), jnp.asarray(yt),
                            jnp.asarray(mk),
                            None if a0 is None else jnp.asarray(a0),
                            **cfgs)
        else:
            fit = _sharded_fit_many(mesh, tuple(worker_axes),
                                    warm=a0 is not None, **cfgs)
            sh = NamedSharding(mesh, P(worker_axes))
            args = [jax.device_put(jnp.asarray(xt), sh),
                    jax.device_put(jnp.asarray(yt), sh),
                    jax.device_put(jnp.asarray(mk), sh)]
            if a0 is not None:
                args.append(jax.device_put(jnp.asarray(a0), sh))
            out = fit(*args)
        out = jax.tree.map(np.asarray, out)
        for s, t in enumerate(bucket.task_ids.reshape(-1)):
            if t < 0:
                continue
            k = int(sizes[t])
            alpha[t, :k] = out.alpha[s, :k]
            b[t] = out.b[s]
            n_iter[t] = out.n_iter[s]
            converged[t] = out.converged[s]
    return TaskSetFit(alpha=alpha, b=b, n_iter=n_iter, converged=converged,
                      sizes=sizes)


def taskset_from_ovo(tasks: OvOTasks) -> MC.TaskSet:
    """Legacy padded ``OvOTasks`` stack -> variable-length TaskSet.

    Fully-masked padding tasks (the ``pad_tasks_to`` dummies) are
    dropped — the scheduler re-creates worker-count padding as dummy
    slots on its own."""
    cls_index = {c: i for i, c in enumerate(tasks.classes)}
    out = []
    seen_empty = False
    for t in range(tasks.x.shape[0]):
        k = int(tasks.mask[t].sum())
        if k == 0:
            seen_empty = True
            continue
        if seen_empty:
            # the shims re-expand results positionally (alpha[:c_real]),
            # which is only correct when dropped dummies are TRAILING
            raise ValueError(
                f"fully-masked OvOTasks entry precedes real task {t}; "
                f"padding tasks must be trailing (ovo.build_tasks "
                f"pad_tasks_to appends them)")
        if not tasks.mask[t, :k].all():
            raise ValueError(f"OvOTasks mask for task {t} is not a "
                             f"prefix; cannot convert to a TaskSet")
        a, b = tasks.pairs[t]
        out.append(MC.BinaryTask(
            x=np.asarray(tasks.x[t, :k], np.float32),
            y=np.asarray(tasks.y[t, :k], np.float32),
            pos=cls_index[a], neg=cls_index[b]))
    return MC.TaskSet(tasks=tuple(out), classes=tasks.classes,
                      strategy="ovo")


def _ovo_fit_shim(tasks: OvOTasks, mesh, worker_axes, *, solver, smo_cfg,
                  gd_cfg, kernel, engine) -> OvOFit:
    """Run a legacy OvOTasks stack through fit_taskset at the original
    padded width (single bucket), re-expanding results to the old
    (c_total, n_task) layout."""
    c_total, n_task = tasks.y.shape
    taskset = taskset_from_ovo(tasks)
    fit = fit_taskset(
        taskset, mesh=mesh, worker_axes=worker_axes, solver=solver,
        smo_cfg=smo_cfg, gd_cfg=gd_cfg, kernel=kernel, engine=engine,
        schedule_cfg=MC.ScheduleConfig(bucket_by="none", pad_width=n_task))
    c_real = taskset.n_tasks
    alpha = np.zeros((c_total, n_task), np.float32)
    alpha[:c_real, :fit.alpha.shape[1]] = fit.alpha
    b = np.zeros(c_total, np.float32)
    b[:c_real] = fit.b
    n_iter = np.zeros(c_total, np.int32)
    n_iter[:c_real] = fit.n_iter
    converged = np.ones(c_total, bool)  # dummy tasks trivially converge
    converged[:c_real] = fit.converged
    return OvOFit(alpha=jnp.asarray(alpha), b=jnp.asarray(b),
                  n_iter=jnp.asarray(n_iter),
                  converged=jnp.asarray(converged))


def distributed_ovo_fit(tasks: OvOTasks,
                        mesh: Mesh,
                        worker_axes: tuple[str, ...] = ("workers",),
                        *,
                        solver: str = "smo",
                        smo_cfg: smo_mod.SMOConfig = smo_mod.SMOConfig(),
                        gd_cfg: gd_mod.GDConfig = gd_mod.GDConfig(),
                        kernel: K.KernelParams = K.KernelParams(),
                        engine: Optional[KE.EngineConfig | str] = None
                        ) -> OvOFit:
    """Legacy shim: fit a padded OvO stack, task axis sharded over
    ``worker_axes`` of ``mesh``, via ``fit_taskset``.

    The task axis length must be divisible by the total worker count
    (use ``build_tasks(pad_tasks_to=n_workers)``).
    """
    n_workers = resolve_worker_count(mesh, tuple(worker_axes))
    c_total = tasks.x.shape[0]
    if c_total % n_workers:
        raise ValueError(
            f"task count {c_total} not divisible by {n_workers} workers; "
            f"build tasks with pad_tasks_to={n_workers}")
    return _ovo_fit_shim(tasks, mesh, worker_axes, solver=solver,
                         smo_cfg=smo_cfg, gd_cfg=gd_cfg, kernel=kernel,
                         engine=engine)


def vmapped_ovo_fit(tasks: OvOTasks, *, solver: str = "smo",
                    smo_cfg: smo_mod.SMOConfig = smo_mod.SMOConfig(),
                    gd_cfg: gd_mod.GDConfig = gd_mod.GDConfig(),
                    kernel: K.KernelParams = K.KernelParams(),
                    engine: Optional[KE.EngineConfig | str] = None
                    ) -> OvOFit:
    """Legacy shim: single-device stacked fit (no mesh) — the CUDA-only
    configuration — via ``fit_taskset``."""
    return _ovo_fit_shim(tasks, None, ("workers",), solver=solver,
                         smo_cfg=smo_cfg, gd_cfg=gd_cfg, kernel=kernel,
                         engine=engine)


def sequential_ovo_fit(tasks: OvOTasks, *, solver: str = "gd",
                       smo_cfg: smo_mod.SMOConfig = smo_mod.SMOConfig(),
                       gd_cfg: gd_mod.GDConfig = gd_mod.GDConfig(),
                       kernel: K.KernelParams = K.KernelParams(),
                       engine: Optional[KE.EngineConfig | str] = None,
                       n_real_tasks: Optional[int] = None) -> OvOFit:
    """The paper's "Multi-Tensorflow": one session per task, sequentially.

    A Python loop of separately-dispatched solver calls — intentionally
    NOT vmapped/sharded, to reproduce the baseline's execution profile.
    The jitted solver is built ONCE outside the loop: every task has the
    same padded shape, so one trace serves all of them (the sequential
    dispatch profile is preserved; only redundant retraces went away).
    """
    c_total = tasks.x.shape[0] if n_real_tasks is None else n_real_tasks
    if solver == "gd":
        solve = jax.jit(partial(gd_mod.binary_gd, cfg=gd_cfg,  # repro: noqa[R001] -- paper-baseline reproduction: jit built once per call, outside the task loop
                                kernel=kernel, engine=engine))
    else:
        solve = jax.jit(partial(smo_mod.binary_smo, cfg=smo_cfg,  # repro: noqa[R001] -- paper-baseline reproduction: jit built once per call, outside the task loop
                                kernel=kernel, engine=engine))
    outs = []
    for t in range(c_total):
        xt = jnp.asarray(tasks.x[t])  # repro: noqa[R001] -- tasks pre-padded by build_tasks; every row has the same shape
        yt = jnp.asarray(tasks.y[t])  # repro: noqa[R001] -- tasks pre-padded by build_tasks; every row has the same shape
        mt = jnp.asarray(tasks.mask[t])  # repro: noqa[R001] -- tasks pre-padded by build_tasks; every row has the same shape
        r = solve(xt, yt, mt)
        if solver == "gd":
            outs.append(OvOFit(r.alpha, r.b, r.n_iter, jnp.asarray(True)))
        else:
            outs.append(OvOFit(r.alpha, r.b, r.n_iter, r.converged))
    stack = lambda *xs: jnp.stack(xs)
    return jax.tree.map(stack, *outs)
