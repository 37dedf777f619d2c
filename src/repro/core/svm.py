"""Public SVM API — sklearn-flavoured front end over the parallel solvers.

    clf = SVC(kernel="rbf", C=1.0, solver="smo")      # paper's CUDA path
    clf = SVC(kernel="rbf", C=1.0, solver="gd")       # paper's TF baseline
    clf = SVC(engine="chunked", shrink_every=4)       # n >> 8k training
    clf = SVC(engine="nystrom", rank=512)             # low-rank approx
    clf = SVC(engine="rff", rank=1024)                # random features
    clf = SVC(strategy="ovr")                         # one-vs-rest
    clf = SVC(decision="margin")                      # OvO summed margins
    clf = SVC(mesh=mesh, shard="data")                # samples sharded
    clf = SVC(mesh=mesh, shard="auto")                # hybrid per bucket
    clf = SVC(shard="cascade", cascade_shards=8)      # hierarchical cascade
    clf.fit(X, y)                                     # binary OR multiclass
    clf.predict(Xt); clf.score(Xt, yt)

    reg = SVR(kernel="rbf", C=1.0, epsilon=0.1)       # epsilon-SVR
    reg = SVR(solver="gd")                            # projected-GD dual
    reg = SVR(engine="chunked", shrink_every=4)       # large-n regression
    reg = SVR(engine="nystrom", rank=512)             # low-rank approx
    reg = SVR(mesh=mesh, shard="data")                # doubled axis sharded
    reg.fit(X, y).predict(Xt); reg.score(Xt, yt)      # R^2

``SVR`` rides the exact same stack as binary ``SVC``: the generalized
QP core (``smo.solve_qp`` with the doubled-variable epsilon-SVR spec),
every ``KernelEngine`` backend, adaptive shrinking, and the
data-parallel sharded solver — the regression solve is ONE QP over the
doubled (2n) sample axis, so ``shard="data"`` shards that axis over the
mesh. Serving is compacted exactly like binary SVC: only rows with
|alpha - alpha*| > 0 are kept.

``engine="nystrom"`` / ``engine="rff"`` switch BOTH classes onto the
approximate-kernel tier: an explicit low-rank feature map Φ (n, rank)
(``repro.core.approx``) feeds the O(n·rank) linear dual coordinate
descent (``repro.core.linear``) instead of the kernel SMO, so training
memory is O(n·rank) — never (n, n) — and million-sample fits are
feasible on one device. ``rank`` / ``landmarks`` / ``seed`` tune the
map; this path always runs locally (``solver``/``mesh``/``shard`` are
ignored) and serving packs the map arrays plus linear weights instead
of a support-vector bank.

Multiclass fits go through the strategy layer (``repro.core.multiclass``):
``strategy`` picks the decomposition ("ovo" pairwise, "ovr" one-vs-rest),
``decision`` the OvO aggregation ("vote" majority, "margin" summed
tanh-margins; OvR always argmaxes). The size-bucketed scheduler solves
each shape bucket at its own width (``schedule="bucketed"``) instead of
padding every task to the widest class pair (``schedule="padded"``, the
legacy layout). ``mesh``/``worker_axes`` shard each bucket's task axis
over the distributed (shard_map) "MPI" layer with a greedy LPT worker
layout; without a mesh the buckets are vmapped on the local device
(single-GPU configuration of the paper).

``shard`` picks WHICH axis of parallelism the mesh carries: ``"task"``
(default) distributes independent binary tasks, ``"data"`` shards the
SAMPLE axis of every solve (``smo.sharded_binary_smo`` — one big QP
across all devices, binary fits included), and ``"auto"`` chooses per
serving bucket: wide-and-few tasks go data-parallel, small-and-many stay
task-parallel. ``shard="cascade"`` trains hierarchically instead
(``repro.core.cascade``): the data is partitioned into
``cascade_shards`` sub-SVMs solved independently (task-parallel over
the mesh when one is given), support-vector unions merge up a binary
reduction tree, and feedback rounds (max ``cascade_rounds``) repeat
until the full-dataset KKT certificate passes at the solver tol —
``converged_`` reports the CERTIFICATE, and ``cascade_rounds_`` /
``cascade_kkt_`` / ``cascade_history_`` expose the trail. The serving
state is identical in shape to every other path, so ``serve.pack`` and
``Predictor`` work unchanged; on the low-rank backends the cascade runs
over row slices of the one shared feature map.

All Gram computation — training AND serving — flows through
``repro.core.kernel_engine``; ``engine`` picks the backend ("auto" |
"dense" | "chunked" | "pallas" or a full ``EngineConfig``). After ``fit``
the model keeps only the support vectors (alpha > 0): per serving bucket
for multiclass, so ``decision_function`` cost scales with #SV, not with
the training-set size.

Serving routes through ``repro.serve``: ``predict`` /
``decision_function`` pack the compacted SV bank into an immutable
``serve.PackedModel`` once and answer every subsequent call through a
cached ``serve.Predictor`` (device-resident SV bank, one jitted decide
program per bucket/batch-bucket shape — the pallas backend uses the
fused multi-task decision kernel). The packed artifact is also the
export format: ``serve.save(path, serve.pack(clf))``. The pre-predictor
per-call engine path is kept as ``_decision_function_engine`` /
``SVR._predict_engine`` — the reference implementation the serve path
is tested bit-identical against.

Binary decision values follow the sklearn sign convention: ``fit`` maps
``classes_[1]`` to +1, so a POSITIVE margin predicts ``classes_[1]``
(before PR 5 the orientation was inverted: ``classes_[0]`` mapped to
+1). The support threshold is RELATIVE to the box: alpha (|beta| for
SVR) counts as a support vector above ``1e-8 * C``, so small-C models
keep their support set instead of collapsing to a constant-bias
predictor.
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import approx, dist, gd, kernel_engine as KE, kernels as K
from repro.core import cascade as cascade_mod
from repro.core import linear
from repro.core import multiclass as MC
from repro.core import smo
from repro import serve

# Support threshold, RELATIVE to the box constraint: alpha > _SV_EPS * C
# counts as a support vector. An absolute cutoff drops EVERY SV once
# C < eps (all alpha <= C), collapsing the model to its constant bias.
_SV_EPS = 1e-8


def _sv_threshold(C: float) -> float:
    return _SV_EPS * float(C)


def _resolve_fit_inputs(kernel_cfg: K.KernelParams,
                        x) -> tuple[np.ndarray, K.KernelParams]:
    """Shared SVC/SVR fit-entry plumbing: f32-cast the training matrix
    and re-resolve the gamma<=0 "scale" sentinel from THIS data, so a
    refit on new data recomputes gamma (sklearn semantics) instead of
    reusing the first fit's value."""
    x = np.asarray(x, np.float32)
    return x, K.resolve_gamma(kernel_cfg, jnp.asarray(x))


@lru_cache(maxsize=64)
def _jitted_binary_fit(solver: str, cfg, kernel, ecfg):
    """Jitted binary solver, cached per static config: jit keys its
    cache on the callable object, so wrapping a fresh lambda per ``fit``
    would retrace and recompile every call (cf.
    ``smo._sharded_smo_program``) — a warm-up fit would warm nothing."""
    fn = smo.binary_smo if solver == "smo" else gd.binary_gd
    return jax.jit(lambda xx, yv: fn(xx, yv, cfg=cfg, kernel=kernel,
                                     engine=ecfg))


@lru_cache(maxsize=64)
def _jitted_svr_fit(solver: str, epsilon: float, cfg, kernel, ecfg):
    """Jitted epsilon-SVR solver, cached per static config (see
    ``_jitted_binary_fit``)."""
    fn = smo.svr_smo if solver == "smo" else gd.svr_gd
    return jax.jit(lambda xx, yv: fn(xx, yv, epsilon=epsilon, cfg=cfg,
                                     kernel=kernel, engine=ecfg))


# serving-side engine resolution lives with the serving subsystem now
_serving_cfg = serve.serving_config


def _cached_predictor(model) -> "serve.Predictor":
    """Shared SVC/SVR predictor cache: one ``serve.Predictor`` per
    serving engine config, packed lazily; ``fit`` resets the cache so a
    refit repacks."""
    assert model._fitted
    scfg = _serving_cfg(model.engine_cfg)
    pred = model._predictors.get(scfg)
    if pred is None:
        pred = serve.Predictor(serve.pack(model), engine=scfg)
        model._predictors[scfg] = pred
    return pred


class _ServingBucket(NamedTuple):
    """One compacted serving group: tasks whose SV counts round to the
    same pow2 width, stacked for a single vmapped engine.decide."""

    task_ids: np.ndarray  # (Cb,) TaskSet indices
    sv_x: np.ndarray      # (Cb, w, d) support vectors, zero-padded
    sv_coef: np.ndarray   # (Cb, w) alpha_i * y_i, 0 on padding
    b: np.ndarray         # (Cb,)


class SVC:
    def __init__(self, *, kernel: str = "rbf", C: float = 1.0,
                 gamma: float = -1.0, degree: int = 3, coef0: float = 0.0,
                 tol: float = 1e-3, max_iter: int = 100_000,
                 solver: str = "smo", gd_lr: float = 0.01,
                 gd_steps: int = 300,
                 engine: str | KE.EngineConfig = "auto",
                 rank: int = 256, landmarks: str = "uniform",
                 seed: int = 0,
                 shrink_every: int = 0,
                 strategy: str | MC.MulticlassStrategy = "ovo",
                 decision: str = "vote",
                 schedule: str = "bucketed",
                 mesh: Optional[Mesh] = None,
                 worker_axes: tuple[str, ...] = ("workers",),
                 shard: str = "task",
                 cascade_shards: int = 4,
                 cascade_rounds: int = 8):
        # the constructor's params keep the gamma<=0 "scale" sentinel;
        # fit() re-resolves from THEM each call, so a refit on new data
        # recomputes gamma (sklearn semantics) instead of reusing the
        # value resolved from the first fit's data
        self._kernel_cfg = K.KernelParams(name=kernel, gamma=gamma,
                                          degree=degree, coef0=coef0)
        self.kernel_params = self._kernel_cfg
        self.smo_cfg = smo.SMOConfig(C=C, tol=tol, max_iter=max_iter,
                                     shrink_every=shrink_every)
        self.gd_cfg = gd.GDConfig(C=C, lr=gd_lr, steps=gd_steps)
        self.solver = solver
        # rank/landmarks/seed only matter for the approximate backends
        # ("nystrom" | "rff"); they ride in EngineConfig so an explicit
        # EngineConfig instance carries its own values
        self.engine_cfg = (engine if isinstance(engine, KE.EngineConfig)
                           else KE.EngineConfig(backend=engine, rank=rank,
                                                landmarks=landmarks,
                                                seed=seed))
        # max_iter bounds BOTH solvers: SMO pair updates and (as epochs)
        # the low-rank DCD sweeps — it used to be silently dropped here
        self.dcd_cfg = linear.DCDConfig(C=C, tol=tol, max_epochs=max_iter)
        self.strategy = MC.get_strategy(strategy)
        if decision not in ("vote", "margin"):
            raise ValueError(f"unknown OvO decision {decision!r}; "
                             "expected 'vote' or 'margin'")
        self.decision = decision
        if schedule not in ("bucketed", "padded"):
            raise ValueError(f"unknown schedule {schedule!r}; "
                             "expected 'bucketed' or 'padded'")
        self.schedule = schedule
        self.mesh = mesh
        self.worker_axes = worker_axes
        if shard not in ("task", "data", "auto", "cascade"):
            raise ValueError(f"unknown shard mode {shard!r}; expected "
                             "'task', 'data', 'auto' or 'cascade'")
        self.shard = shard
        self.cascade_cfg = cascade_mod.CascadeConfig(
            shards=cascade_shards, rounds=cascade_rounds)
        self._fitted = False

    def _serving_cfg(self) -> KE.EngineConfig:
        return _serving_cfg(self.engine_cfg)

    def _serving_engine(self, sv: jax.Array) -> KE.KernelEngine:
        return KE.make_engine(sv, self.kernel_params, self._serving_cfg())

    # ------------------------------------------------------------------ fit
    def fit(self, x: np.ndarray, y: np.ndarray) -> "SVC":
        x, self.kernel_params = _resolve_fit_inputs(self._kernel_cfg, x)
        y = np.asarray(y)
        classes = np.unique(y)
        if len(classes) < 2:
            raise ValueError(
                f"SVC.fit needs >= 2 classes in y, got {len(classes)} "
                f"({classes.tolist()}); a single-class problem has no "
                f"decision boundary to learn")
        self.classes_ = classes
        self._predictors: dict = {}
        self._feature_map = None
        lowrank = self.engine_cfg.backend in KE.LOWRANK_BACKENDS
        if len(classes) == 2:
            if lowrank:
                self._fit_binary_lowrank(x, y, classes)
            else:
                self._fit_binary(x, y, classes)
        elif lowrank:
            self._fit_multiclass_lowrank(x, y)
        else:
            self._fit_multiclass(x, y)
        self._fitted = True
        return self

    def _use_data_parallel_binary(self, n: int) -> bool:
        """The sharded single-problem path: explicit shard="data"
        (validated hard by the shared ``dist.validate_data_shard`` —
        no mesh / GD / multi-axis raises instead of silently fitting
        locally), or "auto" once the problem is wide enough to amortize
        the per-iteration collectives."""
        if self.shard == "data":
            dist.validate_data_shard(self.mesh, self.worker_axes,
                                     self.solver)
            return True
        if self.mesh is None or self.shard in ("task", "cascade"):
            return False
        # auto: mirror _wants_data_parallel's guards — never route a
        # single-worker mesh through the collective program (worker-axis
        # resolution validates the axes against the mesh up front)
        n_workers = dist.resolve_worker_count(self.mesh,
                                              tuple(self.worker_axes))
        return (self.solver == "smo" and len(self.worker_axes) == 1
                and n_workers > 1 and n >= dist.DATA_PARALLEL_MIN_WIDTH)

    def _fit_binary(self, x, y, classes) -> None:
        # sklearn orientation: classes_[1] maps to +1, so a positive
        # decision margin predicts classes_[1]
        yy = np.where(y == classes[1], 1.0, -1.0).astype(np.float32)
        ecfg = self.engine_cfg
        if self.shard == "cascade":
            cascade_mod.validate_cascade(self.solver, self.cascade_cfg)
            r = cascade_mod.cascade_binary(
                x, yy, smo_cfg=self.smo_cfg, kernel=self.kernel_params,
                engine=ecfg, cascade=self.cascade_cfg, mesh=self.mesh,
                worker_axes=self.worker_axes)
            self.n_iter_ = int(r.n_iter)
            # the cascade's convergence IS the certificate: kkt_violation
            # over the full dataset <= tol, recomputed in float64
            self.converged_ = bool(r.converged)
            self.cascade_rounds_ = int(r.rounds)
            self.cascade_kkt_ = float(r.kkt)
            self.cascade_history_ = r.history
        elif self._use_data_parallel_binary(x.shape[0]):
            r = smo.sharded_binary_smo(
                jnp.asarray(x), jnp.asarray(yy), mesh=self.mesh,
                axis=self.worker_axes[0], cfg=self.smo_cfg,
                kernel=self.kernel_params, engine=ecfg)
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)
        elif self.solver == "smo":
            r = _jitted_binary_fit("smo", self.smo_cfg,
                                   self.kernel_params, ecfg)(
                jnp.asarray(x), jnp.asarray(yy))
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)
        else:
            r = _jitted_binary_fit("gd", self.gd_cfg,
                                   self.kernel_params, ecfg)(
                jnp.asarray(x), jnp.asarray(yy))
            self.n_iter_ = int(r.n_iter)
            self.converged_ = True
        self._binary = True
        self.alpha_, self.b_ = np.asarray(r.alpha), float(r.b)
        # serving state: compacted support-vector set only
        sv = self.alpha_ > _sv_threshold(self.smo_cfg.C)
        self.support_ = np.where(sv)[0]
        self.n_support_ = int(sv.sum())
        self.support_vectors_ = x[sv]
        self.dual_coef_ = (self.alpha_ * yy)[sv].astype(np.float32)

    def _fit_binary_lowrank(self, x, y, classes) -> None:
        """Approximate-kernel binary fit: explicit low-rank features
        (Nystrom landmarks / random Fourier features,
        ``repro.core.approx``) + the O(n k) dual coordinate descent
        (``repro.core.linear``) — no (n, n) object is ever formed, so n
        is bounded by O(n·rank) memory, not the Gram. The linear path
        always runs locally and ignores ``solver``/``mesh``/``shard``."""
        yy = np.where(y == classes[1], 1.0, -1.0).astype(np.float32)
        xj = jnp.asarray(x)
        fmap = approx.make_feature_map(xj, self.kernel_params,
                                       self.engine_cfg)
        phi = fmap.transform(xj)
        if self.shard == "cascade":
            # cascade over row slices of the ONE shared feature map; the
            # solver knob is ignored on this path, so don't validate it
            cascade_mod.validate_cascade(None, self.cascade_cfg)
            r = cascade_mod.cascade_dcd(phi, yy, dcd_cfg=self.dcd_cfg,
                                        cascade=self.cascade_cfg)
            self.cascade_rounds_ = int(r.rounds)
            self.cascade_kkt_ = float(r.kkt)
            self.cascade_history_ = r.history
        else:
            r = linear.fit_linear_svc(self.dcd_cfg)(phi, jnp.asarray(yy))
        self._binary = True
        self._feature_map = fmap
        self.alpha_, self.b_ = np.asarray(r.alpha), float(r.b)
        self.w_ = np.asarray(r.w)
        self.n_iter_ = int(r.n_iter)
        self.converged_ = bool(r.converged)
        sv = self.alpha_ > _sv_threshold(self.smo_cfg.C)
        self.support_ = np.where(sv)[0]
        self.n_support_ = int(sv.sum())
        self.support_vectors_ = x[sv]
        self.dual_coef_ = (self.alpha_ * yy)[sv].astype(np.float32)

    def _fit_multiclass_lowrank(self, x, y) -> None:
        """Multiclass over ONE feature map shared by every binary task:
        each task is a linear DCD solve over its slice of the SAME
        low-rank feature space, so serving is one feature transform
        followed by a (n_tasks, rank) matmul — no per-task SV banks."""
        taskset = self.strategy.build_taskset(x, y)
        fmap = approx.make_feature_map(jnp.asarray(x), self.kernel_params,
                                       self.engine_cfg)
        # transform the full X ONCE and gather each task's rows — OvO
        # tasks overlap heavily (every class appears in m-1 pairs), so
        # per-task transforms recompute the same feature rows m-1 times
        phi = fmap.transform(jnp.asarray(x))
        fit = linear.fit_linear_svc(self.dcd_cfg)
        use_cascade = self.shard == "cascade"
        if use_cascade:
            cascade_mod.validate_cascade(None, self.cascade_cfg)
            rounds = np.zeros(taskset.n_tasks, np.int64)
            kkt = np.zeros(taskset.n_tasks, np.float64)  # repro: noqa[R002] -- host-side store of the f64 cascade certificate values
        n_tasks = taskset.n_tasks
        task_w = np.zeros((n_tasks, fmap.rank), np.float32)
        task_b = np.zeros((n_tasks,), np.float32)
        n_support = np.zeros(n_tasks, np.int64)
        n_iter = np.zeros(n_tasks, np.int64)
        converged = np.ones(n_tasks, bool)
        alphas = []
        thr = _sv_threshold(self.smo_cfg.C)
        for t, task in enumerate(taskset.tasks):
            phi_t = (phi[jnp.asarray(task.indices)]
                     if task.indices is not None
                     else fmap.transform(jnp.asarray(task.x)))
            if use_cascade:
                r = cascade_mod.cascade_dcd(phi_t, task.y,
                                            dcd_cfg=self.dcd_cfg,
                                            cascade=self.cascade_cfg)
                rounds[t] = r.rounds
                kkt[t] = r.kkt
            else:
                r = fit(phi_t, jnp.asarray(task.y))
            a = np.asarray(r.alpha)
            alphas.append(a)
            task_w[t] = np.asarray(r.w)
            task_b[t] = float(r.b)
            n_support[t] = int((a > thr).sum())
            n_iter[t] = int(r.n_iter)
            converged[t] = bool(r.converged)
        if use_cascade:
            self.cascade_rounds_ = rounds
            self.cascade_kkt_ = kkt
        self._binary = False
        self._feature_map = fmap
        self._taskset = taskset
        self._task_alpha = alphas
        self.task_w_ = task_w
        self.task_b_ = task_b
        self.n_support_ = n_support
        self.n_iter_ = int(n_iter.max())
        self.converged_ = bool(converged.all())

    def _fit_taskset_cascade(self, taskset: MC.TaskSet) -> dist.TaskSetFit:
        """Each binary task trained by its own hierarchical cascade
        (shard leaves distribute task-parallel over the mesh inside each
        cascade level); results come back in TaskSetFit layout so the
        standard serving compaction applies unchanged. ``converged``
        entries report the per-task global KKT certificate."""
        c = taskset.n_tasks
        sizes = taskset.sizes
        alpha = np.zeros((c, int(sizes.max())), np.float32)
        b = np.zeros(c, np.float32)
        n_iter = np.zeros(c, np.int64)
        converged = np.zeros(c, bool)
        rounds = np.zeros(c, np.int64)
        kkt = np.zeros(c, np.float64)  # repro: noqa[R002] -- host-side store of the f64 cascade certificate values
        for t, task in enumerate(taskset.tasks):
            r = cascade_mod.cascade_binary(
                task.x, task.y, smo_cfg=self.smo_cfg,
                kernel=self.kernel_params, engine=self.engine_cfg,
                cascade=self.cascade_cfg, mesh=self.mesh,
                worker_axes=self.worker_axes)
            alpha[t, :task.size] = r.alpha
            b[t] = r.b
            n_iter[t] = r.n_iter
            converged[t] = r.converged
            rounds[t] = r.rounds
            kkt[t] = r.kkt
        self.cascade_rounds_ = rounds
        self.cascade_kkt_ = kkt
        return dist.TaskSetFit(alpha=alpha, b=b, n_iter=n_iter,
                               converged=converged, sizes=sizes)

    def _fit_multiclass(self, x, y) -> None:
        taskset = self.strategy.build_taskset(x, y)
        if self.shard == "cascade":
            cascade_mod.validate_cascade(self.solver, self.cascade_cfg)
            sched = None
            fit = self._fit_taskset_cascade(taskset)
        else:
            n_workers = dist.resolve_worker_count(self.mesh,
                                                  tuple(self.worker_axes))
            bucket_by = "pow2" if self.schedule == "bucketed" else "none"
            sched = MC.build_schedule(
                taskset.sizes,
                MC.ScheduleConfig(bucket_by=bucket_by,
                                  n_workers=n_workers))
            fit = dist.fit_taskset(
                taskset, sched, mesh=self.mesh,
                worker_axes=self.worker_axes, solver=self.solver,
                smo_cfg=self.smo_cfg, gd_cfg=self.gd_cfg,
                kernel=self.kernel_params, engine=self.engine_cfg,
                shard=self.shard)
        self._binary = False
        self._taskset = taskset
        self._schedule = sched
        self._fit = fit
        self.n_iter_ = int(np.max(fit.n_iter))
        self.converged_ = bool(np.all(fit.converged))
        self._compact_tasks()

    def _compact_tasks(self) -> None:
        """Per-bucket SV compaction: keep only alpha > 0 rows of each
        task, grouped into pow2 SV-width serving buckets — one vmapped
        ``engine.decide`` program per bucket at #SV cost, instead of one
        program padded to the widest task."""
        taskset, fit = self._taskset, self._fit
        sv_counts = np.zeros(taskset.n_tasks, np.int64)
        sv_idx = []
        for t, task in enumerate(taskset.tasks):
            idx = np.flatnonzero(fit.alpha[t, :task.size]
                                 > _sv_threshold(self.smo_cfg.C))
            sv_idx.append(idx)
            sv_counts[t] = len(idx)
        self.n_support_ = sv_counts

        sched = MC.build_schedule(
            np.maximum(sv_counts, 1),
            MC.ScheduleConfig(bucket_by="pow2", min_width=8, n_workers=1))
        d = taskset.tasks[0].x.shape[1]
        groups = []
        for bucket in sched.buckets:
            ids = bucket.task_ids.reshape(-1)
            ids = ids[ids >= 0]
            # pow2 groups the tasks; the stack width is the exact max SV
            # count inside the group (never wider than any member task)
            width = max(1, int(sv_counts[ids].max()))
            sv_x = np.zeros((len(ids), width, d), np.float32)
            sv_coef = np.zeros((len(ids), width), np.float32)
            for s, t in enumerate(ids):
                idx = sv_idx[t]
                task = taskset.tasks[t]
                sv_x[s, :len(idx)] = task.x[idx]
                sv_coef[s, :len(idx)] = (fit.alpha[t, idx]
                                         * task.y[idx]).astype(np.float32)
            groups.append(_ServingBucket(task_ids=ids, sv_x=sv_x,
                                         sv_coef=sv_coef, b=fit.b[ids]))
        self._serving_buckets = groups

    # ------------------------------------------------------------- predict
    def predictor(self) -> "serve.Predictor":
        """The cached batched serving engine for this fit (one per
        serving engine config — the SV bank stays resident on device and
        decide programs jit-cache across calls). Repacked on refit."""
        return _cached_predictor(self)

    def decision_function(self, xt: np.ndarray) -> np.ndarray:
        """(n_test,) margins for binary (positive => ``classes_[1]``,
        the sklearn orientation), (n_tasks, n_test) stacked binary
        decisions for multiclass (OvO: m(m-1)/2 rows, OvR: m rows)."""
        return self.predictor().decision_function(xt)

    def _decision_function_engine(self, xt: np.ndarray) -> np.ndarray:
        """Pre-predictor reference path: rebuilds a ``KernelEngine`` and
        loops serving buckets in Python on every call. Kept as the
        fallback the serve path is tested bit-identical against (and as
        the baseline ``benchmarks/bench_serving.py`` measures)."""
        assert self._fitted
        xt = jnp.asarray(np.asarray(xt, np.float32))
        if self._feature_map is not None:
            # low-rank linear path: one feature transform, then w (or the
            # stacked task_w matrix) — no SV bank, no kernel engine
            phi_t = self._feature_map.transform(xt)
            if self._binary:
                return np.asarray(K.f32_dot(phi_t, jnp.asarray(self.w_))
                                  + self.b_)
            df = K.f32_dot(phi_t, jnp.asarray(self.task_w_).T)
            return (np.asarray(df).T
                    + self.task_b_[:, None]).astype(np.float32)
        if self._binary:
            if self.n_support_ == 0:  # degenerate fit: constant decision
                return np.full(xt.shape[0], self.b_, np.float32)
            eng = self._serving_engine(jnp.asarray(self.support_vectors_))
            df = eng.decide(xt, jnp.asarray(self.dual_coef_), self.b_)
            return np.asarray(df)
        # (C, n_test) stacked binary decisions, one vmapped engine-backed
        # program per serving bucket (respects engine="pallas"/"chunked")
        scfg = self._serving_cfg()
        kp = self.kernel_params

        def one(sv, coef, b):
            return KE.make_engine(sv, kp, scfg).decide(xt, coef, b)

        df = np.zeros((self._taskset.n_tasks, xt.shape[0]), np.float32)
        for g in self._serving_buckets:
            out = jax.vmap(one)(jnp.asarray(g.sv_x), jnp.asarray(g.sv_coef),
                                jnp.asarray(g.b))
            df[g.task_ids] = np.asarray(out)
        return df

    def predict(self, xt: np.ndarray) -> np.ndarray:
        return self.predictor().predict(xt)

    def score(self, xt: np.ndarray, yt: np.ndarray) -> float:
        return float(np.mean(self.predict(xt) == np.asarray(yt)))


class SVR:
    """epsilon-insensitive Support Vector Regression on the generalized
    SMO core — one doubled-variable QP through the same engine /
    shrinking / sharding stack as binary ``SVC`` (module docstring)."""

    def __init__(self, *, kernel: str = "rbf", C: float = 1.0,
                 epsilon: float = 0.1,
                 gamma: float = -1.0, degree: int = 3, coef0: float = 0.0,
                 tol: float = 1e-3, max_iter: int = 100_000,
                 solver: str = "smo", gd_lr: float = 0.01,
                 gd_steps: int = 300,
                 engine: str | KE.EngineConfig = "auto",
                 rank: int = 256, landmarks: str = "uniform",
                 seed: int = 0,
                 shrink_every: int = 0,
                 mesh: Optional[Mesh] = None,
                 worker_axes: tuple[str, ...] = ("workers",),
                 shard: str = "task",
                 cascade_shards: int = 4,
                 cascade_rounds: int = 8):
        # gamma "scale" sentinel kept; re-resolved per fit (see SVC)
        self._kernel_cfg = K.KernelParams(name=kernel, gamma=gamma,
                                          degree=degree, coef0=coef0)
        self.kernel_params = self._kernel_cfg
        self.smo_cfg = smo.SMOConfig(C=C, tol=tol, max_iter=max_iter,
                                     shrink_every=shrink_every)
        self.gd_cfg = gd.GDConfig(C=C, lr=gd_lr, steps=gd_steps)
        self.epsilon = float(epsilon)
        self.solver = solver
        # approximate-backend knobs ride in EngineConfig (see SVC)
        self.engine_cfg = (engine if isinstance(engine, KE.EngineConfig)
                           else KE.EngineConfig(backend=engine, rank=rank,
                                                landmarks=landmarks,
                                                seed=seed))
        # max_iter bounds BOTH solvers: SMO pair updates and (as epochs)
        # the low-rank DCD sweeps — it used to be silently dropped here
        self.dcd_cfg = linear.DCDConfig(C=C, tol=tol, max_epochs=max_iter)
        self.mesh = mesh
        self.worker_axes = worker_axes
        if shard not in ("task", "data", "auto", "cascade"):
            raise ValueError(f"unknown shard mode {shard!r}; expected "
                             "'task', 'data', 'auto' or 'cascade'")
        self.shard = shard
        self.cascade_cfg = cascade_mod.CascadeConfig(
            shards=cascade_shards, rounds=cascade_rounds)
        self._fitted = False

    def _use_data_parallel(self, n: int) -> bool:
        """Mirrors ``SVC._use_data_parallel_binary`` on the DOUBLED
        sample axis (the sharded program sees 2n rows)."""
        if self.shard == "data":
            dist.validate_data_shard(self.mesh, self.worker_axes,
                                     self.solver)
            return True
        if self.mesh is None or self.shard in ("task", "cascade"):
            return False
        n_workers = dist.resolve_worker_count(self.mesh,
                                              tuple(self.worker_axes))
        return (self.solver == "smo" and len(self.worker_axes) == 1
                and n_workers > 1
                and 2 * n >= dist.DATA_PARALLEL_MIN_WIDTH)

    # ------------------------------------------------------------------ fit
    def fit(self, x: np.ndarray, y: np.ndarray) -> "SVR":
        x, self.kernel_params = _resolve_fit_inputs(self._kernel_cfg, x)
        y = np.asarray(y, np.float32)
        self._feature_map = None
        eps, ecfg = self.epsilon, self.engine_cfg
        if ecfg.backend in KE.LOWRANK_BACKENDS:
            # approximate-kernel path: low-rank features + linear DCD on
            # the doubled epsilon-SVR QP (see SVC._fit_binary_lowrank)
            xj = jnp.asarray(x)
            fmap = approx.make_feature_map(xj, self.kernel_params, ecfg)
            phi = fmap.transform(xj)
            if self.shard == "cascade":
                cascade_mod.validate_cascade(None, self.cascade_cfg)
                r = cascade_mod.cascade_dcd_svr(
                    phi, y, epsilon=eps, dcd_cfg=self.dcd_cfg,
                    cascade=self.cascade_cfg)
                self.cascade_rounds_ = int(r.rounds)
                self.cascade_kkt_ = float(r.kkt)
                self.cascade_history_ = r.history
            else:
                r = linear.fit_linear_svr(eps, self.dcd_cfg)(
                    phi, jnp.asarray(y))
            self._feature_map = fmap
            self.w_ = np.asarray(r.w)
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)
        elif self.shard == "cascade":
            cascade_mod.validate_cascade(self.solver, self.cascade_cfg)
            r = cascade_mod.cascade_svr(
                x, y, epsilon=eps, smo_cfg=self.smo_cfg,
                kernel=self.kernel_params, engine=ecfg,
                cascade=self.cascade_cfg, mesh=self.mesh,
                worker_axes=self.worker_axes)
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)   # certified (see SVC)
            self.cascade_rounds_ = int(r.rounds)
            self.cascade_kkt_ = float(r.kkt)
            self.cascade_history_ = r.history
        elif self._use_data_parallel(x.shape[0]):
            r = smo.sharded_svr_smo(
                jnp.asarray(x), jnp.asarray(y), epsilon=eps,
                mesh=self.mesh, axis=self.worker_axes[0],
                cfg=self.smo_cfg, kernel=self.kernel_params, engine=ecfg)
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)
        elif self.solver == "smo":
            r = _jitted_svr_fit("smo", eps, self.smo_cfg,
                                self.kernel_params, ecfg)(
                jnp.asarray(x), jnp.asarray(y))
            self.n_iter_ = int(r.n_iter)
            self.converged_ = bool(r.converged)
        else:
            r = _jitted_svr_fit("gd", eps, self.gd_cfg,
                                self.kernel_params, ecfg)(
                jnp.asarray(x), jnp.asarray(y))
            self.n_iter_ = int(r.n_iter)
            self.converged_ = True
            self.loss_curve_ = np.asarray(r.loss_curve)
        if isinstance(r, cascade_mod.CascadeResult):
            # cascade layout: alpha IS the per-sample beta, alpha_raw the
            # (2n,) doubled scatter of the root solve
            self.beta_ = np.asarray(r.alpha)
            self.b_ = float(r.b)
            self.alpha_raw_ = np.asarray(r.alpha_raw)
        else:
            self.beta_ = np.asarray(r.beta)
            self.b_ = float(r.b)
            self.alpha_raw_ = np.asarray(r.alpha)  # (2n,) [alpha; alpha*]
        # serving state: compacted support-vector set only
        sv = np.abs(self.beta_) > _sv_threshold(self.smo_cfg.C)
        self.support_ = np.where(sv)[0]
        self.n_support_ = int(sv.sum())
        self.support_vectors_ = x[sv]
        self.dual_coef_ = self.beta_[sv].astype(np.float32)
        self._predictors: dict = {}
        self._fitted = True
        return self

    # ------------------------------------------------------------- predict
    def predictor(self) -> "serve.Predictor":
        """The cached batched serving engine for this fit (see
        ``SVC.predictor``)."""
        return _cached_predictor(self)

    def predict(self, xt: np.ndarray) -> np.ndarray:
        return self.predictor().predict(xt)

    def _predict_engine(self, xt: np.ndarray) -> np.ndarray:
        """Pre-predictor reference path (see
        ``SVC._decision_function_engine``)."""
        assert self._fitted
        xt = jnp.asarray(np.asarray(xt, np.float32))
        if self._feature_map is not None:
            phi_t = self._feature_map.transform(xt)
            return np.asarray(K.f32_dot(phi_t, jnp.asarray(self.w_))
                              + self.b_)
        if self.n_support_ == 0:   # every sample inside the tube
            return np.full(xt.shape[0], self.b_, np.float32)
        eng = KE.make_engine(jnp.asarray(self.support_vectors_),
                             self.kernel_params,
                             _serving_cfg(self.engine_cfg))
        pred = eng.decide(xt, jnp.asarray(self.dual_coef_), self.b_)
        return np.asarray(pred)

    def score(self, xt: np.ndarray, yt: np.ndarray) -> float:
        """Coefficient of determination R^2 (sklearn convention)."""
        yt = np.asarray(yt, np.float64)  # repro: noqa[R002] -- host-side R^2 accumulation, never enters jit
        resid = yt - np.asarray(self.predict(xt), np.float64)  # repro: noqa[R002] -- host-side R^2 accumulation, never enters jit
        ss_res = float(np.sum(resid ** 2))
        ss_tot = float(np.sum((yt - yt.mean()) ** 2))
        if ss_tot == 0.0:
            return 1.0 if ss_res == 0.0 else 0.0
        return 1.0 - ss_res / ss_tot
