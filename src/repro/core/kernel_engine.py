"""Unified KernelEngine — every Gram evaluation in the system, one interface.

The paper's central observation is that SVM training cost is dominated by
kernel (Gram) evaluations inside the QP solve, and that the winning
implementation is the one that organizes those evaluations for the
hardware. Before this module the repo scattered that logic over four call
sites (inline Pallas routing in ``core.smo``, the decision paths in
``core.svm``, the OvO layer in ``core.dist`` and ``kernels.ops``), and
every path either materialized the full O(n^2) Gram or recomputed rows
from scratch. ``KernelEngine`` centralizes it.

Interface (all methods jit/vmap-safe; ``x`` may be a tracer)::

    engine.full()            # (n, n) Gram — dense backends only
    engine.diag()            # (n,)  K(x_i, x_i)
    engine.row(i, cache)     # ((n,), cache') one kernel row, LRU-cached
    engine.block(rows, cols) # (r, c) arbitrary sub-block
    engine.matvec(v)         # (n,)  K @ v, chunked — never builds (n, n)
    engine.cross(z)          # (t, n) K(z, X) test-vs-train block
    engine.decide(z, coef,b) # (t,)  K(z, X) @ coef + b, chunked serving
    engine.init_cache()      # functional row-cache state (None if unused)

Backends
--------
``dense``
    Precomputes the (n, n) Gram once (jnp reference kernels). Fastest for
    n up to a few thousand; memory O(n^2). ``row`` is a gather, the cache
    state is ``None``.
``chunked``
    Never materializes (n, n). Rows are computed on the fly in O(n d) and
    cached in a fixed-capacity functional LRU keyed on the working-set
    index — SMO revisits the same violating pair region for many
    consecutive iterations, so the cache converts most row requests into
    a (slots, n) gather. ``matvec``/``decide`` stream over row blocks of
    ``chunk`` samples (peak extra memory O(chunk * n)). This is the
    backend that trains n = 16k-32k RBF problems the dense path cannot
    hold.
``pallas``
    The chunked layout with the Gram hot spots routed through the tiled
    Pallas TPU kernels in ``repro.kernels.ops`` (MXU-aligned VMEM blocks;
    RBF and linear). Non-Pallas kernels fall back to the jnp path.
``sharded``
    The data-parallel backend for SINGLE-problem solves, used INSIDE a
    ``shard_map`` body whose sample axis is sharded over
    ``EngineConfig.shard_axis``. ``x`` is the local (n_local, d) shard;
    the full (n, d) sample matrix is all-gathered once (the data, never
    the Gram), after which every Gram evaluation is local compute:
    methods return the LOCAL SLICE of the global quantity. ``row(i)`` is
    the owner-replicated global row restricted to local samples,
    ``matvec(v_local)`` all-gathers ``v`` and returns the local row
    block of ``K @ v``, ``decide`` psums per-shard partial decisions.
    This is the engine behind ``core.smo.sharded_binary_smo`` — the JAX
    analog of the paper's per-rank Gram row blocks + MPI_Allreduce.

Mixed precision (engine-level)
------------------------------
``EngineConfig(gram_dtype="bf16")`` switches every backend's Gram
computation to bf16 operands with f32 accumulation: the dense/chunked
jnp paths via ``kernels.make_gram_fn(..., compute_dtype=...)``, the
Pallas backend via bf16 tile loads in ``repro.kernels.ops``. Squared
norms are computed from the same rounded values, so RBF self-similarity
stays exactly 1. fp32 remains the default; the bf16 path is
parity-gated against fp32 on the KKT-violation certificate and serving
deltas in ``tests/test_mixed_precision.py``.

Adaptive shrinking (solver-side, engine-aware)
----------------------------------------------
``SMOConfig(shrink_every=k)`` turns on mask-based adaptive shrinking in
``core.smo.binary_smo`` (Narasimhan et al., *Fast SVMs Using Parallel
Adaptive Shrinking*): every ``k`` convergence checks, samples whose alpha
is pinned at a bound (0 or C) and whose optimality value ``f`` lies
beyond the current ``[b_up, b_low]`` corridor on its non-violating side
(``f > b_low + slack`` for I_up-only members, ``f < b_up - slack`` for
I_low-only, slack = ``shrink_slack * tol``) are frozen out of the active
set; working-set selection and f-cache updates are restricted to the
survivors. When the
active set converges, the solver reconstructs the exact f-cache for ALL
samples with one ``engine.matvec`` (chunked — no (n, n) materialization)
and re-checks the un-shrunk KKT conditions before reporting convergence;
if the full problem still violates, the active set resets and
optimization resumes. Knobs: ``shrink_every`` (checks between shrink
passes; 0 disables) and ``shrink_slack`` (corridor slack in units of
``tol``; larger = more conservative freezing).

Shrinking targets the SINGLE-problem (binary, scalar-jit) path. Under
``vmap``/``shard_map`` OvO batching, ``lax.cond`` lowers to ``select``
and executes BOTH branches, so the un-shrink ``matvec`` would run at
every convergence check for every task — leave ``shrink_every=0`` there
(the ``core.dist`` entry points also strip the LRU row cache for the
same reason: a batched cache lookup recomputes the row regardless).

Migration note (old ``gram=`` / ``row_fn=`` / ``use_pallas`` arguments)
-----------------------------------------------------------------------
The pre-engine keyword plumbing still works as thin deprecation shims::

    binary_smo(x, y, gram=G)                  -> DenseKernelEngine(gram=G)
    binary_smo(x, y, row_fn=f)                -> ChunkedKernelEngine(row_fn=f)
    SMOConfig(use_pallas=True)                -> pallas backend
    SMOConfig(precompute_gram=False)          -> chunked backend

New code should pass ``engine=EngineConfig(backend=...)`` (built lazily
inside the jitted solver) or a bound engine from ``make_engine``:

    eng = make_engine(x, kernel, EngineConfig(backend="chunked"))
    r = binary_smo(x, y, engine=eng, cfg=SMOConfig(shrink_every=4))

``SVC`` accepts ``engine="auto"|"dense"|"chunked"|"pallas"`` or a full
``EngineConfig``, and after ``fit`` serves predictions from a compacted
support-vector set (alpha > 0 rows only), so serving cost scales with
#SV rather than n. Serving itself routes through ``repro.serve``: the
predictor's chunked/dense configs run ``engine.decide`` (built inside
the jitted decide program — every method here is jit/vmap-safe), which
makes this module the REFERENCE path the fused pallas serving kernel is
tested against; ``serve.serving_config`` owns the training->serving
backend degradation (dense/auto -> chunked, cache_slots=0).

Regression rides the same engines: the epsilon-SVR solvers
(``core.smo.svr_smo`` / ``core.gd.svr_gd`` / ``SVR``) bind their engine
to the DOUBLED sample matrix [x; x] — the doubled QP's Gram is exactly
the Gram of [x; x], so no backend needs any regression-specific code.
The only knob that reads differently there is ``dense_limit``: the
auto dense/chunked switch sees 2n rows.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import kernels as K

class RowCache(NamedTuple):
    """Functional LRU row-cache state (threaded through solver loops)."""

    keys: jax.Array    # (slots,) int32 row index per slot, -1 = empty
    stamp: jax.Array   # (slots,) int32 last-use tick (min = LRU victim)
    rows: jax.Array    # (slots, n) float32 cached kernel rows
    clock: jax.Array   # () int32 monotone tick
    hits: jax.Array    # () int32 lookup statistics
    misses: jax.Array  # () int32


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine selection/config — hashable, safe to close over jit.

    backend:     auto | dense | chunked | pallas | sharded, or one of
                 the low-rank approximations nystrom | rff
                 (``repro.core.approx.LowRankKernelEngine``: K ≈ Φ Φ^T
                 from an explicit (n, rank) feature map — the
                 million-sample tier).
    cache_slots: LRU row-cache capacity (chunked/pallas row mode).
    chunk:       row-block size for matvec()/decide() streaming.
    dense_limit: 'auto' picks dense up to this n, chunked above; also the
                 guard above which ChunkedKernelEngine.full() refuses to
                 materialize (n, n).
    shard_axis:  mesh axis name the sample axis is sharded over —
                 required by (and only meaningful for) the "sharded"
                 backend, which must be built inside a shard_map body.
    gram_dtype:  Gram compute precision, "fp32" (exact, default) or
                 "bf16" (mixed precision: bf16 operands with f32
                 accumulation — halves Gram HBM traffic on every
                 backend; Pallas tiles load bf16 natively). Training
                 under bf16 is parity-gated against fp32 by the
                 KKT-certificate tests (tests/test_mixed_precision.py).
    rank:        low-rank backends only: feature count (RFF) / landmark
                 count (Nyström, capped at n).
    landmarks:   Nyström landmark sampling, "uniform" | "kmeans++".
    seed:        PRNG seed for landmark choice / frequency sampling —
                 part of the config so a fit is exactly reproducible.
    """

    backend: str = "auto"
    cache_slots: int = 32
    chunk: int = 2048
    dense_limit: int = 8192
    shard_axis: Optional[str] = None
    gram_dtype: str = "fp32"
    rank: int = 256
    landmarks: str = "uniform"
    seed: int = 0


class KernelEngine:
    """Base: owns x + kernel params; subclasses define the Gram strategy."""

    backend = "base"

    def __init__(self, x: jax.Array, kernel: K.KernelParams,
                 cfg: EngineConfig = EngineConfig()):
        self.x = jnp.asarray(x, jnp.float32)
        self.n = self.x.shape[0]
        self.kernel = kernel
        self.cfg = cfg
        self._gram_fn = K.make_gram_fn(kernel,
                                       compute_dtype=cfg.gram_dtype)

    # -------------------------------------------------------- interface
    def full(self) -> jax.Array:
        raise NotImplementedError

    def diag(self) -> jax.Array:
        if self.kernel.name == "rbf":  # K(x, x) = exp(0) exactly
            return jnp.ones((self.n,), jnp.float32)
        return jax.vmap(lambda r: self._gram_fn(r[None], r[None])[0, 0])(
            self.x)

    def row(self, i: jax.Array, cache=None):
        raise NotImplementedError

    def block(self, rows: jax.Array, cols: jax.Array) -> jax.Array:
        return self._gram_fn(self.x[rows], self.x[cols])

    def cross(self, z: jax.Array) -> jax.Array:
        return self._gram_fn(jnp.asarray(z, jnp.float32), self.x)

    def matvec(self, v: jax.Array) -> jax.Array:
        raise NotImplementedError

    def decide(self, z: jax.Array, coef: jax.Array,
               b: jax.Array | float = 0.0) -> jax.Array:
        """K(z, X) @ coef + b, streamed over test-row chunks."""
        z = jnp.asarray(z, jnp.float32)
        t = z.shape[0]
        chunk = min(self.cfg.chunk, max(t, 1))
        pad = (-t) % chunk
        zp = jnp.pad(z, ((0, pad), (0, 0)))
        blocks = zp.reshape(-1, chunk, z.shape[1])
        out = jax.lax.map(lambda zb: K.f32_dot(self.cross(zb), coef), blocks)
        return out.reshape(-1)[:t] + b

    def init_cache(self):
        return None


class DenseKernelEngine(KernelEngine):
    """Precomputed (n, n) Gram — the n<=~8k fast path."""

    backend = "dense"

    def __init__(self, x, kernel, cfg: EngineConfig = EngineConfig(), *,
                 gram: Optional[jax.Array] = None):
        super().__init__(x, kernel, cfg)
        self.gram = self._gram_fn(self.x, self.x) if gram is None else gram

    def full(self):
        return self.gram

    def diag(self):
        return jnp.diagonal(self.gram)

    def row(self, i, cache=None):
        return self.gram[i], cache

    def block(self, rows, cols):
        return self.gram[rows][:, cols]

    def matvec(self, v):
        return K.f32_dot(self.gram, v)


class ChunkedKernelEngine(KernelEngine):
    """On-the-fly rows + functional LRU cache; O(n d) resident memory."""

    backend = "chunked"

    def __init__(self, x, kernel, cfg: EngineConfig = EngineConfig(), *,
                 row_fn: Optional[Callable] = None):
        super().__init__(x, kernel, cfg)
        self._row_fn = row_fn

    # ------------------------------------------------------------- rows
    def _compute_row(self, i):
        if self._row_fn is not None:
            return self._row_fn(self.x, self.x[i])
        return self._gram_fn(self.x, self.x[i][None, :])[:, 0]

    def init_cache(self) -> Optional[RowCache]:
        slots = self.cfg.cache_slots
        if slots <= 0:
            return None
        z32 = jnp.zeros((), jnp.int32)
        return RowCache(keys=jnp.full((slots,), -1, jnp.int32),
                        stamp=jnp.zeros((slots,), jnp.int32),
                        rows=jnp.zeros((slots, self.n), jnp.float32),
                        clock=z32, hits=z32, misses=z32)

    def row(self, i, cache: Optional[RowCache] = None):
        if cache is None:
            return self._compute_row(i), None
        hit_vec = cache.keys == i
        hit_slot = jnp.argmax(hit_vec)
        lru_slot = jnp.argmin(cache.stamp)
        tick = cache.clock + 1

        def on_hit(c: RowCache):
            return c.rows[hit_slot], c._replace(
                stamp=c.stamp.at[hit_slot].set(tick),
                clock=tick, hits=c.hits + 1)

        def on_miss(c: RowCache):
            r = self._compute_row(i)
            return r, c._replace(
                keys=c.keys.at[lru_slot].set(i.astype(jnp.int32)
                                             if hasattr(i, "astype")
                                             else jnp.int32(i)),
                rows=c.rows.at[lru_slot].set(r),
                stamp=c.stamp.at[lru_slot].set(tick),
                clock=tick, misses=c.misses + 1)

        return jax.lax.cond(jnp.any(hit_vec), on_hit, on_miss, cache)

    # ---------------------------------------------------------- streams
    def _row_blocks(self):
        chunk = min(self.cfg.chunk, self.n)
        pad = (-self.n) % chunk
        xp = jnp.pad(self.x, ((0, pad), (0, 0)))
        return xp.reshape(-1, chunk, self.x.shape[1]), chunk

    def matvec(self, v):
        blocks, _ = self._row_blocks()
        out = jax.lax.map(lambda xb: K.f32_dot(self._gram_fn(xb, self.x), v),
                          blocks)
        return out.reshape(-1)[:self.n]

    def full(self):
        if self.n > self.cfg.dense_limit:
            raise RuntimeError(
                f"ChunkedKernelEngine.full(): refusing to materialize a "
                f"({self.n}, {self.n}) Gram (dense_limit="
                f"{self.cfg.dense_limit}); use row()/block()/matvec()")
        blocks, _ = self._row_blocks()
        out = jax.lax.map(lambda xb: self._gram_fn(xb, self.x), blocks)
        return out.reshape(-1, self.n)[:self.n]


class PallasKernelEngine(ChunkedKernelEngine):
    """Chunked layout with Gram hot spots on the tiled Pallas TPU kernels.

    RBF and linear route through ``repro.kernels.ops`` (MXU-aligned VMEM
    tiles); other kernels fall back to the jnp reference path.
    """

    backend = "pallas"

    def __init__(self, x, kernel, cfg: EngineConfig = EngineConfig()):
        from repro.kernels import ops as pallas_ops
        self._ops = pallas_ops
        self._pallas_mode = (kernel.name
                             if kernel.name in ("rbf", "linear") else None)
        row_fn = None
        if kernel.name == "rbf":
            row_fn = pallas_ops.gram_row_fn(gamma=kernel.gamma,
                                            compute_dtype=cfg.gram_dtype)
        super().__init__(x, kernel, cfg, row_fn=row_fn)

    def _pallas_gram(self, a, b):
        return self._ops.rbf_gram(a, b, gamma=self.kernel.gamma,
                                  mode=self._pallas_mode,
                                  compute_dtype=self.cfg.gram_dtype)

    def cross(self, z):
        if self._pallas_mode is None:
            return super().cross(z)
        return self._pallas_gram(jnp.asarray(z, jnp.float32), self.x)

    def block(self, rows, cols):
        if self._pallas_mode is None:
            return super().block(rows, cols)
        return self._pallas_gram(self.x[rows], self.x[cols])

    def matvec(self, v):
        if self._pallas_mode is None:
            return super().matvec(v)
        blocks, _ = self._row_blocks()
        out = jax.lax.map(
            lambda xb: K.f32_dot(self._pallas_gram(xb, self.x), v), blocks)
        return out.reshape(-1)[:self.n]

    def decide(self, z, coef, b=0.0):
        if self.kernel.name == "rbf":
            return self._ops.decision(jnp.asarray(z, jnp.float32), self.x,
                                      coef, b, gamma=self.kernel.gamma,
                                      compute_dtype=self.cfg.gram_dtype)
        return super().decide(z, coef, b)

    def full(self):
        if self.n > self.cfg.dense_limit:
            raise RuntimeError(
                f"PallasKernelEngine.full(): refusing to materialize a "
                f"({self.n}, {self.n}) Gram (dense_limit="
                f"{self.cfg.dense_limit})")
        if self._pallas_mode is None:
            return super().full()
        return self._pallas_gram(self.x, self.x)


class ShardedKernelEngine(ChunkedKernelEngine):
    """Sample-axis-sharded engine for use INSIDE a ``shard_map`` body.

    ``x`` is the LOCAL (n_local, d) shard of the sample matrix;
    construction all-gathers the full (n, d) matrix once (tiled — the
    data is O(n d) and replicating it is what makes every subsequent
    Gram evaluation collective-free; the (n, n) Gram itself is never
    materialized anywhere). Methods return the LOCAL SLICE of the global
    quantity, so the solver's per-sample state (f-cache, alpha, mask)
    stays sharded:

      row(i)     -> (n_local,)  K(x_i, x_local); i is a GLOBAL index,
                    LRU-cached per shard under the global key
      matvec(v)  -> (n_local,)  local row block of K @ v from the LOCAL
                    shard of v (one all_gather of v per call)
      diag()     -> (n_local,)  local self-kernel diagonal
      cross(z)   -> (t, n_local) local column block of K(z, X)
      decide(..) -> (t,)        exact global decision (psum of partials)

    ``full()`` is refused: there is no global Gram in this layout.
    """

    backend = "sharded"

    def __init__(self, x, kernel, cfg: EngineConfig = EngineConfig()):
        if not cfg.shard_axis:
            raise ValueError(
                "ShardedKernelEngine needs EngineConfig.shard_axis (the "
                "mesh axis the sample dimension is sharded over)")
        super().__init__(x, kernel, cfg)
        self.axis = cfg.shard_axis
        self.x_full = jax.lax.all_gather(self.x, self.axis, tiled=True)
        self.n_global = self.x_full.shape[0]

    def _compute_row(self, i):
        # x_i comes off the replicated x_full: no collective per row
        if self._row_fn is not None:
            return self._row_fn(self.x, self.x_full[i])
        return self._gram_fn(self.x, self.x_full[i][None, :])[:, 0]

    def matvec(self, v):
        v_full = jax.lax.all_gather(v, self.axis, tiled=True)
        blocks, _ = self._row_blocks()
        out = jax.lax.map(
            lambda xb: K.f32_dot(self._gram_fn(xb, self.x_full), v_full),
            blocks)
        return out.reshape(-1)[:self.n]

    def decide(self, z, coef, b=0.0):
        # per-shard partial over local columns, then one psum
        part = super().decide(z, coef, 0.0)
        return jax.lax.psum(part, self.axis) + b

    def full(self):
        raise RuntimeError(
            "ShardedKernelEngine has no global Gram; row()/matvec() "
            "return local slices of the sharded sample axis")


_BACKENDS = {
    "dense": DenseKernelEngine,
    "chunked": ChunkedKernelEngine,
    "pallas": PallasKernelEngine,
    "sharded": ShardedKernelEngine,
}

# low-rank approximation backends resolve lazily (repro.core.approx
# imports this module for the base class / EngineConfig)
LOWRANK_BACKENDS = ("nystrom", "rff")


def make_engine(x: jax.Array, kernel: K.KernelParams,
                cfg: EngineConfig | str = EngineConfig(), *,
                gram: Optional[jax.Array] = None,
                row_fn: Optional[Callable] = None) -> KernelEngine:
    """Resolve an EngineConfig (or backend name) into a bound engine.

    ``gram``/``row_fn`` are the deprecation shims for the old keyword
    plumbing: a provided Gram forces the dense backend, a provided row
    function forces chunked.
    """
    if isinstance(cfg, str):
        cfg = EngineConfig(backend=cfg)
    backend = cfg.backend
    if gram is not None:
        return DenseKernelEngine(x, kernel, cfg, gram=gram)
    if row_fn is not None:
        return ChunkedKernelEngine(x, kernel, cfg, row_fn=row_fn)
    if backend == "auto":
        backend = "dense" if x.shape[0] <= cfg.dense_limit else "chunked"
    if backend in LOWRANK_BACKENDS:
        from repro.core.approx import LowRankKernelEngine
        return LowRankKernelEngine(x, kernel, cfg)
    try:
        cls = _BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown engine backend {backend!r}; expected one of "
            f"{sorted([*_BACKENDS, *LOWRANK_BACKENDS])} or 'auto'"
        ) from None
    return cls(x, kernel, cfg)
