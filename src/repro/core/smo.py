"""Parallel binary SMO — the paper's CUDA solver, adapted to TPU/JAX.

The paper (Fig. 3) launches one CUDA thread per training sample so that
every data-parallel stage of SMO runs on the device, and performs
convergence checks "on the host for every set of iterations on the
device". The TPU-native adaptation:

* the per-sample axis is vectorized (VPU lanes / Pallas VMEM tiles)
  instead of SIMT threads;
* working-set selection (the block-reduce argmax in CUDA) is a masked
  max/argmax reduction on the vector unit;
* the host-side convergence check becomes the predicate of a
  ``lax.while_loop`` whose body runs ``check_every`` SMO iterations
  (``lax.fori_loop``), mirroring the paper's device-iterations-between-
  checks structure without host round-trips (free scalar check on-chip).

The algorithm is first-order working-set selection SMO (Keerthi
modification 2, the same family as the GPU SVM implementations the paper
builds on):

  f_i = sum_j alpha_j y_j K_ij - y_i                (optimality gradient)
  I_up  = {i: (y_i=+1, a_i<C) or (y_i=-1, a_i>0)}
  I_low = {i: (y_i=+1, a_i>0) or (y_i=-1, a_i<C)}
  b_up = min_{I_up} f_i ;  b_low = max_{I_low} f_i
  converged  <=>  b_low <= b_up + 2 tol

Each iteration updates the maximal-violating pair (i_low, i_up) and then
updates the WHOLE f-cache with two kernel rows — the fully data-parallel
"one thread per sample" stage.

All Gram access goes through a ``repro.core.kernel_engine.KernelEngine``
(dense precomputed, chunked on-the-fly with an LRU row cache, or
Pallas-tiled); the old ``gram=`` / ``row_fn=`` / ``use_pallas`` plumbing
survives as deprecation shims that resolve to an engine. With
``cfg.shrink_every > 0`` the solver runs mask-aware adaptive shrinking:
bound-pinned samples outside the violation corridor are frozen out of
selection and f-cache updates, and a final un-shrunk KKT re-check (one
chunked ``engine.matvec``) gates the reported convergence.

Everything is mask-aware so that one ``vmap``/``shard_map`` program can
drive many padded one-vs-one tasks (the MPI layer in ``core.dist``).

``sharded_binary_smo`` is the complementary axis of parallelism: ONE
binary problem data-parallel across the mesh (samples sharded, selection
made globally exact by ``combine_selection`` — the paper's per-rank
block-reduce + MPI_Allreduce), for the single large QP that task
parallelism cannot help with.

The generalized QP core
-----------------------
Classification is just one instance of the box-constrained dual QP

    min_a  1/2 a' Q a + p' a    s.t.  sum_i y_i a_i = 0,  lo <= a <= hi

with Q_ij = y_i y_j K(x_i, x_j) (y is a sign vector, not necessarily a
class label). ``solve_qp`` / ``sharded_solve_qp`` take the explicit spec
``(p, lo, hi)``; ``binary_smo`` is the classification instance
(p = -1, box [0, C]) and ``svr_smo`` the epsilon-SVR instance via the
standard doubled-variable layout (Smola & Schoelkopf; LIBSVM): variables
beta = [alpha; alpha*] over the doubled sample matrix [x; x], signs
s = [+1; -1], linear term p = [eps - y; eps + y], box [0, C]. The
doubled Gram IS the Gram of the doubled sample matrix, so every
``KernelEngine`` backend (dense / chunked / pallas / sharded) and the
whole selection / pair-update / shrinking / sharded-collective machinery
serve regression unchanged. All internal stages work on the optimality
vector f_i = y_i * ((Q a)_i + p_i), which for classification reduces to
the familiar ``sum_j a_j y_j K_ij - y_i``.

``kkt_violation`` is the solver-independent optimality certificate over
the same spec: the smallest max per-sample KKT violation over all
choices of the equality multiplier (== half the (b_low - b_up) gap).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import kernel_engine as KE
from repro.core import kernels as K

_EPS = 1e-8
_BIG = jnp.inf


@dataclasses.dataclass(frozen=True)
class SMOConfig:
    """Solver hyper-parameters (box constraint + stopping rule)."""

    C: float = 1.0
    tol: float = 1e-3
    max_iter: int = 100_000       # hard cap on SMO pair updates
    check_every: int = 32         # device iterations per convergence check
    precompute_gram: bool = True  # legacy shim -> dense/chunked backend
    use_pallas: bool = False      # legacy shim -> pallas backend
    selection: str = "first"      # first (paper) | second (WSS2, beyond-
                                  # paper: maximal-gain partner choice)
    shrink_every: int = 0         # convergence checks between adaptive-
                                  # shrinking passes; 0 disables shrinking
    shrink_slack: float = 1.0     # freeze corridor slack, in units of tol


class SMOResult(NamedTuple):
    alpha: jax.Array      # (n,) Lagrange multipliers
    b: jax.Array          # () bias, decision = sum a_i y_i K(x_i, .) + b
    n_iter: jax.Array     # () pair updates actually applied
    converged: jax.Array  # () bool
    gap: jax.Array        # () final b_low - b_up duality-violation gap
    n_active: jax.Array   # () samples still active at exit (== n valid
                          # when shrinking is off)


class _State(NamedTuple):
    alpha: jax.Array
    f: jax.Array
    n_iter: jax.Array
    b_up: jax.Array
    b_low: jax.Array
    active: jax.Array   # (n,) bool adaptive-shrinking active set
    done: jax.Array     # () bool convergence decided (post un-shrunk check)
    checks: jax.Array   # () int32 outer convergence checks run
    cache: object       # engine row-cache state (None for dense)


def _selection(f, alpha, y, mask, lo, hi):
    """Working-set selection: (b_up, i_up, b_low, i_low).

    This is the reduction stage — CUDA block-reduce in the paper, a masked
    min/argmax on the vector unit here.

    ``lo`` / ``hi`` are the (broadcastable, possibly per-sample) box
    bounds of the QP spec. Membership epsilon is RELATIVE to the box
    width: f32 residues (alpha ~ 1e-8 left over from a clipped update)
    must not count as movable, or the solver can cycle on a box-blocked
    maximal-violating pair forever.
    """
    eps = 1e-6 * (hi - lo)
    pos, neg = y > 0, y <= 0
    not_upper = alpha < hi - eps    # can increase
    not_lower = alpha > lo + eps    # can decrease
    up_mask = mask & ((pos & not_upper) | (neg & not_lower))
    low_mask = mask & ((pos & not_lower) | (neg & not_upper))
    f_up = jnp.where(up_mask, f, _BIG)
    f_low = jnp.where(low_mask, f, -_BIG)
    i_up = jnp.argmin(f_up)
    i_low = jnp.argmax(f_low)
    return f_up[i_up], i_up, f_low[i_low], i_low


def _pair_update(a_i, a_j, y_i, y_j, f_i, f_j, k_ii, k_jj, k_ij,
                 lo_i, hi_i, lo_j, hi_j):
    """Scalar two-multiplier update for the working pair (i, j).

    Unconstrained Newton step on a_j along the pair's violation
    (f_i - f_j == b_low - b_up under first-order selection), clipped to
    the segment the equality constraint cuts out of the box
    [lo_i, hi_i] x [lo_j, hi_j], with exact-bound snapping: f32 residues
    near the bounds would otherwise keep dead multipliers inside
    I_up/I_low and stall working-set selection. Shared verbatim by the
    single-device and sharded iterations — this is what keeps their
    numerics identical. (At the classification box [0, C] every
    expression below reduces bit-for-bit to the pre-QP-spec form.)
    """
    eta = jnp.maximum(k_ii + k_jj - 2.0 * k_ij, 1e-12)
    a_j_new = a_j + y_j * (f_i - f_j) / eta
    same = y_i == y_j
    # same sign: a_i + a_j is conserved; opposite: a_j - a_i is conserved
    lo_seg = jnp.where(same, jnp.maximum(lo_j, a_i + a_j - hi_i),
                       jnp.maximum(lo_j, lo_i + a_j - a_i))
    hi_seg = jnp.where(same, jnp.minimum(hi_j, a_i + a_j - lo_i),
                       jnp.minimum(hi_j, hi_i + a_j - a_i))
    a_j_new = jnp.clip(a_j_new, lo_seg, hi_seg)
    a_i_new = a_i + y_i * y_j * (a_j - a_j_new)

    snap_i = 1e-6 * (hi_i - lo_i)
    snap_j = 1e-6 * (hi_j - lo_j)
    a_j_new = jnp.where(a_j_new < lo_j + snap_j, lo_j,
                        jnp.where(a_j_new > hi_j - snap_j, hi_j, a_j_new))
    a_i_new = jnp.where(a_i_new < lo_i + snap_i, lo_i,
                        jnp.where(a_i_new > hi_i - snap_i, hi_i, a_i_new))
    return a_i_new, a_j_new


def _shrink_active(f, alpha, y, mask, b_up, b_low, lo, hi, cfg: SMOConfig):
    """Samples that may still join a violating pair (LIBSVM-style).

    Freeze i when alpha_i is pinned at a bound AND its f lies beyond the
    current [b_up, b_low] corridor on its non-violating side (slack in
    units of tol): an I_up-only member with f > b_low has no I_low
    partner to violate with (it is KEPT while f <= b_low + slack), and
    symmetrically an I_low-only member is frozen once f < b_up - slack.
    Free (lo < a < hi) samples are in both index sets and never frozen.
    """
    eps = 1e-6 * (hi - lo)
    slack = cfg.shrink_slack * cfg.tol
    pos, neg = y > 0, y <= 0
    not_upper = alpha < hi - eps
    not_lower = alpha > lo + eps
    in_up = (pos & not_upper) | (neg & not_lower)
    in_low = (pos & not_lower) | (neg & not_upper)
    free = not_upper & not_lower
    keep_up = in_up & (f <= b_low + slack)
    keep_low = in_low & (f >= b_up - slack)
    return mask & (free | keep_up | keep_low)


def kkt_violation(alpha, y, f, lo, hi, tol: float = 0.0, mask=None,
                  r=None):
    """Max per-sample KKT violation of the box QP at ``alpha`` — the
    solver-independent optimality certificate.

    ``f`` is the optimality vector f_i = y_i * ((Q alpha)_i + p_i)
    (recompute it from scratch — e.g. ``K @ (alpha * y) + y * p`` — to
    certify a solver rather than trust its own bookkeeping). KKT with
    equality multiplier r requires f_i >= r on I_up and f_i <= r on
    I_low; the returned scalar is the smallest achievable max violation

        min_r max_i [ (r - f_i)_+ on I_up,  (f_i - r)_+ on I_low ]
          == max(0, (b_low - b_up) / 2)

    so a solve that stopped at duality gap <= 2*tol certifies at <= tol.
    ``tol`` loosens the bound-membership epsilon (as a fraction of the
    box width) for solutions not exactly snapped to their bounds, e.g.
    projected GD; 0 keeps the solver's own 1e-6 relative rule. Returns 0
    when either index set is empty (any r beyond the occupied side
    certifies).

    ``r`` PINS the equality multiplier instead of minimizing over it:
    the violation becomes ``max((r - b_up)_+, (b_low - r)_+)``. This is
    the certificate for box QPs WITHOUT an equality constraint — the
    dual coordinate descent of ``repro.core.linear``, whose
    augmented-bias formulation absorbs the offset into the features, is
    optimal iff the r = 0 conditions hold.
    """
    alpha = jnp.asarray(alpha, jnp.float32)
    f = jnp.asarray(f, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), alpha.shape)
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), alpha.shape)
    if mask is None:
        mask = jnp.ones(alpha.shape, bool)
    eps = jnp.maximum(1e-6, tol) * (hi - lo)
    pos, neg = y > 0, y <= 0
    not_upper = alpha < hi - eps
    not_lower = alpha > lo + eps
    up_mask = mask & ((pos & not_upper) | (neg & not_lower))
    low_mask = mask & ((pos & not_lower) | (neg & not_upper))
    b_up = jnp.min(jnp.where(up_mask, f, _BIG))
    b_low = jnp.max(jnp.where(low_mask, f, -_BIG))
    if r is None:
        return jnp.maximum(0.0, (b_low - b_up) / 2.0)
    r = jnp.float32(r)
    return jnp.maximum(0.0, jnp.maximum(r - b_up, b_low - r))


def _smo_iteration(state: _State, *, y, mask, lo, hi,
                   engine: KE.KernelEngine, cfg: SMOConfig, diag=None,
                   shrink: bool = False):
    """One working-set pair update + f-cache refresh over the active set.

    selection="first": maximal violating pair (the paper's GPU solver).
    selection="second" (WSS2, Fan et al. 2005): i = argmin_{I_up} f, then
    j maximizes the guaranteed objective gain (f_j - f_i)^2 / (2 eta_ij)
    over I_low — pays one already-needed kernel row, typically converges
    in ~2x fewer iterations.
    """
    alpha, f = state.alpha, state.f
    sel_mask = (mask & state.active) if shrink else mask
    b_up, i_up, b_low, i_low = _selection(f, alpha, y, sel_mask, lo, hi)
    step_live = b_low > b_up + 2.0 * cfg.tol  # not yet converged

    j = i_up
    row_j, cache = engine.row(j, state.cache)
    k_jj = row_j[j]

    if cfg.selection == "second":
        # gain_l = (f_l - b_up)^2 / (2 eta_lj) over valid I_low partners
        eps = 1e-6 * (hi - lo)
        pos, neg = y > 0, y <= 0
        low_mask = sel_mask & ((pos & (alpha > lo + eps))
                               | (neg & (alpha < hi - eps)))
        eta_all = jnp.maximum(diag + k_jj - 2.0 * row_j, 1e-12)
        df = f - b_up
        gain = jnp.where(low_mask & (df > 0.0), df * df / eta_all, -jnp.inf)
        i = jnp.argmax(gain)
    else:
        i = i_low

    y_i, y_j = y[i], y[j]
    a_i, a_j = alpha[i], alpha[j]

    row_i, cache = engine.row(i, cache)
    k_ii = row_i[i]
    k_ij = row_i[j]
    a_i_new, a_j_new = _pair_update(a_i, a_j, y_i, y_j, f[i], f[j],
                                    k_ii, k_jj, k_ij,
                                    lo[i], hi[i], lo[j], hi[j])

    d_i = jnp.where(step_live, a_i_new - a_i, 0.0)
    d_j = jnp.where(step_live, a_j_new - a_j, 0.0)

    alpha = alpha.at[i].add(d_i)
    alpha = alpha.at[j].add(d_j)
    # the "one thread per sample" stage: every active sample updates its
    # f entry (shrinking restricts the update to the active set; frozen
    # entries are reconstructed exactly at the un-shrink check). NOTE:
    # the float association (f + a) + b is load-bearing — it must match
    # across vmapped/sequential/sharded dispatch for bit-compatibility.
    if shrink:
        upd = d_i * y_i * row_i + d_j * y_j * row_j
        f = jnp.where(state.active, f + upd, f)
    else:
        f = f + d_i * y_i * row_i + d_j * y_j * row_j

    return state._replace(alpha=alpha,
                          f=f,
                          n_iter=state.n_iter + step_live.astype(jnp.int32),
                          b_up=b_up,
                          b_low=b_low,
                          cache=cache)


def _resolve_engine(x, kernel: K.KernelParams, cfg: SMOConfig,
                    engine, gram, row_fn) -> KE.KernelEngine:
    """Engine resolution incl. the legacy gram=/row_fn=/use_pallas shims."""
    if isinstance(engine, KE.KernelEngine):
        return engine
    if gram is not None or row_fn is not None:
        base = engine if isinstance(engine, KE.EngineConfig) else (
            KE.EngineConfig(backend=engine) if isinstance(engine, str)
            else KE.EngineConfig())
        return KE.make_engine(x, kernel, base, gram=gram, row_fn=row_fn)
    if engine is not None:  # EngineConfig or backend name
        return KE.make_engine(x, kernel, engine)
    # legacy SMOConfig flags
    if cfg.use_pallas and kernel.name == "rbf":
        if cfg.precompute_gram:
            from repro.kernels import ops as pallas_ops
            return KE.DenseKernelEngine(
                x, kernel, gram=pallas_ops.rbf_gram(x, x,
                                                    gamma=kernel.gamma))
        return KE.PallasKernelEngine(x, kernel)
    backend = "dense" if cfg.precompute_gram else "chunked"
    return KE.make_engine(x, kernel, KE.EngineConfig(backend=backend))


def solve_qp(x: jax.Array,
             y: jax.Array,
             p: jax.Array,
             lo: jax.Array | float,
             hi: jax.Array | float,
             mask: Optional[jax.Array] = None,
             *,
             cfg: SMOConfig = SMOConfig(),
             kernel: K.KernelParams = K.KernelParams(),
             engine: Optional[KE.KernelEngine | KE.EngineConfig | str] = None,
             gram: Optional[jax.Array] = None,
             row_fn: Optional[Callable] = None,
             alpha0: Optional[jax.Array] = None) -> SMOResult:
    """Solve the general box-constrained dual QP with parallel SMO:

        min_a 1/2 a'Qa + p'a   s.t. sum_i y_i a_i = 0, lo <= a <= hi

    with Q_ij = y_i y_j K(x_i, x_j). ``binary_smo`` (classification:
    p = -1, box [0, C]) and ``svr_smo`` (epsilon-SVR via the doubled
    layout) are instances; every stage — selection, pair update,
    shrinking, engine-backed Gram access — is shared.

    Args:
      x: (n, d) float training samples.
      y: (n,) sign vector in {+1, -1} (float or int; 0 marks padding).
      p: (n,) linear term of the QP.
      lo / hi: box bounds, scalar or (n,) per-sample arrays.
      mask: (n,) bool validity mask — padded entries are never selected
        and keep alpha = 0 (used by the distributed OvO layer).
      engine: a bound ``KernelEngine``, an ``EngineConfig``, or a backend
        name ("dense" | "chunked" | "pallas" | "auto"). Owns all Gram
        computation.
      gram / row_fn: DEPRECATED shims — precomputed (n, n) Gram (forces
        the dense backend) / row override (forces chunked).
      alpha0: (n,) warm-start multipliers (e.g. a previous cascade
        round's solution). Clipped to the box and zeroed on masked
        entries; the f-cache is reconstructed with one engine matvec.
        The CALLER must keep the equality constraint's initial residue
        ``sum_i y_i alpha0_i`` at ~0: pair updates preserve it, so a
        biased start converges to a biased "optimum". None keeps the
        cold alpha = 0 start (bit-identical to the pre-warm-start
        solver).
    """
    n = x.shape[0]
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    p = jnp.asarray(p, jnp.float32)
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (n,))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (n,))
    # the solver starts at alpha = 0 (f0 = y*p is only the gradient
    # there), so 0 must be inside the box; a box excluding 0 would
    # silently return an infeasible "optimum". Validate whenever the
    # bounds are concrete (they are for every shipped spec, even under
    # jit — constants created inside a trace stay concrete).
    if not (isinstance(lo, jax.core.Tracer)
            or isinstance(hi, jax.core.Tracer)):
        if bool(jnp.any((lo > 0.0) | (hi < 0.0))):
            raise ValueError(
                "solve_qp initializes alpha = 0, which must be feasible: "
                "need lo <= 0 <= hi elementwise (shift the variables to "
                "move the box)")
    if mask is None:
        mask = jnp.ones((n,), dtype=bool)
    mask = mask & (jnp.abs(y) > 0.5)  # padded signs may be 0

    eng = _resolve_engine(x, kernel, cfg, engine, gram, row_fn)
    shrink = cfg.shrink_every > 0

    if alpha0 is None:
        a0 = jnp.zeros((n,), jnp.float32)
        f0 = y * p  # alpha = 0  =>  f_i = y_i p_i (classification: -y_i)
    else:
        a0 = jnp.clip(jnp.asarray(alpha0, jnp.float32), lo, hi) * mask
        f0 = eng.matvec(a0 * y) + y * p
    state0 = _State(alpha=a0, f=f0,
                    n_iter=jnp.zeros((), jnp.int32),
                    b_up=jnp.asarray(-1.0, jnp.float32),
                    b_low=jnp.asarray(1.0, jnp.float32),
                    active=mask,
                    done=jnp.asarray(False),
                    checks=jnp.zeros((), jnp.int32),
                    cache=eng.init_cache())

    diag = eng.diag() if cfg.selection == "second" else None
    iteration = partial(_smo_iteration, y=y, mask=mask, lo=lo, hi=hi,
                        engine=eng, cfg=cfg, diag=diag, shrink=shrink)

    def cond(state: _State):
        return (~state.done) & (state.n_iter < cfg.max_iter)

    def body(state: _State):
        # paper Fig. 3: run `check_every` device iterations between checks
        state = jax.lax.fori_loop(0, cfg.check_every,
                                  lambda _, s: iteration(s), state)
        conv_active = state.b_low <= state.b_up + 2.0 * cfg.tol
        if not shrink:
            return state._replace(done=conv_active)
        state = state._replace(checks=state.checks + 1)

        def unshrink(s: _State):
            # exact gradient for ALL samples via one chunked matvec, then
            # the un-shrunk KKT re-check; resume on the full set if the
            # shrunk optimum does not survive it
            f_full = eng.matvec(s.alpha * y) + y * p
            b_up, _, b_low, _ = _selection(f_full, s.alpha, y, mask,
                                           lo, hi)
            return s._replace(f=f_full, active=mask,
                              done=b_low <= b_up + 2.0 * cfg.tol,
                              b_up=b_up, b_low=b_low)

        def maybe_shrink(s: _State):
            do = (s.checks % cfg.shrink_every) == 0
            shrunk = _shrink_active(s.f, s.alpha, y, mask, s.b_up,
                                    s.b_low, lo, hi, cfg) & s.active
            return s._replace(active=jnp.where(do, shrunk, s.active))

        return jax.lax.cond(conv_active, unshrink, maybe_shrink, state)

    state = jax.lax.while_loop(cond, body, state0)
    # final selection for the reported gap / bias — on the UN-shrunk set
    # (shrinking may leave frozen entries with a stale f if the iteration
    # cap fired mid-phase; reconstruct before reporting)
    f_final = eng.matvec(state.alpha * y) + y * p if shrink else state.f
    b_up, _, b_low, _ = _selection(f_final, state.alpha, y, mask, lo, hi)
    b = -(b_up + b_low) / 2.0
    n_active = jnp.sum((state.active & mask).astype(jnp.int32))
    return SMOResult(alpha=state.alpha * mask, b=b, n_iter=state.n_iter,
                     converged=b_low <= b_up + 2.0 * cfg.tol,
                     gap=b_low - b_up, n_active=n_active)


def _classification_spec(y, c):
    """(p, lo, hi) of the soft-margin classification dual: maximize
    1'a - 1/2 a'Qa over the box [0, C] — i.e. p = -1."""
    n = y.shape[0]
    return (jnp.full((n,), -1.0, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.full((n,), c, jnp.float32))


def _svr_spec(y, epsilon, c):
    """Doubled-variable epsilon-SVR spec over [x; x]: beta = [alpha;
    alpha*], signs s = [+1; -1], p = [eps - y; eps + y], box [0, C].
    The combined regression coefficient is alpha - alpha*."""
    y = jnp.asarray(y, jnp.float32)
    n = y.shape[0]
    s = jnp.concatenate([jnp.ones((n,), jnp.float32),
                         -jnp.ones((n,), jnp.float32)])
    p = jnp.concatenate([epsilon - y, epsilon + y])
    lo = jnp.zeros((2 * n,), jnp.float32)
    hi = jnp.full((2 * n,), c, jnp.float32)
    return s, p, lo, hi


def binary_smo(x: jax.Array,
               y: jax.Array,
               mask: Optional[jax.Array] = None,
               *,
               cfg: SMOConfig = SMOConfig(),
               kernel: K.KernelParams = K.KernelParams(),
               engine: Optional[KE.KernelEngine | KE.EngineConfig | str] = None,
               gram: Optional[jax.Array] = None,
               row_fn: Optional[Callable] = None,
               alpha0: Optional[jax.Array] = None) -> SMOResult:
    """Solve one binary soft-margin SVM dual with parallel SMO — the
    classification instance of ``solve_qp``.

    Args:
      x: (n, d) float training samples.
      y: (n,) labels in {+1, -1} (float or int).
      mask: (n,) bool validity mask — padded entries are never selected and
        keep alpha = 0 (used by the distributed OvO layer).
      engine: a bound ``KernelEngine``, an ``EngineConfig``, or a backend
        name ("dense" | "chunked" | "pallas" | "auto"). Owns all Gram
        computation.
      gram: DEPRECATED shim — precomputed (n, n) Gram; forces the dense
        engine backend.
      row_fn: DEPRECATED shim — ``(X, z) -> K(X, z)`` row override; forces
        the chunked engine backend.
      alpha0: (n,) warm-start multipliers (see ``solve_qp``); None is
        the cold start.
    """
    y = y.astype(jnp.float32)
    p, lo, hi = _classification_spec(y, cfg.C)
    return solve_qp(x, y, p, lo, hi, mask, cfg=cfg, kernel=kernel,
                    engine=engine, gram=gram, row_fn=row_fn,
                    alpha0=alpha0)


class SVRResult(NamedTuple):
    beta: jax.Array       # (n,) alpha - alpha*: K(x_i, .) coefficients
    b: jax.Array          # () bias, prediction = sum_i beta_i K(x_i,.) + b
    alpha: jax.Array      # (2n,) raw doubled multipliers [alpha; alpha*]
    n_iter: jax.Array
    converged: jax.Array
    gap: jax.Array
    n_active: jax.Array


def _svr_result(r: SMOResult, n: int) -> SVRResult:
    return SVRResult(beta=r.alpha[:n] - r.alpha[n:], b=r.b, alpha=r.alpha,
                     n_iter=r.n_iter, converged=r.converged, gap=r.gap,
                     n_active=r.n_active)


def svr_smo(x: jax.Array,
            y: jax.Array,
            mask: Optional[jax.Array] = None,
            *,
            epsilon: float = 0.1,
            cfg: SMOConfig = SMOConfig(),
            kernel: K.KernelParams = K.KernelParams(),
            engine: Optional[KE.EngineConfig | str] = None,
            alpha0: Optional[jax.Array] = None) -> SVRResult:
    """Solve one epsilon-SVR dual with parallel SMO (doubled-variable
    instance of ``solve_qp``; see the module docstring).

    Args:
      x: (n, d) float training samples.
      y: (n,) real-valued targets.
      mask: (n,) bool validity mask, doubled internally.
      epsilon: half-width of the insensitive tube.
      engine: an ``EngineConfig`` or backend name; the engine is built on
        the DOUBLED (2n, d) sample matrix, so a pre-bound (n-row)
        ``KernelEngine`` is rejected.
      alpha0: (2n,) raw doubled warm-start multipliers [alpha; alpha*]
        (the layout of ``SVRResult.alpha``; build one from beta as
        ``[max(beta, 0); max(-beta, 0)]``). See ``solve_qp`` — the
        caller keeps ``sum_i beta0_i ~ 0``.
    """
    if isinstance(engine, KE.KernelEngine):
        raise ValueError(
            "svr_smo solves the doubled 2n-variable QP and must build its "
            "engine on [x; x]; pass an EngineConfig or backend name, not "
            f"a bound engine ({type(engine).__name__})")
    n = x.shape[0]
    x = jnp.asarray(x, jnp.float32)
    s, p, lo, hi = _svr_spec(y, epsilon, cfg.C)
    x2 = jnp.concatenate([x, x], axis=0)
    m2 = None
    if mask is not None:
        m2 = jnp.concatenate([mask, mask])
    r = solve_qp(x2, s, p, lo, hi, m2, cfg=cfg, kernel=kernel,
                 engine=engine, alpha0=alpha0)
    return _svr_result(r, n)


# --------------------------------------------------------------------------
# Sharded single-problem SMO: data-parallel over the SAMPLE axis.
#
# The paper's MPI-CUDA solver is data-parallel WITHIN one QP: every rank
# owns a row block of the Gram matrix, working-set selection is a per-rank
# block-reduce followed by an MPI_Allreduce, and the f-cache update is
# embarrassingly parallel over the rank's samples. The JAX analog below
# shards x / y / alpha / f over a mesh axis via shard_map:
#
#   per-rank block-reduce   ->  masked min/argmin on the LOCAL shard
#   MPI_Allreduce           ->  all_gather of P (value, global index)
#                               pairs + an identical local reduction
#                               (combine_selection) on every shard
#   Gram row block          ->  ShardedKernelEngine.row — x is replicated
#                               (all-gathered once), rows are local compute
#   scalar pair state       ->  one psum of owner-masked picks per step
#
# The combine preserves FIRST-OCCURRENCE argmin/argmax semantics (shards
# are contiguous sample blocks in axis order), so the selected violating
# pair — and therefore the whole optimization trajectory — is bit-for-bit
# the single-device one.
# --------------------------------------------------------------------------
def _combine_min(vals, idxs):
    s = jnp.argmin(vals)
    return vals[s], idxs[s]


def _combine_max(vals, idxs):
    s = jnp.argmax(vals)
    return vals[s], idxs[s]


def combine_selection(b_up_shards, i_up_shards, b_low_shards, i_low_shards):
    """Cross-shard WSS reduction: per-shard extrema (+ GLOBAL argindices),
    ordered by shard, -> global (b_up, i_up, b_low, i_low).

    Bit-exact vs. the unsharded ``_selection``: ``argmin`` over per-shard
    minima picks the FIRST shard attaining the global min, and the local
    ``argmin`` inside that shard picked its first local attainer, so the
    composed index is the first GLOBAL attainer — identical tie-breaking
    to ``jnp.argmin`` over the concatenated array (and symmetrically for
    the max side). This is the correctness-critical collective kernel;
    it is tested in isolation in ``tests/test_sharded_smo.py``.
    """
    b_up, i_up = _combine_min(b_up_shards, i_up_shards)
    b_low, i_low = _combine_max(b_low_shards, i_low_shards)
    return b_up, i_up, b_low, i_low


def _sharded_selection(f, alpha, y, mask, lo, hi, axis):
    """Globally-exact working-set selection from (n_local,) shards.

    One local ``_selection`` + two small all_gathers (P values, P global
    indices per side) + the replicated ``combine_selection`` — the
    MPI_Allreduce stage of the paper's Fig. 3, returning GLOBAL indices.
    """
    n_local = f.shape[0]
    b_up_l, i_up_l, b_low_l, i_low_l = _selection(f, alpha, y, mask,
                                                  lo, hi)
    base = jax.lax.axis_index(axis) * n_local
    vals = jax.lax.all_gather(jnp.stack([b_up_l, b_low_l]), axis)
    idxs = jax.lax.all_gather(jnp.stack([base + i_up_l, base + i_low_l]),
                              axis)
    return combine_selection(vals[:, 0], idxs[:, 0], vals[:, 1], idxs[:, 1])


def _owner_pick(vec, g, me):
    """Owner-masked entry of a sharded vector at GLOBAL index g: the
    owner shard contributes its value, everyone else 0 — summing the
    picks across shards (one stacked psum) replicates the scalar."""
    n_local = vec.shape[0]
    return jnp.where((g // n_local) == me, vec[g % n_local], 0.0)


def _sharded_smo_iteration(state: _State, *, y, mask, lo, hi,
                           engine: KE.ShardedKernelEngine, cfg: SMOConfig,
                           diag=None, shrink: bool = False):
    """One pair update with all per-sample state sharded over engine.axis.

    Mirrors ``_smo_iteration`` stage for stage; every divergence is a
    collective: selection all-gathers per-shard extrema, the pair's
    scalars (f, alpha, y, box bounds, kernel entries at i and j) arrive
    via ONE stacked psum of owner-masked picks, and the f-cache update
    applies the shared ``_pair_update`` deltas to the local slice of the
    two kernel rows.
    """
    axis = engine.axis
    alpha, f = state.alpha, state.f
    me = jax.lax.axis_index(axis)
    n_local = y.shape[0]
    sel_mask = (mask & state.active) if shrink else mask
    b_up, i_up, b_low, i_low = _sharded_selection(f, alpha, y, sel_mask,
                                                  lo, hi, axis)
    step_live = b_low > b_up + 2.0 * cfg.tol

    j = i_up  # global index
    row_j, cache = engine.row(j, state.cache)
    k_jj = jax.lax.psum(_owner_pick(row_j, j, me), axis)

    if cfg.selection == "second":
        # local gain block-reduce + the same first-occurrence combine
        eps = 1e-6 * (hi - lo)
        pos, neg = y > 0, y <= 0
        low_mask = sel_mask & ((pos & (alpha > lo + eps))
                               | (neg & (alpha < hi - eps)))
        eta_all = jnp.maximum(diag + k_jj - 2.0 * row_j, 1e-12)
        df = f - b_up
        gain = jnp.where(low_mask & (df > 0.0), df * df / eta_all, -jnp.inf)
        li = jnp.argmax(gain)
        _, i = _combine_max(jax.lax.all_gather(gain[li], axis),
                            jax.lax.all_gather(me * n_local + li, axis))
    else:
        i = i_low

    row_i, cache = engine.row(i, cache)
    # every scalar the update needs, in one collective
    picks = jnp.stack([
        _owner_pick(f, i, me), _owner_pick(f, j, me),
        _owner_pick(alpha, i, me), _owner_pick(alpha, j, me),
        _owner_pick(y, i, me), _owner_pick(y, j, me),
        _owner_pick(row_i, i, me), _owner_pick(row_i, j, me),
        _owner_pick(lo, i, me), _owner_pick(hi, i, me),
        _owner_pick(lo, j, me), _owner_pick(hi, j, me),
    ])
    (f_i, f_j, a_i, a_j, y_i, y_j, k_ii, k_ij,
     lo_i, hi_i, lo_j, hi_j) = jax.lax.psum(picks, axis)
    a_i_new, a_j_new = _pair_update(a_i, a_j, y_i, y_j, f_i, f_j,
                                    k_ii, k_jj, k_ij,
                                    lo_i, hi_i, lo_j, hi_j)

    d_i = jnp.where(step_live, a_i_new - a_i, 0.0)
    d_j = jnp.where(step_live, a_j_new - a_j, 0.0)

    alpha = alpha.at[i % n_local].add(
        jnp.where((i // n_local) == me, d_i, 0.0))
    alpha = alpha.at[j % n_local].add(
        jnp.where((j // n_local) == me, d_j, 0.0))
    # the "one thread per sample" stage, on this shard's samples only;
    # float association matches _smo_iteration branch for branch
    if shrink:
        upd = d_i * y_i * row_i + d_j * y_j * row_j
        f = jnp.where(state.active, f + upd, f)
    else:
        f = f + d_i * y_i * row_i + d_j * y_j * row_j

    return state._replace(alpha=alpha,
                          f=f,
                          n_iter=state.n_iter + step_live.astype(jnp.int32),
                          b_up=b_up,
                          b_low=b_low,
                          cache=cache)


def _sharded_smo_solve(x, y, p, lo, hi, mask, *, cfg: SMOConfig,
                       kernel: K.KernelParams, ecfg: KE.EngineConfig):
    """shard_map body: ``solve_qp`` with (n_local,) shards of
    x/y/p/lo/hi/mask.

    Scalars (b, n_iter, converged, gap, n_active) come out replicated;
    alpha comes out sharded. Structured like ``solve_qp`` — same
    while/fori convergence loop, same shrinking state machine — with the
    sharded iteration/selection and a psum'd n_active.
    """
    axis = ecfg.shard_axis
    y = y.astype(jnp.float32)
    mask = mask & (jnp.abs(y) > 0.5)  # padded signs are 0

    eng = KE.ShardedKernelEngine(x.astype(jnp.float32), kernel, ecfg)
    shrink = cfg.shrink_every > 0
    n_local = y.shape[0]

    f0 = y * p
    state0 = _State(alpha=jnp.zeros((n_local,), jnp.float32), f=f0,
                    n_iter=jnp.zeros((), jnp.int32),
                    b_up=jnp.asarray(-1.0, jnp.float32),
                    b_low=jnp.asarray(1.0, jnp.float32),
                    active=mask,
                    done=jnp.asarray(False),
                    checks=jnp.zeros((), jnp.int32),
                    cache=eng.init_cache())

    diag = eng.diag() if cfg.selection == "second" else None
    iteration = partial(_sharded_smo_iteration, y=y, mask=mask, lo=lo,
                        hi=hi, engine=eng, cfg=cfg, diag=diag,
                        shrink=shrink)

    def cond(state: _State):
        return (~state.done) & (state.n_iter < cfg.max_iter)

    def body(state: _State):
        state = jax.lax.fori_loop(0, cfg.check_every,
                                  lambda _, s: iteration(s), state)
        # b_up/b_low are replicated, so every shard takes the same branch
        conv_active = state.b_low <= state.b_up + 2.0 * cfg.tol
        if not shrink:
            return state._replace(done=conv_active)
        state = state._replace(checks=state.checks + 1)

        def unshrink(s: _State):
            f_full = eng.matvec(s.alpha * y) + y * p
            b_up, _, b_low, _ = _sharded_selection(f_full, s.alpha, y,
                                                   mask, lo, hi, axis)
            return s._replace(f=f_full, active=mask,
                              done=b_low <= b_up + 2.0 * cfg.tol,
                              b_up=b_up, b_low=b_low)

        def maybe_shrink(s: _State):
            do = (s.checks % cfg.shrink_every) == 0
            shrunk = _shrink_active(s.f, s.alpha, y, mask, s.b_up,
                                    s.b_low, lo, hi, cfg) & s.active
            return s._replace(active=jnp.where(do, shrunk, s.active))

        return jax.lax.cond(conv_active, unshrink, maybe_shrink, state)

    state = jax.lax.while_loop(cond, body, state0)
    f_final = eng.matvec(state.alpha * y) + y * p if shrink else state.f
    b_up, _, b_low, _ = _sharded_selection(f_final, state.alpha, y, mask,
                                           lo, hi, axis)
    b = -(b_up + b_low) / 2.0
    n_active = jax.lax.psum(
        jnp.sum((state.active & mask).astype(jnp.int32)), axis)
    return SMOResult(alpha=state.alpha * mask, b=b, n_iter=state.n_iter,
                     converged=b_low <= b_up + 2.0 * cfg.tol,
                     gap=b_low - b_up, n_active=n_active)


@lru_cache(maxsize=64)
def _sharded_smo_program(mesh: Mesh, axis: str, cfg: SMOConfig,
                         kernel: K.KernelParams, ecfg: KE.EngineConfig):
    """Jitted shard_map program, cached per (mesh, configs): rebuilding
    the wrapper per call would retrace on every solve (jit keys its cache
    on the callable object)."""
    body = partial(_sharded_smo_solve, cfg=cfg, kernel=kernel, ecfg=ecfg)
    spec, rep = P(axis), P()
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6,
        out_specs=SMOResult(spec, rep, rep, rep, rep, rep),
        check_vma=False))


def _resolve_sharded_cfg(engine, axis: str) -> KE.EngineConfig:
    if engine is None:
        return KE.EngineConfig(backend="sharded", shard_axis=axis)
    if isinstance(engine, str):
        engine = KE.EngineConfig(backend=engine)
    if isinstance(engine, KE.EngineConfig):
        # keep the tuning knobs (chunk, cache_slots, ...); the backend is
        # necessarily "sharded" inside the shard_map body
        return dataclasses.replace(engine, backend="sharded",
                                   shard_axis=axis)
    raise ValueError(
        "sharded_binary_smo builds its engine inside the shard_map body; "
        "pass an EngineConfig or backend name, not a bound engine "
        f"({type(engine).__name__})")


def sharded_solve_qp(x: jax.Array,
                     y: jax.Array,
                     p: jax.Array,
                     lo: jax.Array | float,
                     hi: jax.Array | float,
                     mask: Optional[jax.Array] = None,
                     *,
                     mesh: Mesh,
                     axis: str = "shards",
                     cfg: SMOConfig = SMOConfig(),
                     kernel: K.KernelParams = K.KernelParams(),
                     engine: Optional[KE.EngineConfig | str] = None
                     ) -> SMOResult:
    """Solve ONE box-constrained dual QP (the ``solve_qp`` problem) with
    the sample axis sharded over ``mesh.shape[axis]`` devices — the
    paper's data-parallel-within-one-QP MPI-CUDA configuration, for
    problems a single device can't hold (or can't hold fast enough).

    x / y / p / lo / hi / mask / alpha / f are sharded as equal
    contiguous blocks (n is zero-padded to a multiple of the shard
    count; padded rows are masked out and their alphas are identically
    0). Working-set selection is globally exact: the cross-shard
    reduction (``combine_selection``) is bit-identical to the unsharded
    argmin/argmax, so any divergence from single-device ``solve_qp``
    comes only from compiler-level float contraction differences in the
    Gram rows (the SPMD partitioner may fuse dots differently). In
    practice that means the SOLUTION matches — same support set,
    |delta b| well under tol, identical predictions (enforced by
    tests/test_sharded_smo.py) — while the iteration-by-iteration
    trajectory can occasionally differ by a few pair updates on its way
    to the same optimum.

    Scalar-jit semantics apply per shard: adaptive shrinking
    (``cfg.shrink_every``) and the LRU row cache both work here, unlike
    the vmapped task-parallel path.

    Returns a host-layout SMOResult with alpha trimmed back to (n,).
    """
    n = x.shape[0]
    n_shards = int(mesh.shape[axis])
    pad = (-n) % n_shards
    x = jnp.pad(jnp.asarray(x, jnp.float32), ((0, pad), (0, 0)))
    y = jnp.pad(jnp.asarray(y, jnp.float32), ((0, pad),))
    p = jnp.pad(jnp.asarray(p, jnp.float32), ((0, pad),))
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (n,))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (n,))
    if bool(jnp.any((lo > 0.0) | (hi < 0.0))):  # see solve_qp
        raise ValueError(
            "sharded_solve_qp initializes alpha = 0, which must be "
            "feasible: need lo <= 0 <= hi elementwise")
    lo = jnp.pad(lo, ((0, pad),))
    hi = jnp.pad(hi, ((0, pad),))
    m = (jnp.ones((n,), bool) if mask is None
         else jnp.asarray(mask, bool))
    m = jnp.pad(m, ((0, pad),))
    ecfg = _resolve_sharded_cfg(engine, axis)
    fit = _sharded_smo_program(mesh, axis, cfg, kernel, ecfg)
    r = fit(x, y, p, lo, hi, m)
    # the unpadded slice of a sample-sharded array is not a whole number
    # of shards: ask for it replicated (an Explicit-axis mesh refuses a
    # plain alpha[:n] here)
    return r._replace(alpha=r.alpha.at[:n].get(
        out_sharding=NamedSharding(mesh, P())))


def sharded_binary_smo(x: jax.Array,
                       y: jax.Array,
                       mask: Optional[jax.Array] = None,
                       *,
                       mesh: Mesh,
                       axis: str = "shards",
                       cfg: SMOConfig = SMOConfig(),
                       kernel: K.KernelParams = K.KernelParams(),
                       engine: Optional[KE.EngineConfig | str] = None
                       ) -> SMOResult:
    """Solve ONE binary SVM dual data-parallel over the mesh — the
    classification instance of ``sharded_solve_qp`` (see there for the
    sharding layout and exactness guarantees)."""
    y = jnp.asarray(y, jnp.float32)
    p, lo, hi = _classification_spec(y, cfg.C)
    return sharded_solve_qp(x, y, p, lo, hi, mask, mesh=mesh, axis=axis,
                            cfg=cfg, kernel=kernel, engine=engine)


def sharded_svr_smo(x: jax.Array,
                    y: jax.Array,
                    mask: Optional[jax.Array] = None,
                    *,
                    epsilon: float = 0.1,
                    mesh: Mesh,
                    axis: str = "shards",
                    cfg: SMOConfig = SMOConfig(),
                    kernel: K.KernelParams = K.KernelParams(),
                    engine: Optional[KE.EngineConfig | str] = None
                    ) -> SVRResult:
    """Solve ONE epsilon-SVR dual data-parallel over the mesh: the
    doubled 2n-variable QP of ``svr_smo`` through ``sharded_solve_qp``
    (the doubled sample axis is what gets sharded, so alpha and alpha*
    of the same sample may live on different shards — the collective
    machinery is index-agnostic and the selection stays globally
    exact)."""
    n = x.shape[0]
    x = jnp.asarray(x, jnp.float32)
    s, p, lo, hi = _svr_spec(y, epsilon, cfg.C)
    x2 = jnp.concatenate([x, x], axis=0)
    m2 = None
    if mask is not None:
        m2 = jnp.concatenate([mask, mask])
    r = sharded_solve_qp(x2, s, p, lo, hi, m2, mesh=mesh, axis=axis,
                         cfg=cfg, kernel=kernel, engine=engine)
    return _svr_result(r, n)


def decision_function(x_train, y_train, alpha, b, x_test, *,
                      kernel: K.KernelParams = K.KernelParams(),
                      gram_fn: Optional[Callable] = None,
                      engine: Optional[KE.KernelEngine | KE.EngineConfig | str]
                      = None) -> jax.Array:
    """f(z) = sum_i alpha_i y_i K(x_i, z) + b for each test row z.

    With ``engine`` the evaluation streams over test-row chunks through
    ``engine.decide`` (never materializing the (n_test, n_train) block
    for chunked backends); otherwise the legacy full cross-Gram path.
    """
    coef = alpha * y_train.astype(jnp.float32)
    if engine is not None:
        if not isinstance(engine, KE.KernelEngine):
            engine = KE.make_engine(
                jnp.asarray(x_train, jnp.float32), kernel, engine)
        return engine.decide(x_test, coef, b)
    if gram_fn is None:
        gram_fn = K.make_gram_fn(kernel)
    kmat = gram_fn(x_test.astype(jnp.float32), x_train.astype(jnp.float32))
    return K.f32_dot(kmat, coef) + b


def dual_objective(y, alpha, gram) -> jax.Array:
    """W(alpha) = 1'a - 1/2 a' (yy' * K) a — maximized by the dual SVM."""
    ay = alpha * y
    return jnp.sum(alpha) - 0.5 * ay @ (gram @ ay)


def qp_objective(alpha, y, p, gram) -> jax.Array:
    """W(a) = -(1/2 (ya)'K(ya) + p'a) — the maximized dual objective of
    the general box QP (``dual_objective`` is the p = -1 instance; for
    the SVR doubled layout pass the (2n, 2n) Gram of [x; x], i.e. K
    tiled 2x2)."""
    ay = alpha * y
    return -(0.5 * ay @ (gram @ ay) + p @ alpha)
