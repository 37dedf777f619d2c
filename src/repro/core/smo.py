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

One driver, two layouts
-----------------------
``sharded_solve_qp`` is the complementary axis of parallelism: ONE QP
data-parallel across a mesh axis, samples in contiguous blocks, for the
single large problem that task parallelism cannot help with. It runs the
same driver (``_smo_solve``) and the same iteration (``_smo_iteration``)
as ``solve_qp``, inside a ``shard_map`` body. What differs is only where
the per-sample vectors live, and that sits behind a ``_Layout`` seam:
``_LOCAL`` indexes plainly, ``_sharded_layout`` turns each access into
the paper's MPI stage (per-rank block-reduce + all_gather + the
first-occurrence ``combine_selection`` for selection, one stacked psum of
owner-masked picks for the pair's scalars). Shrinking, warm starts, the
stopping rule and the float association of the f-update are therefore
written once. The sharded seam reads ``_sharded_selection`` as a module
global when it is traced; ``bench/control.py`` relies on that to swap in
a selection without the exchange.

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
    I_up/I_low and stall working-set selection. The one iteration calls
    it in both layouts, on replicated scalars — this is what keeps the
    local and sharded numerics identical. (At the classification box
    [0, C] every expression below reduces bit-for-bit to the pre-QP-spec
    form.)
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


class _Layout(NamedTuple):
    """The seam between the one SMO driver and where its per-sample
    vectors live: whole on one device (``_LOCAL``) or in contiguous blocks
    over a mesh axis (``_sharded_layout``). Indices are global; what
    ``select`` / ``argmax`` / ``replicate`` / ``count`` return is the same
    on every shard."""

    select: Callable     # (f, alpha, y, mask, lo, hi) -> _selection's 4
    argmax: Callable     # v -> global index of v's first maximum
    pick: Callable       # (vec, g) -> this device's share of vec[g]
    replicate: Callable  # (*picks) -> the picked scalars
    add: Callable        # (vec, g, d) -> vec with d added at g
    count: Callable      # bool vec -> global count of True


# plain indexing, nothing exchanged
_LOCAL = _Layout(select=_selection, argmax=jnp.argmax,
                 pick=lambda vec, g: vec[g],
                 replicate=lambda *picks: picks,
                 add=lambda vec, g, d: vec.at[g].add(d),
                 count=lambda m: jnp.sum(m.astype(jnp.int32)))


def _smo_iteration(state: _State, *, lay, y, mask, lo, hi,
                   engine: KE.KernelEngine, cfg: SMOConfig, diag=None,
                   shrink: bool = False):
    """One working-set pair update + f-cache refresh over the active set.

    selection="first": maximal violating pair (the paper's GPU solver).
    selection="second" (WSS2, Fan et al. 2005): i = argmin_{I_up} f, then
    j maximizes the guaranteed objective gain (f_j - f_i)^2 / (2 eta_ij)
    over I_low — pays one already-needed kernel row, typically converges
    in ~2x fewer iterations.

    ``lay`` is the ``_Layout`` the per-sample vectors live in.
    """
    alpha, f = state.alpha, state.f
    sel_mask = (mask & state.active) if shrink else mask
    b_up, i_up, b_low, i_low = lay.select(f, alpha, y, sel_mask, lo, hi)
    step_live = b_low > b_up + 2.0 * cfg.tol  # not yet converged

    j = i_up
    row_j, cache = engine.row(j, state.cache)
    (k_jj,) = lay.replicate(lay.pick(row_j, j))

    if cfg.selection == "second":
        # gain_l = (f_l - b_up)^2 / (2 eta_lj) over valid I_low partners
        eps = 1e-6 * (hi - lo)
        pos, neg = y > 0, y <= 0
        low_mask = sel_mask & ((pos & (alpha > lo + eps))
                               | (neg & (alpha < hi - eps)))
        eta_all = jnp.maximum(diag + k_jj - 2.0 * row_j, 1e-12)
        df = f - b_up
        gain = jnp.where(low_mask & (df > 0.0), df * df / eta_all, -jnp.inf)
        i = lay.argmax(gain)
    else:
        i = i_low

    # the pair's scalars: y and alpha are picked before row i is fetched
    # (the order the local program is traced in), and the sharded layout
    # replicates all twelve in one collective
    y_i, y_j = lay.pick(y, i), lay.pick(y, j)
    a_i, a_j = lay.pick(alpha, i), lay.pick(alpha, j)
    row_i, cache = engine.row(i, cache)
    (y_i, y_j, a_i, a_j, k_ii, k_ij, f_i, f_j,
     lo_i, hi_i, lo_j, hi_j) = lay.replicate(
        y_i, y_j, a_i, a_j, lay.pick(row_i, i), lay.pick(row_i, j),
        lay.pick(f, i), lay.pick(f, j), lay.pick(lo, i), lay.pick(hi, i),
        lay.pick(lo, j), lay.pick(hi, j))
    a_i_new, a_j_new = _pair_update(a_i, a_j, y_i, y_j, f_i, f_j,
                                    k_ii, k_jj, k_ij,
                                    lo_i, hi_i, lo_j, hi_j)

    d_i = jnp.where(step_live, a_i_new - a_i, 0.0)
    d_j = jnp.where(step_live, a_j_new - a_j, 0.0)

    alpha = lay.add(alpha, i, d_i)
    alpha = lay.add(alpha, j, d_j)
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


def _smo_solve(eng, lay, y, p, lo, hi, mask, cfg: SMOConfig,
               alpha0=None) -> SMOResult:
    """The SMO driver, shared by ``solve_qp`` and the ``shard_map`` body
    of ``sharded_solve_qp``: state init, the while/fori convergence loop
    with its shrinking state machine, the final selection and the bias.
    ``y`` / ``p`` / ``lo`` / ``hi`` / ``mask`` are float32 (bool) vectors
    in ``lay``'s layout and ``eng`` is an engine over the same rows."""
    shrink = cfg.shrink_every > 0

    if alpha0 is None:
        a0 = jnp.zeros(y.shape, jnp.float32)
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
    iteration = partial(_smo_iteration, lay=lay, y=y, mask=mask, lo=lo,
                        hi=hi, engine=eng, cfg=cfg, diag=diag, shrink=shrink)

    def cond(state: _State):
        return (~state.done) & (state.n_iter < cfg.max_iter)

    def body(state: _State):
        # paper Fig. 3: run `check_every` device iterations between checks
        state = jax.lax.fori_loop(0, cfg.check_every,
                                  lambda _, s: iteration(s), state)
        # b_up/b_low are replicated, so every shard takes the same branch
        conv_active = state.b_low <= state.b_up + 2.0 * cfg.tol
        if not shrink:
            return state._replace(done=conv_active)
        state = state._replace(checks=state.checks + 1)

        def unshrink(s: _State):
            # exact gradient for ALL samples via one chunked matvec, then
            # the un-shrunk KKT re-check; resume on the full set if the
            # shrunk optimum does not survive it
            f_full = eng.matvec(s.alpha * y) + y * p
            b_up, _, b_low, _ = lay.select(f_full, s.alpha, y, mask, lo, hi)
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
    b_up, _, b_low, _ = lay.select(f_final, state.alpha, y, mask, lo, hi)
    b = -(b_up + b_low) / 2.0
    n_active = lay.count(state.active & mask)
    return SMOResult(alpha=state.alpha * mask, b=b, n_iter=state.n_iter,
                     converged=b_low <= b_up + 2.0 * cfg.tol,
                     gap=b_low - b_up, n_active=n_active)


def _check_box(lo, hi, who: str):
    """The solver starts at alpha = 0 (f0 = y*p is only the gradient
    there), so 0 must be inside the box; a box excluding 0 would silently
    return an infeasible "optimum". Validated whenever the bounds are
    concrete (they are for every shipped spec, even under jit — constants
    created inside a trace stay concrete)."""
    if isinstance(lo, jax.core.Tracer) or isinstance(hi, jax.core.Tracer):
        return
    if bool(jnp.any((lo > 0.0) | (hi < 0.0))):
        raise ValueError(
            f"{who} initializes alpha = 0, which must be feasible: need "
            "lo <= 0 <= hi elementwise (shift the variables to move the "
            "box)")


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
    _check_box(lo, hi, "solve_qp")
    if mask is None:
        mask = jnp.ones((n,), dtype=bool)
    mask = mask & (jnp.abs(y) > 0.5)  # padded signs may be 0

    eng = _resolve_engine(x, kernel, cfg, engine, gram, row_fn)
    return _smo_solve(eng, _LOCAL, y, p, lo, hi, mask, cfg, alpha0)


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


def _doubled(x, mask):
    """The doubled epsilon-SVR sample layout: [x; x] and [mask; mask]."""
    return (jnp.concatenate([x, x], axis=0),
            None if mask is None else jnp.concatenate([mask, mask]))


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
    x2, m2 = _doubled(x, mask)
    r = solve_qp(x2, s, p, lo, hi, m2, cfg=cfg, kernel=kernel,
                 engine=engine, alpha0=alpha0)
    return _svr_result(r, n)


# --------------------------------------------------------------------------
# Sharded single-problem SMO: the collectives behind ``_sharded_layout``.
# A shard's block-reduce becomes global through an all_gather of P
# (value, global index) pairs and the same reduction on every shard;
# ``combine_selection`` keeps FIRST-OCCURRENCE argmin/argmax semantics
# (shards are contiguous blocks in axis order), so the selected pair is
# the single-device one. x is all-gathered once (ShardedKernelEngine);
# Gram rows are local compute.
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


def _sharded_layout(axis: str) -> _Layout:
    """The seam inside a ``shard_map`` body whose sample axis is
    ``axis``: selection is the all-gathered block-reduce, a pick is the
    owner's entry (0 elsewhere), and ``replicate`` sums the picks in one
    stacked psum. ``select`` looks ``_sharded_selection`` up as a module
    global when it is traced, never binds it here: ``bench/control.py``'s
    ``no_exchange`` fault swaps that global (then clears
    ``_sharded_smo_program``'s cache) to show that the benchmark refuses
    a fit without the exchange."""
    me = jax.lax.axis_index(axis)

    def argmax(v):  # local block-reduce + the first-occurrence combine
        li = jnp.argmax(v)
        return _combine_max(jax.lax.all_gather(v[li], axis),
                            jax.lax.all_gather(me * v.shape[0] + li,
                                               axis))[1]

    def add(vec, g, d):
        n_local = vec.shape[0]
        return vec.at[g % n_local].add(
            jnp.where((g // n_local) == me, d, 0.0))

    return _Layout(
        select=lambda *a: _sharded_selection(*a, axis),
        argmax=argmax, pick=lambda vec, g: _owner_pick(vec, g, me),
        replicate=lambda *picks: tuple(jax.lax.psum(jnp.stack(picks),
                                                    axis)),
        add=add,
        count=lambda m: jax.lax.psum(jnp.sum(m.astype(jnp.int32)), axis))


@lru_cache(maxsize=64)
def _sharded_smo_program(mesh: Mesh, axis: str, cfg: SMOConfig,
                         kernel: K.KernelParams, ecfg: KE.EngineConfig):
    """Jitted shard_map program, cached per (mesh, configs): rebuilding
    the wrapper per call would retrace on every solve (jit keys its cache
    on the callable object). The body is ``solve_qp``'s driver on
    (n_local,) shards of x/y/p/lo/hi/mask: scalars (b, n_iter,
    converged, gap, n_active) come out replicated, alpha sharded."""
    def sharded_solve(x, y, p, lo, hi, mask):
        y = y.astype(jnp.float32)
        mask = mask & (jnp.abs(y) > 0.5)  # padded signs are 0
        eng = KE.ShardedKernelEngine(x.astype(jnp.float32), kernel, ecfg)
        return _smo_solve(eng, _sharded_layout(axis), y, p, lo, hi,
                          mask, cfg)
    spec, rep = P(axis), P()
    return jax.jit(jax.shard_map(
        sharded_solve, mesh=mesh, in_specs=(spec,) * 6,
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
    problems one device can't hold (or can't hold fast enough).

    The shard_map body runs ``solve_qp``'s own driver through the
    sharded ``_Layout`` (module docstring). Every vector is split into
    equal contiguous blocks (n zero-padded to a multiple of the shard
    count; pad rows are masked and keep alpha = 0). Selection is
    globally exact, so the solution matches single-device ``solve_qp``
    — same support set, |delta b| well under tol, identical predictions
    (tests/test_sharded_smo.py) — while the trajectory can differ by a
    few pair updates where the SPMD partitioner contracts a Gram row's
    dot differently. Shrinking and the LRU row cache work here, unlike
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
    _check_box(lo, hi, "sharded_solve_qp")
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
    x2, m2 = _doubled(x, mask)
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
