"""Linear-path dual coordinate descent — the O(n·k) solver behind the
approximate-kernel tier.

Once a kernel problem has an explicit feature map ``Φ ∈ (n, k)``
(Nyström landmarks or random Fourier features, ``repro.core.approx``),
the kernel QP becomes a LINEAR SVM in feature space and the per-pair
SMO machinery — O(n) f-cache updates per iteration, iteration counts
that grow with n — is the wrong tool. This module implements the
LIBLINEAR dual coordinate descent of Hsieh et al. (2008): sweep the
dual variables cyclically, and for each coordinate apply the exact
box-clipped Newton step

    beta_i <- clip(beta_i - g_i / Q_ii, lo_i, hi_i),
    g_i = y_i (phibar_i . w) + p_i,   w = PhiBar^T (y * beta)

maintaining the primal image ``w`` incrementally (O(k) per coordinate,
O(n k) per epoch, O(n + k) solver state beyond Φ itself — never any
(n, n) object). The bias is the classic augmented constant feature
``phibar_i = [phi_i, bias]``, which drops the equality constraint from
the dual — exactly the no-offset box QP whose optimality the
``smo.kkt_violation`` certificate checks with the multiplier pinned at
``r = 0``.

Stopping follows LIBLINEAR: the maximum projected gradient over a full
epoch. The loop exits at ``viol <= tol / 2`` so the REPORTED solution
(whose coordinates moved after their gradient was measured) still
certifies at ``kkt_violation(..., r=0) <= tol`` — the convention the
KKT-certificate tests pin for both backends, SVC and SVR.

Both entry points mirror the SMO QP specs (``smo._classification_spec``
/ ``smo._svr_spec``): ``linear_svc`` is the hinge-loss dual (p = -1,
box [0, C]); ``linear_svr`` solves the epsilon-insensitive dual as the
doubled-variable QP over ``[Φ; Φ]`` with signs [+1; -1] — the same
doubling the kernel path uses, so beta = alpha - alpha* and the
certificate harness needs no regression-specific code.

Everything is jit-safe (``lax.while_loop`` over ``lax.fori_loop``);
``fit_linear_svc`` / ``fit_linear_svr`` are the jitted, config-cached
wrappers ``SVC`` / ``SVR`` call (cf. ``svm._jitted_binary_fit``).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import kernels as K


@dataclasses.dataclass(frozen=True)
class DCDConfig:
    """Static DCD solver config — hashable, safe to close over jit.

    C:          box constraint (upper bound of every dual variable).
    tol:        certificate tolerance: the solve stops once the max
                projected gradient over an epoch is <= tol / 2, which
                certifies ``kkt_violation(..., r=0) <= tol``.
    max_epochs: full passes over the n dual coordinates.
    bias:       augmented constant-feature value (the bias enters the
                model as ``bias * w_bias``); 0 disables the intercept.
    """

    C: float = 1.0
    tol: float = 1e-3
    max_epochs: int = 1000
    bias: float = 1.0


class DCDResult(NamedTuple):
    alpha: jax.Array      # (n,) dual variables at the box optimum
    w: jax.Array          # (k,) primal weights  Phi^T (y * alpha)
    b: jax.Array          # ()   intercept  bias * w_bias
    n_iter: jax.Array     # ()   epochs run
    converged: jax.Array  # ()   bool: viol <= tol/2 before max_epochs
    gap: jax.Array        # ()   last epoch's max projected gradient


def dcd_qp(phi: jax.Array, y: jax.Array, p: jax.Array,
           lo: jax.Array, hi: jax.Array,
           mask: Optional[jax.Array] = None, *,
           cfg: DCDConfig = DCDConfig(),
           alpha0: Optional[jax.Array] = None) -> DCDResult:
    """Minimize ``1/2 beta^T Qbar beta + p^T (y-signed terms)`` over the
    box ``lo <= beta <= hi`` where ``Qbar_ij = y_i y_j (phi_i.phi_j +
    bias^2)`` — generic spec-driven form shared by SVC and SVR (module
    docstring). ``mask=False`` coordinates are frozen at their initial
    value (0) and excluded from the stopping criterion. ``alpha0`` warm
    starts the sweep (clipped to the box, zeroed on masked coordinates);
    the augmented-bias dual has no equality constraint, so any
    box-feasible start is admissible — None keeps the cold beta = 0
    start bit-identical to the pre-warm-start solver."""
    phi = jnp.asarray(phi, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    p = jnp.broadcast_to(jnp.asarray(p, jnp.float32), y.shape)
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), y.shape)
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), y.shape)
    n, k = phi.shape
    live = (jnp.ones((n,), bool) if mask is None
            else jnp.asarray(mask, bool))
    bias = jnp.float32(cfg.bias)
    stop = 0.5 * cfg.tol
    # deterministic per-epoch coordinate shuffles (the LIBLINEAR trick:
    # cyclic order couples badly with correlated features — low-rank Φ
    # columns ARE correlated — and can slow convergence by orders of
    # magnitude); a fixed key keeps refits bit-identical
    key = jax.random.PRNGKey(0)

    # per-coordinate curvature Qbar_ii (y_i^2 = 1); the floor guards
    # all-zero feature rows (a padded sample) from a 0/0 Newton step
    q_diag = jnp.maximum(jnp.sum(phi * phi, axis=1) + bias * bias, 1e-12)
    ys = jnp.where(live, y, 0.0)

    def exact_w(beta):
        # O(n k) matmul refresh of the incremental primal image: bounds
        # the f32 drift of n accumulated rank-1 updates to one epoch, so
        # the measured projected gradient IS the certificate quantity
        coef = ys * beta
        return K.f32_dot(phi.T, coef), jnp.sum(coef)

    def coord(t, carry):
        beta, w, wb, viol, perm = carry
        i = perm[t]
        phi_i = phi[i]
        g = y[i] * (K.f32_dot(phi_i, w) + bias * wb) + p[i]
        # projected gradient: the certificate quantity at this coordinate
        at_lo = beta[i] <= lo[i]
        at_hi = beta[i] >= hi[i]
        pg = jnp.where(at_lo, jnp.minimum(g, 0.0),
                       jnp.where(at_hi, jnp.maximum(g, 0.0), g))
        viol = jnp.where(live[i], jnp.maximum(viol, jnp.abs(pg)), viol)
        b_new = jnp.clip(beta[i] - g / q_diag[i], lo[i], hi[i])
        d = jnp.where(live[i], b_new - beta[i], 0.0)
        return (beta.at[i].add(d), w + d * y[i] * phi_i,
                wb + d * y[i] * bias, viol, perm)

    def epoch(state):
        beta, _, _, _, n_ep = state
        w, wsum = exact_w(beta)
        perm = jax.random.permutation(jax.random.fold_in(key, n_ep), n)
        beta, w, wb, viol, _ = jax.lax.fori_loop(
            0, n, coord, (beta, w, wsum, jnp.float32(0.0), perm))
        return beta, w, wb, viol, n_ep + 1

    def keep_going(state):
        _, _, _, viol, n_ep = state
        return (viol > stop) & (n_ep < cfg.max_epochs)

    if alpha0 is None:
        beta0 = jnp.zeros((n,), jnp.float32)
    else:
        # each epoch refreshes (w, wb) from beta via exact_w, so the warm
        # start only needs the clipped multipliers themselves
        beta0 = jnp.clip(jnp.asarray(alpha0, jnp.float32), lo, hi) * live
    init = (beta0, jnp.zeros((k,), jnp.float32),
            jnp.float32(0.0), jnp.float32(jnp.inf), jnp.int32(0))
    beta, _, _, viol, n_ep = jax.lax.while_loop(keep_going, epoch, init)
    w, wsum = exact_w(beta)   # the served/certified state, drift-free
    return DCDResult(alpha=beta, w=w, b=bias * wsum, n_iter=n_ep,
                     converged=viol <= stop, gap=viol)


def linear_svc(phi: jax.Array, y: jax.Array, *,
               cfg: DCDConfig = DCDConfig(),
               mask: Optional[jax.Array] = None,
               alpha0: Optional[jax.Array] = None) -> DCDResult:
    """Hinge-loss dual on explicit features: p = -1, box [0, C] (the
    linear-space image of ``smo._classification_spec``). ``y`` in
    {-1, +1}; decision f(z) = phi(z) . w + b."""
    n = phi.shape[0]
    return dcd_qp(phi, y, -jnp.ones((n,), jnp.float32),
                  jnp.zeros((n,), jnp.float32),
                  jnp.full((n,), cfg.C, jnp.float32), mask, cfg=cfg,
                  alpha0=alpha0)


class LinearSVRResult(NamedTuple):
    beta: jax.Array       # (n,) alpha - alpha*
    w: jax.Array          # (k,) Phi^T beta
    b: jax.Array          # ()
    alpha: jax.Array      # (2n,) raw doubled variables [alpha; alpha*]
    n_iter: jax.Array
    converged: jax.Array
    gap: jax.Array


def linear_svr(phi: jax.Array, y: jax.Array, *, epsilon: float,
               cfg: DCDConfig = DCDConfig(),
               mask: Optional[jax.Array] = None,
               alpha0: Optional[jax.Array] = None) -> LinearSVRResult:
    """epsilon-insensitive dual as the doubled QP over [Φ; Φ] with signs
    s = [+1; -1] and p = [eps - y; eps + y] (the linear-space image of
    ``smo._svr_spec``); w = Φ^T (alpha - alpha*) falls out of the
    doubling automatically. ``mask`` and ``alpha0`` are per-SAMPLE
    (length n): the mask doubles with the variables; ``alpha0`` is a
    beta = alpha - alpha* warm start split into its canonical doubled
    decomposition ``[max(beta, 0); max(-beta, 0)]``."""
    n = phi.shape[0]
    y = jnp.asarray(y, jnp.float32)
    phi2 = jnp.concatenate([phi, phi], axis=0)
    s = jnp.concatenate([jnp.ones((n,), jnp.float32),
                         -jnp.ones((n,), jnp.float32)])
    p = jnp.concatenate([epsilon - y, epsilon + y])
    m2 = None
    if mask is not None:
        m2 = jnp.concatenate([mask, mask])
    a2 = None
    if alpha0 is not None:
        beta0 = jnp.asarray(alpha0, jnp.float32)
        a2 = jnp.concatenate([jnp.maximum(beta0, 0.0),
                              jnp.maximum(-beta0, 0.0)])
    r = dcd_qp(phi2, s, p, jnp.zeros((2 * n,), jnp.float32),
               jnp.full((2 * n,), cfg.C, jnp.float32), m2, cfg=cfg,
               alpha0=a2)
    beta = r.alpha[:n] - r.alpha[n:]
    return LinearSVRResult(beta=beta, w=r.w, b=r.b, alpha=r.alpha,
                           n_iter=r.n_iter, converged=r.converged,
                           gap=r.gap)


@lru_cache(maxsize=64)
def fit_linear_svc(cfg: DCDConfig):
    """Jitted classification solve, cached per static config (jit keys
    its cache on the callable — cf. ``svm._jitted_binary_fit``)."""
    return jax.jit(lambda phi, y: linear_svc(phi, y, cfg=cfg))


@lru_cache(maxsize=64)
def fit_linear_svr(epsilon: float, cfg: DCDConfig):
    """Jitted epsilon-SVR solve, cached per static config."""
    return jax.jit(lambda phi, y: linear_svr(phi, y, epsilon=epsilon,
                                             cfg=cfg))
