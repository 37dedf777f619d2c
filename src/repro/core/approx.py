"""Low-rank kernel approximations: Nyström landmarks + random Fourier
features, behind the KernelEngine interface.

Exact SMO is O(n^2) in Gram work no matter how well the rows are tiled,
cached, or sharded (PRs 1-6); Tyree et al. (*Parallel SVMs in
Practice*) conclude that at scale approximate kernel methods dominate
exact parallel solvers. This module is that tier: both approximations
map the kernel problem to an EXPLICIT feature space ``Φ ∈ (n, k)``
with ``K ≈ Φ Φ^T``, after which training is a linear SVM solved by the
O(n·k) dual coordinate descent in ``repro.core.linear`` — nothing of
size (n, n) is ever materialized.

Nyström (any PSD kernel)
    Pick k landmark rows L (uniform subsample or k-means++ D^2-weighted
    seeding), form ``C = K(X, L)`` and ``W = K(L, L)``, and take
    ``Φ = C · U diag(clip(e)^{-1/2})`` from the eigendecomposition
    ``W = U diag(e) U^T`` — the spectral clip zeroes directions below
    ``e_max * 1e-6`` so a rank-deficient landmark set yields the
    pseudo-inverse map instead of noise blow-up. With landmarks == all
    points, ``Φ Φ^T = K K^+ K = K`` (exactly, up to the clip), the
    approximation-limit identity the tests pin.

RFF (RBF kernel only; Rahimi & Recht 2007)
    ``φ(z) = sqrt(2/k) cos(z Ω + b)`` with ``Ω ~ N(0, 2γ I)`` and
    ``b ~ U[0, 2π)``; ``E[φ(x)·φ(z)] = exp(-γ|x-z|^2)`` with
    O(1/sqrt(k)) Monte-Carlo error. The transform is one (n, d)x(d, k)
    matmul + cos — on TPU it runs through the fused Pallas feature-map
    kernel (``repro.kernels.ops.rff_features``, same tiling/autotune
    machinery as ``rbf_gram``); elsewhere the jnp path is used.

``LowRankKernelEngine`` exposes Φ through every KernelEngine method
(row/block/matvec/cross/decide are O(n k) matmuls against Φ), so the
exact solvers and the KKT-certificate harness run unchanged against the
APPROXIMATE Gram — ``engine="nystrom"|"rff"`` is a drop-in backend.
Note ``diag()`` is the feature-space diagonal ``|φ_i|^2`` (NOT exactly
1 for RBF): the engine represents K̃ = Φ Φ^T faithfully, approximation
error included.

All construction is jit-safe: landmark choice / frequency sampling use
``jax.random`` keyed on ``EngineConfig.seed``, so a fit is exactly
reproducible and an engine may be built on tracers inside a jitted
solver.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import kernel_engine as KE
from repro.core import kernels as K

# spectral clip for the Nyström eigenscale, relative to the largest
# eigenvalue of W: directions below it are dropped (pseudo-inverse)
EIG_CLIP_REL = 1e-6

LANDMARK_METHODS = ("uniform", "kmeans++")


# ---------------------------------------------------------- feature maps
class NystromMap:
    """``φ(z) = K(z, L) · proj`` with ``proj = U diag(clip(e)^{-1/2})``."""

    kind = "nystrom"

    def __init__(self, kernel: K.KernelParams, landmarks: jax.Array,
                 proj: jax.Array, *, gram_dtype: str = "fp32"):
        self.kernel = kernel
        self.landmarks = jnp.asarray(landmarks, jnp.float32)  # (k, d)
        self.proj = jnp.asarray(proj, jnp.float32)            # (k, k)
        self._gram_fn = K.make_gram_fn(kernel, compute_dtype=gram_dtype)

    @property
    def rank(self) -> int:
        return self.proj.shape[1]

    @property
    def n_features(self) -> int:
        return self.landmarks.shape[1]

    @property
    def arrays(self):
        """(a, b) serialization pair — see ``serve.artifact``."""
        return self.landmarks, self.proj

    def transform(self, z: jax.Array) -> jax.Array:
        z = jnp.asarray(z, jnp.float32)
        return K.f32_dot(self._gram_fn(z, self.landmarks), self.proj)


class RFFMap:
    """``φ(z) = sqrt(2/k) cos(z Ω + phase)`` — RBF only.

    ``fused=None`` routes the transform through the Pallas feature-map
    kernel on a TPU (``kernels.ops.on_tpu``) and the jnp reference path
    elsewhere (the Pallas interpreter on CPU is a correctness tool, not
    a fast path); ``True``/``False`` force it either way.
    """

    kind = "rff"

    def __init__(self, kernel: K.KernelParams, omega: jax.Array,
                 phase: jax.Array, *, gram_dtype: str = "fp32",
                 fused: bool | None = None):
        self.kernel = kernel
        self.omega = jnp.asarray(omega, jnp.float32)  # (d, k)
        self.phase = jnp.asarray(phase, jnp.float32)  # (k,)
        self.gram_dtype = gram_dtype
        self.fused = fused

    @property
    def rank(self) -> int:
        return self.omega.shape[1]

    @property
    def n_features(self) -> int:
        return self.omega.shape[0]

    @property
    def arrays(self):
        return self.omega, self.phase

    @property
    def scale(self) -> float:
        return math.sqrt(2.0 / self.rank)

    def transform(self, z: jax.Array) -> jax.Array:
        from repro.kernels import ops
        z = jnp.asarray(z, jnp.float32)
        fused = ops.on_tpu() if self.fused is None else self.fused
        if fused:
            return ops.rff_features(z, self.omega, self.phase,
                                    scale=self.scale,
                                    compute_dtype=self.gram_dtype)
        return self.scale * jnp.cos(K.f32_dot(z, self.omega) + self.phase)


def map_from_arrays(kind: str, kernel: K.KernelParams, a, b,
                    *, gram_dtype: str = "fp32"):
    """Rebuild a feature map from its serialized ``(kind, a, b)`` triple
    (the ``serve.artifact`` low-rank payload)."""
    if kind == "nystrom":
        return NystromMap(kernel, a, b, gram_dtype=gram_dtype)
    if kind == "rff":
        return RFFMap(kernel, a, b, gram_dtype=gram_dtype)
    raise ValueError(f"unknown feature-map kind {kind!r}; "
                     f"expected 'nystrom' or 'rff'")


# ------------------------------------------------------------- landmarks
def _sqdist_to(x: jax.Array, c: jax.Array) -> jax.Array:
    d = x - c[None, :]
    return jnp.sum(d * d, axis=1)


def select_landmarks(x: jax.Array, k: int, method: str,
                     key: jax.Array) -> jax.Array:
    """(k,) landmark row indices: "uniform" subsample or "kmeans++"
    D^2-weighted seeding (each next landmark drawn with probability
    proportional to its squared distance to the chosen set — the
    spread-out seeding that keeps W well-conditioned on clustered
    data). Both are jit-safe."""
    n = x.shape[0]
    if method == "uniform":
        return jax.random.permutation(key, n)[:k]
    if method != "kmeans++":
        raise ValueError(f"unknown landmark method {method!r}; "
                         f"expected one of {LANDMARK_METHODS}")
    k0, kloop = jax.random.split(key)
    i0 = jax.random.randint(k0, (), 0, n)
    idx0 = jnp.zeros((k,), jnp.int32).at[0].set(i0.astype(jnp.int32))
    d0 = _sqdist_to(x, x[i0])

    def body(j, carry):
        idx, d2, kk = carry
        kk, sub = jax.random.split(kk)
        # D^2 sampling via inverse-CDF; an all-zero d2 (k >= #distinct
        # points) degrades to picking the last index — harmless, the
        # spectral clip absorbs duplicate landmarks
        cum = jnp.cumsum(d2)
        u = jax.random.uniform(sub, (), jnp.float32) * cum[-1]
        nxt = jnp.clip(jnp.searchsorted(cum, u), 0, n - 1).astype(jnp.int32)
        idx = idx.at[j].set(nxt)
        return idx, jnp.minimum(d2, _sqdist_to(x, x[nxt])), kk

    idx, _, _ = jax.lax.fori_loop(1, k, body, (idx0, d0, kloop))
    return idx


# ---------------------------------------------------------- construction
def make_feature_map(x: jax.Array, kernel: K.KernelParams,
                     cfg: KE.EngineConfig):
    """Resolve ``EngineConfig(backend="nystrom"|"rff", rank, landmarks,
    seed)`` into a fitted feature map for sample matrix ``x``."""
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    key = jax.random.PRNGKey(cfg.seed)
    if cfg.backend == "rff":
        if kernel.name != "rbf":
            raise ValueError(
                f"engine='rff' approximates the RBF kernel only, got "
                f"kernel={kernel.name!r}; use engine='nystrom' for "
                f"arbitrary PSD kernels")
        kw, kp = jax.random.split(key)
        omega = (math.sqrt(2.0 * kernel.gamma)
                 * jax.random.normal(kw, (d, cfg.rank), jnp.float32))
        phase = jax.random.uniform(kp, (cfg.rank,), jnp.float32,
                                   0.0, 2.0 * math.pi)
        return RFFMap(kernel, omega, phase, gram_dtype=cfg.gram_dtype)
    if cfg.backend != "nystrom":
        raise ValueError(f"make_feature_map: not a low-rank backend "
                         f"{cfg.backend!r}; expected one of "
                         f"{KE.LOWRANK_BACKENDS}")
    k = min(cfg.rank, n)
    idx = select_landmarks(x, k, cfg.landmarks, key)
    landmarks = x[idx]
    gram_fn = K.make_gram_fn(kernel, compute_dtype=cfg.gram_dtype)
    w = gram_fn(landmarks, landmarks)
    e, u = jnp.linalg.eigh(w)
    clip = jnp.maximum(e[-1], 0.0) * EIG_CLIP_REL
    inv_sqrt = jnp.where(e > clip,
                         1.0 / jnp.sqrt(jnp.maximum(e, clip)), 0.0)
    proj = u * inv_sqrt[None, :]
    return NystromMap(kernel, landmarks, proj, gram_dtype=cfg.gram_dtype)


# ---------------------------------------------------------------- engine
class LowRankKernelEngine(KE.KernelEngine):
    """K̃ = Φ Φ^T behind the full KernelEngine interface.

    Every method is an O(n k) (or O(t k)) matmul against the resident
    feature matrix ``Φ (n, k)`` — no (n, n) object exists anywhere, so
    the exact solvers (SMO included) and the KKT-certificate harness
    run unchanged against the approximate Gram. The intended fast path
    for TRAINING is ``repro.core.linear`` directly on ``engine.phi``.
    """

    backend = "lowrank"

    def __init__(self, x, kernel, cfg: KE.EngineConfig = KE.EngineConfig()):
        super().__init__(x, kernel, cfg)
        self.fmap = make_feature_map(self.x, kernel, cfg)
        self.phi = self.fmap.transform(self.x)     # (n, k) resident

    @property
    def rank(self) -> int:
        return self.phi.shape[1]

    def full(self):
        if self.n > self.cfg.dense_limit:
            raise RuntimeError(
                f"LowRankKernelEngine.full(): refusing to materialize a "
                f"({self.n}, {self.n}) approximate Gram (dense_limit="
                f"{self.cfg.dense_limit}); use row()/block()/matvec()")
        return K.f32_dot(self.phi, self.phi.T)

    def diag(self):
        # the APPROXIMATE diagonal |phi_i|^2, not the exact K(x_i, x_i):
        # the engine represents K-tilde faithfully (module docstring)
        return jnp.sum(self.phi * self.phi, axis=1)

    def row(self, i, cache=None):
        return K.f32_dot(self.phi, self.phi[i]), cache

    def block(self, rows, cols):
        return K.f32_dot(self.phi[rows], self.phi[cols].T)

    def cross(self, z):
        return K.f32_dot(self.fmap.transform(z), self.phi.T)

    def matvec(self, v):
        return K.f32_dot(self.phi, K.f32_dot(self.phi.T, v))

    def decide(self, z, coef, b=0.0):
        return K.f32_dot(self.fmap.transform(z),
                         K.f32_dot(self.phi.T, coef)) + b
