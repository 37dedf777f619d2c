"""Kernel (Gram) functions for SVM — pure-jnp reference path.

These are the mathematical kernels K(x, z) used by both solvers. The
performance-critical tiled TPU versions live in ``repro.kernels`` (Pallas);
every Pallas kernel's oracle delegates to the functions here.

All functions take matrices ``A (n, d)`` and ``B (m, d)`` and return the
Gram block ``K (n, m)`` in float32.

"fp32" means near-f32 products on every backend: a TPU contracts f32
operands in a single bf16 pass unless told otherwise (about three
significant digits), so every f32 contraction, here, in the engines and
in the Pallas kernels, names its precision (``f32_dot``,
``xla_precision``, ``pallas_precision``). On a CPU XLA computes in f32
whatever is asked.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Hyper-parameters of the SVM kernel function.

    gamma:  RBF / poly / sigmoid scale. ``gamma <= 0`` means "scale":
            1 / (d * Var[X]) resolved at fit time.
    degree: polynomial degree.
    coef0:  poly / sigmoid offset.
    """

    name: str = "rbf"  # linear | poly | rbf | sigmoid
    gamma: float = 1.0
    degree: int = 3
    coef0: float = 0.0


COMPUTE_DTYPES = ("fp32", "bf16")


# f32 contraction precision, as measured on a TPU v5e against the f64
# KKT certificates and the float64 serving reference (PERF.md): one bf16
# pass (DEFAULT) fails them, in XLA and in Mosaic alike; three passes
# (HIGH) hold them in XLA. Mosaic offers only DEFAULT and HIGHEST.
XLA_F32_PRECISION = jax.lax.Precision.HIGH
PALLAS_F32_PRECISION = jax.lax.Precision.HIGHEST


def xla_precision(dtype):
    """XLA contraction precision for operands of ``dtype``: three bf16
    passes for f32, the native single pass for bf16."""
    return XLA_F32_PRECISION if dtype == jnp.float32 else None


def pallas_precision(dtype):
    """The same for a dot inside a Pallas TPU kernel."""
    return PALLAS_F32_PRECISION if dtype == jnp.float32 else None


def f32_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` of f32 operands in XLA (module docstring)."""
    return jnp.matmul(a, b, precision=XLA_F32_PRECISION)


def _compute_cast(a: jax.Array, b: jax.Array, compute_dtype: str):
    """Round operands to the Gram compute precision. Under "bf16" both
    the dot and the squared norms see the SAME rounded values (the dot
    itself still accumulates in f32 via ``preferred_element_type``), so
    the RBF zero-distance diagonal stays 1 up to f32 summation-order
    rounding (~1e-6) instead of drifting by the full bf16 epsilon."""
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}; "
                         f"expected one of {COMPUTE_DTYPES}")
    if compute_dtype == "bf16":
        return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return a.astype(jnp.float32), b.astype(jnp.float32)


def linear_gram(a: jax.Array, b: jax.Array, *,
                compute_dtype: str = "fp32") -> jax.Array:
    a, b = _compute_cast(a, b, compute_dtype)
    return jnp.dot(a, b.T, precision=xla_precision(a.dtype),
                   preferred_element_type=jnp.float32)


def poly_gram(a: jax.Array, b: jax.Array, *, gamma: float, degree: int,
              coef0: float, compute_dtype: str = "fp32") -> jax.Array:
    return (gamma * linear_gram(a, b, compute_dtype=compute_dtype)
            + coef0) ** degree


def sigmoid_gram(a: jax.Array, b: jax.Array, *, gamma: float,
                 coef0: float, compute_dtype: str = "fp32") -> jax.Array:
    return jnp.tanh(gamma * linear_gram(a, b, compute_dtype=compute_dtype)
                    + coef0)


def sqdist(a: jax.Array, b: jax.Array, *,
           compute_dtype: str = "fp32") -> jax.Array:
    """Pairwise squared Euclidean distances, numerically clamped at 0.

    Norms are accumulated in f32 from the compute-precision values, so
    the ``sqdist(x, x)`` diagonal stays ~0 (f32 rounding, not bf16
    epsilon) under bf16; the clamp removes the negative residues."""
    a, b = _compute_cast(a, b, compute_dtype)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    a2 = jnp.sum(af * af, axis=-1, keepdims=True)        # (n, 1)
    b2 = jnp.sum(bf * bf, axis=-1, keepdims=True).T      # (1, m)
    d2 = a2 + b2 - 2.0 * jnp.dot(a, b.T, precision=xla_precision(a.dtype),
                                 preferred_element_type=jnp.float32)
    return jnp.maximum(d2, 0.0)


def rbf_gram(a: jax.Array, b: jax.Array, *, gamma: float,
             compute_dtype: str = "fp32") -> jax.Array:
    return jnp.exp(-gamma * sqdist(a, b, compute_dtype=compute_dtype))


def make_gram_fn(params: KernelParams, *, compute_dtype: str = "fp32"
                 ) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Resolve a KernelParams into a jit-friendly ``(A, B) -> K`` closure.

    ``compute_dtype`` selects the Gram operand precision ("fp32" the
    exact default, "bf16" the mixed-precision path: bf16 operands, f32
    accumulation — the jnp realization of ``EngineConfig.gram_dtype``).
    """
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}; "
                         f"expected one of {COMPUTE_DTYPES}")
    name = params.name
    if name == "linear":
        return partial(linear_gram, compute_dtype=compute_dtype)
    if name == "poly":
        return partial(poly_gram, gamma=params.gamma, degree=params.degree,
                       coef0=params.coef0, compute_dtype=compute_dtype)
    if name == "sigmoid":
        return partial(sigmoid_gram, gamma=params.gamma, coef0=params.coef0,
                       compute_dtype=compute_dtype)
    if name == "rbf":
        return partial(rbf_gram, gamma=params.gamma,
                       compute_dtype=compute_dtype)
    raise ValueError(f"unknown kernel {name!r}")


def resolve_gamma(params: KernelParams, x: jax.Array) -> KernelParams:
    """Resolve gamma<=0 to the sklearn-style 'scale' heuristic.

    Constant / near-constant features get ``gamma = 1.0`` (sklearn's
    fallback): the old ``max(var, 1e-12)`` clamp produced gamma ~ 1e12,
    which degenerates the RBF Gram to the identity matrix.
    """
    if params.gamma > 0:
        return params
    var = float(jnp.var(x))
    gamma = 1.0 / (x.shape[-1] * var) if var > 1e-12 else 1.0
    return dataclasses.replace(params, gamma=gamma)
