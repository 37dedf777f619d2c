"""Fused random-Fourier-feature map — the approximate-kernel hot spot.

The RFF transform ``Φ = scale * cos(X Ω + phase)`` is the entire Gram
stage of the low-rank training tier (``repro.core.approx.RFFMap``): one
(n, d)x(d, k) matmul plus an elementwise epilogue, exactly the shape of
the RBF Gram kernel with the exp epilogue swapped for cos. It reuses
that kernel's tiling:

  grid (n/bn, k/bm, d/bd): each step loads an X-tile (bn, bd) and an
  Ω-tile (bd, bm) into VMEM, accumulates X·Ω (bn, bm) on the MXU in
  f32, and on the last d-step fuses the feature epilogue

      Φ = scale * cos(acc + phase)

  in VMEM before the single write back to HBM — the phase vector rides
  along as a (1, bm) block, and the intermediate (n, k) pre-activation
  never exists in HBM.

The d-axis (reduction) must be the innermost, sequential grid
dimension, as in ``rbf_gram``. Mixed precision mirrors the Gram
kernels: bf16 tile loads with ``preferred_element_type=f32``
accumulation, f32 epilogue. Block sizes are tunable through
``kernels.autotune`` under the kernel name ``"rff_features"``; the
padding-aware public wrapper is ``ops.rff_features``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import pallas_precision
from repro.kernels.rbf_gram import check_block_divisibility

_COMPUTE_DTYPES = (jnp.float32, jnp.bfloat16)


def _rff_kernel(x_ref, w_ref, ph_ref, out_ref, *, scale: float,
                n_d_steps: int):
    """One (bn, bm) feature block; accumulates over the d grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                               # (bn, bd) f32 or bf16
    w = w_ref[...]                               # (bd, bm)
    out_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),           # x @ w on the MXU
        precision=pallas_precision(x.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_d_steps - 1)
    def _finish():
        out_ref[...] = scale * jnp.cos(out_ref[...] + ph_ref[...])


def rff_features_pallas(x: jax.Array, omega: jax.Array, phase: jax.Array,
                        *, scale: float, block_n: int = 128,
                        block_m: int = 128, block_d: int = 128,
                        interpret: bool) -> jax.Array:
    """Feature block ``scale * cos(x @ omega + phase)`` of shape (n, k).

    ``x (n, d)``, ``omega (d, k)``, ``phase (1, k)`` must be pre-padded
    to block multiples (see ``ops.rff_features`` for the public,
    padding-aware wrapper). bf16 x/omega run the mixed-precision path:
    bf16 tile loads, f32 accumulation and epilogue.
    """
    n, d = x.shape
    d2, k = omega.shape
    if d != d2:
        raise ValueError(f"rff_features_pallas: feature dims differ "
                         f"({d} vs {d2})")
    if phase.shape != (1, k):
        raise ValueError(f"rff_features_pallas: phase must be (1, {k}), "
                         f"got {phase.shape}")
    check_block_divisibility("rff_features_pallas", n=(n, block_n),
                             k=(k, block_m), d=(d, block_d))
    if x.dtype not in _COMPUTE_DTYPES:
        x = x.astype(jnp.float32)
    if omega.dtype not in _COMPUTE_DTYPES:
        omega = omega.astype(jnp.float32)
    phase = phase.astype(jnp.float32)
    grid = (n // block_n, k // block_m, d // block_d)

    kernel = functools.partial(_rff_kernel, scale=scale,
                               n_d_steps=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_d, block_m), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, block_m), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, omega, phase)
