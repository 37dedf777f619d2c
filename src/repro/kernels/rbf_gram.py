"""Tiled Gram-matrix Pallas kernel — the SVM compute hot spot.

The paper's CUDA solver spends its time computing kernel (Gram) rows /
blocks. The TPU-native formulation tiles the (n, m) output into
MXU-aligned VMEM blocks:

  grid (n/bn, m/bm, d/bd):  each step loads  A-tile (bn, bd)  and
  B-tile (bm, bd) from HBM into VMEM, accumulates the inner-product
  block  A·Bᵀ (bn, bm)  on the MXU (f32 accumulation), and on the last
  d-step fuses the RBF transform

      K = exp(-gamma (|a|² + |b|² - 2 a·b))

  directly in VMEM before writing the finished block back to HBM —
  the squared norms ride along as (bn, 1)/(1, bm) VMEM blocks instead of
  being recomputed from the features.

VMEM working set per step = bn·bd + bm·bd + bn·bm floats; the default
(128, 128, 128) tiles use ≈ 192 KiB — far under the ~16 MiB/core budget,
leaving room for the pipeline's double buffering. The default tiles are
only a safe baseline: ``kernels.autotune`` hillclimbs (bn, bm, bd) per
(device kind, dtype, shape bucket) and ``ops.rbf_gram`` picks tuned
values up from the on-disk cache.

Mixed precision: bf16 inputs are fed to the MXU as-is (halving the HBM
tile traffic) while the dot accumulates in f32
(``preferred_element_type``) and the RBF epilogue runs in f32 — the
squared norms are computed OUTSIDE in f32 from the same (rounded)
operand values, so K(x, x) stays 1 up to f32 rounding (~1e-6), not
bf16 epsilon.

The d-axis (reduction) must be the innermost, sequential grid dimension:
the output block is revisited across d-steps (TPU grids are sequential by
default; `dimension_semantics` marks n/m as parallel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import pallas_precision

_COMPUTE_DTYPES = (jnp.float32, jnp.bfloat16)


def check_block_divisibility(name: str, **axis_blocks) -> None:
    """Uniform padded-shape validation for the Pallas kernels.

    Each kwarg maps an axis label to a ``(size, block)`` pair; any axis
    not a multiple of its block raises a ValueError naming the fix —
    direct callers (and odd tile choices coming out of the autotuner)
    get a clear error instead of a bare assert tuple. The ``ops.py``
    wrappers pad before calling, so they never trip this.
    """
    bad = {axis: (size, block) for axis, (size, block) in
           axis_blocks.items() if size % block != 0}
    if bad:
        detail = ", ".join(f"{axis}={size} % block={block}"
                           for axis, (size, block) in bad.items())
        raise ValueError(
            f"{name}: inputs must be pre-padded to block multiples "
            f"({detail}); call the padding-aware wrapper in "
            f"repro.kernels.ops, or pad the operands / pick block sizes "
            f"dividing the shape")


def _rbf_gram_kernel(a_ref, b_ref, a2_ref, b2_ref, out_ref, *,
                     gamma: float, n_d_steps: int, mode: str):
    """One (bn, bm) output block; accumulates over the d grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                              # (bn, bd) f32 or bf16
    b = b_ref[...]                              # (bm, bd)
    out_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),          # a @ b.T on the MXU
        precision=pallas_precision(a.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_d_steps - 1)
    def _finish():
        if mode == "rbf":
            d2 = a2_ref[...] + b2_ref[...] - 2.0 * out_ref[...]
            out_ref[...] = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
        # mode == "linear": the accumulated dot IS the Gram block


def rbf_gram_pallas(a: jax.Array, b: jax.Array, *, gamma: float,
                    block_n: int = 128, block_m: int = 128,
                    block_d: int = 128, mode: str = "rbf",
                    interpret: bool) -> jax.Array:
    """Gram block K(a, b) of shape (n, m). Inputs must be pre-padded to
    multiples of the block sizes (see ``ops.rbf_gram`` for the public,
    padding-aware wrapper). bf16 inputs run the mixed-precision path:
    bf16 tile loads, f32 accumulation and epilogue."""
    n, d = a.shape
    m, d2 = b.shape
    if d != d2:
        raise ValueError(f"rbf_gram_pallas: feature dims differ "
                         f"({d} vs {d2})")
    check_block_divisibility("rbf_gram_pallas", n=(n, block_n),
                             m=(m, block_m), d=(d, block_d))
    if a.dtype not in _COMPUTE_DTYPES:
        a = a.astype(jnp.float32)
    if b.dtype not in _COMPUTE_DTYPES:
        b = b.astype(jnp.float32)
    grid = (n // block_n, m // block_m, d // block_d)

    a2 = jnp.sum(a.astype(jnp.float32) ** 2, axis=1, keepdims=True)  # (n,1)
    b2 = jnp.sum(b.astype(jnp.float32) ** 2, axis=1, keepdims=True).T  # (1,m)

    kernel = functools.partial(_rbf_gram_kernel, gamma=gamma,
                               n_d_steps=grid[2], mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_d), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_n, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(a, b, a2, b2)
