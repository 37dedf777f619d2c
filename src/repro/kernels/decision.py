"""Batched SVM decision-function Pallas kernels (inference hot spot).

f(z) = sum_i coef_i K(x_i, z) + b  for a batch of test rows z, fusing the
RBF Gram block with the contraction against coef = alpha*y so the (nt, n)
kernel matrix never materializes in HBM:

  grid (nt/bt, n/bn):  per step, VMEM holds the test tile (bt, d), the
  train tile (bn, d) and coef tile (1, bn); computes the RBF block on the
  MXU, contracts it with coef, and accumulates into the (bt, 1) output
  column. The train axis (reduction) is the innermost sequential grid
  dimension; features stay resident per-tile (SVM d is small — 4..102 —
  so one d-chunk suffices; ops.py pads d to the 128 lane width).

``multitask_decision_pallas`` is the serving-side generalization: a
stacked bank of T binary tasks (T, w, d) — one serving bucket of the
packed model artifact — evaluated against ONE test batch in a single
grid (T, nt/bt, w/bn). The task axis is the outermost grid dimension, so
per task the (i, k) iteration order — and therefore the f32 accumulation
order — is exactly the single-task kernel's, and the test tile is reused
across all T tasks instead of re-streaming per task.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import pallas_precision
from repro.kernels.rbf_gram import _COMPUTE_DTYPES, check_block_divisibility


def _decision_kernel(xt_ref, xr_ref, coef_ref, out_ref, *,
                     gamma: float, n_steps: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xt = xt_ref[...]                          # (bt, d) f32 or bf16
    xr = xr_ref[...]                          # (bn, d)
    coef = coef_ref[...].astype(jnp.float32)  # (1, bn)

    # dot runs at the tile dtype (bf16 tiles feed the MXU natively) with
    # f32 accumulation; norms use f32 of the SAME rounded values so the
    # zero-distance diagonal stays exact under mixed precision
    dot = jax.lax.dot_general(xt, xr, (((1,), (1,)), ((), ())),
                              precision=pallas_precision(xt.dtype),
                              preferred_element_type=jnp.float32)
    xtf = xt.astype(jnp.float32)
    xrf = xr.astype(jnp.float32)
    t2 = jnp.sum(xtf * xtf, axis=1, keepdims=True)     # (bt, 1)
    r2 = jnp.sum(xrf * xrf, axis=1, keepdims=True).T   # (1, bn)
    kblock = jnp.exp(-gamma * jnp.maximum(t2 + r2 - 2.0 * dot, 0.0))
    out_ref[...] += jnp.sum(kblock * coef, axis=1, keepdims=True)


def decision_pallas(x_test: jax.Array, x_train: jax.Array, coef: jax.Array,
                    *, gamma: float, block_t: int = 128, block_n: int = 128,
                    interpret: bool) -> jax.Array:
    """Returns (nt,) decision values WITHOUT bias (add b outside).

    Shapes must be pre-padded: nt % block_t == 0, n % block_n == 0;
    padded train rows must carry coef == 0.
    """
    nt, d = x_test.shape
    n, d2 = x_train.shape
    if d != d2:
        raise ValueError(f"decision_pallas: feature dims differ "
                         f"({d} vs {d2})")
    check_block_divisibility("decision_pallas", nt=(nt, block_t),
                             n=(n, block_n))
    if x_test.dtype not in _COMPUTE_DTYPES:
        x_test = x_test.astype(jnp.float32)
    if x_train.dtype not in _COMPUTE_DTYPES:
        x_train = x_train.astype(jnp.float32)
    grid = (nt // block_t, n // block_n)
    kernel = functools.partial(_decision_kernel, gamma=gamma,
                               n_steps=grid[1])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, k: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, k: (k, 0)),
            pl.BlockSpec((1, block_n), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, 1), jnp.float32),
        interpret=interpret,
    )(x_test, x_train, coef.reshape(1, n))
    return out[:, 0]


def _multitask_kernel(xt_ref, sv_ref, coef_ref, out_ref, *,
                      gamma: float, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xt = xt_ref[...]                              # (bt, d) f32 or bf16
    sv = sv_ref[0]                                # (bn, d) task-t SV tile
    coef = coef_ref[0].astype(jnp.float32)        # (1, bn)

    dot = jax.lax.dot_general(xt, sv, (((1,), (1,)), ((), ())),
                              precision=pallas_precision(xt.dtype),
                              preferred_element_type=jnp.float32)
    if mode == "rbf":
        xtf = xt.astype(jnp.float32)
        svf = sv.astype(jnp.float32)
        t2 = jnp.sum(xtf * xtf, axis=1, keepdims=True)     # (bt, 1)
        r2 = jnp.sum(svf * svf, axis=1, keepdims=True).T   # (1, bn)
        kblock = jnp.exp(-gamma * jnp.maximum(t2 + r2 - 2.0 * dot, 0.0))
    else:                                         # linear
        kblock = dot
    out_ref[0] += jnp.sum(kblock * coef, axis=1, keepdims=True).T


def multitask_decision_pallas(x_test: jax.Array, sv_x: jax.Array,
                              coef: jax.Array, *, gamma: float,
                              mode: str = "rbf", block_t: int = 128,
                              block_n: int = 128,
                              interpret: bool) -> jax.Array:
    """(T, nt) stacked decision values WITHOUT bias (add b outside).

    ``sv_x`` is a (T, w, d) serving bucket: T binary tasks padded to a
    common SV width w. Shapes must be pre-padded: nt % block_t == 0,
    w % block_n == 0; padded SV rows must carry coef == 0 (zero-padded
    test rows are sliced off by the caller).
    """
    nt, d = x_test.shape
    n_tasks, w, d2 = sv_x.shape
    if d != d2:
        raise ValueError(f"multitask_decision_pallas: feature dims "
                         f"differ ({d} vs {d2})")
    check_block_divisibility("multitask_decision_pallas",
                             nt=(nt, block_t), w=(w, block_n))
    if block_t % 128:
        raise ValueError(f"multitask_decision_pallas: block_t={block_t} "
                         f"must be a multiple of 128 (it is the lane "
                         f"axis of the output block)")
    if coef.shape != (n_tasks, w):
        raise ValueError(f"multitask_decision_pallas: coef shape "
                         f"{coef.shape} != bank shape {(n_tasks, w)}")
    if x_test.dtype not in _COMPUTE_DTYPES:
        x_test = x_test.astype(jnp.float32)
    if sv_x.dtype not in _COMPUTE_DTYPES:
        sv_x = sv_x.astype(jnp.float32)
    grid = (n_tasks, nt // block_t, w // block_n)
    kernel = functools.partial(_multitask_kernel, gamma=gamma, mode=mode)
    # coef and the output carry a unit middle axis so the last two block
    # dims are (1 == full extent, lane multiple) — the TPU tiling rule a
    # (1, block) block over a (T, w) array breaks for T > 1
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda t, i, k: (i, 0)),
            pl.BlockSpec((1, block_n, d), lambda t, i, k: (t, k, 0)),
            pl.BlockSpec((1, 1, block_n), lambda t, i, k: (t, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_t), lambda t, i, k: (t, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_tasks, 1, nt), jnp.float32),
        interpret=interpret,
    )(x_test, sv_x, coef.reshape(n_tasks, 1, w))
    return out[:, 0, :]
