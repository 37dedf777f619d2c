"""SSD intra-chunk Pallas kernel (mamba2/zamba2 hot spot).

The roofline table shows SSM train/prefill shapes are memory-dominated,
and the biggest single producer is the intra-chunk stage of the SSD
algorithm: the (Q x Q) decay matrix L = exp(cs_i - cs_j) and the masked
quadratic form

    Y_diag[q, p] = sum_{k<=q} (C_q . B_k) * L[q, k] * dt_k * x[k, p]

materialized per (batch, chunk, head) in f32 HBM by the XLA path
(`repro.models.mamba2.ssd_chunked`). This kernel computes the whole
stage per grid cell inside VMEM:

  grid (B*NC, H): per step, VMEM holds C,B (Q, N), x (Q, P), dt/cs (Q,)
  and the (Q, Q) intermediates live only in registers/VMEM — HBM traffic
  collapses to the O(Q*(N+P)) inputs + O(Q*P) output.

VMEM per step (Q=256, N=128, P=64, f32): C+B 256 KiB, x/y 128 KiB,
scores/L 512 KiB — well under budget. MXU does both (Q,N)x(N,Q) and
(Q,Q)x(Q,P) matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_diag_kernel(c_ref, b_ref, x_ref, dt_ref, cs_ref, o_ref):
    c = c_ref[0].astype(jnp.float32)          # (Q, N)
    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q,)
    cs = cs_ref[0, 0].astype(jnp.float32)     # (Q,)

    q = c.shape[0]
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    seg = cs[:, None] - cs[None, :]           # (Q, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(col <= row, jnp.exp(seg), 0.0)
    w = scores * l_mat * dt[None, :]
    o_ref[0, 0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def ssd_diag_pallas(cmat, bmat, x, dt, cs, *, interpret: bool):
    """Intra-chunk SSD contribution.

    cmat/bmat (BC, Q, N)  — chunk C/B projections (group-shared, G=1)
    x         (BC, H, Q, P)
    dt        (BC, H, Q)  — softplus'd step sizes
    cs        (BC, H, Q)  — inclusive cumsum of dt*A within the chunk
    Returns   (BC, H, Q, P) f32.
    """
    bc, q, n = cmat.shape
    h, p = x.shape[1], x.shape[3]
    grid = (bc, h)
    return pl.pallas_call(
        _ssd_diag_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bc, h, q, p), jnp.float32),
        interpret=interpret,
    )(cmat, bmat, x, dt, cs)
