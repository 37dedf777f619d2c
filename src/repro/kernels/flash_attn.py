"""Flash attention Pallas kernel (TPU target, beyond-paper optimization).

The roofline analysis (EXPERIMENTS.md §Roofline) shows every train/
prefill shape is MEMORY-dominated, and ~90% of the per-layer HBM traffic
is the materialized (S x S) attention score tensors. This kernel
computes online-softmax attention entirely in VMEM tiles:

  grid (batch*heads, Sq/bq, Sk/bk):  per (q-tile, kv-step), VMEM holds
  q (bq, d), k/v (bk, d), running (m, l, acc) scratch. HBM traffic
  collapses to Q+K+V+O (+ tiny stats) — the memory roofline term for the
  attention block drops by ~S/bk per layer.

  The kv axis is the innermost sequential grid dimension; (m, l, acc)
  live in VMEM scratch carried across kv steps; the finished tile is
  normalized and written once on the last step.

Causal masking is done per-tile with global position iota; fully-masked
tiles still execute (grid is static) but contribute nothing.

VMEM per step (defaults bq=bk=256, d<=256, f32):
  q/k/v/acc 4 x 256 x 256 x 4B = 1 MiB + stats — comfortably under the
  ~16 MiB budget with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rbf_gram import check_block_divisibility

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k_steps: int, kv_len: int):
    kv_step = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = kv_step * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        qpos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    if kv_len % block_k:  # padded tail keys must not attend
        s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                       # (bq, bk)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(kv_step == n_k_steps - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool, kv_len: int = 0):
    """q (BH, Sq, d), k/v (BH, Sk, d) -> (BH, Sq, d).

    Batch and heads pre-flattened (GQA head-broadcast handled by the
    ops.py wrapper). Sq % block_q == 0, Sk % block_k == 0 required.
    ``kv_len``: number of REAL keys (≤ Sk); the padded tail is masked.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    dv = v.shape[2]
    check_block_divisibility("flash_attention_pallas", sq=(sq, block_q),
                             sk=(sk, block_k))
    grid = (bh, sq // block_q, sk // block_k)
    scale = d ** -0.5
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k_steps=grid[2], kv_len=kv_len or sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
