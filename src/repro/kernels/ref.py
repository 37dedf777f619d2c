"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the mathematical definition the corresponding kernel
must reproduce (asserted with ``assert_allclose`` across shape/dtype
sweeps in ``tests/test_kernels_pallas.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import kernels as K


def rbf_gram(a: jax.Array, b: jax.Array, gamma: float) -> jax.Array:
    """K[i, j] = exp(-gamma ||a_i - b_j||^2), float32."""
    return K.rbf_gram(a, b, gamma=gamma)


def linear_gram(a: jax.Array, b: jax.Array) -> jax.Array:
    return K.linear_gram(a, b)


def decision(x_test: jax.Array, x_train: jax.Array, coef: jax.Array,
             b: jax.Array, gamma: float) -> jax.Array:
    """f(z) = sum_i coef_i exp(-gamma||x_i - z||^2) + b, coef = alpha*y."""
    kmat = K.rbf_gram(x_test, x_train, gamma=gamma)
    return kmat @ coef + b


def ssd_diag(cmat, bmat, x, dt, cs):
    """Intra-chunk SSD oracle (matches repro.models.mamba2.ssd_chunked's
    y_diag stage, G=1). cmat/bmat (BC,Q,N); x (BC,H,Q,P); dt/cs (BC,H,Q)."""
    scores = jnp.einsum("cqn,ckn->cqk", cmat.astype(jnp.float32),
                        bmat.astype(jnp.float32))
    seg = cs[:, :, :, None] - cs[:, :, None, :]      # (BC,H,Q,Q)
    q = cmat.shape[1]
    causal = jnp.tril(jnp.ones((q, q), bool))
    l_mat = jnp.where(causal[None, None], jnp.exp(seg), 0.0)
    w = scores[:, None] * l_mat * dt[:, :, None, :]
    return jnp.einsum("chqk,chkp->chqp", w, x.astype(jnp.float32))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """Plain softmax attention oracle. q (BH,Sq,d), k/v (BH,Sk,d[v])."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)
