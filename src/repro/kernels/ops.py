"""Public, padding-aware jit wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses. They
(1) resolve tile/block sizes — explicit arguments win, otherwise the
``kernels.autotune`` on-disk tuning cache is consulted for this
(device, kernel, dtype, shape bucket) and the hardcoded defaults are
the fallback; (2) pad every axis up to the kernel's block multiples
(MXU/VMEM alignment); (3) dispatch the pallas_call; (4) slice the
padding back off. Whether a kernel compiles for the device or runs in
the Pallas interpreter is decided here and nowhere else (``on_tpu``):
compiled on a TPU, interpreted on every other backend (the CPU test
suite). The raw ``*_pallas`` kernels take ``interpret`` with no default.

Mixed precision: the Gram-shaped kernels take ``compute_dtype``
("fp32" | "bf16"). Under "bf16" the operand tiles are cast to bfloat16
AFTER padding (zeros stay zero), halving the HBM tile traffic, while
the MXU accumulates in f32 and the RBF epilogue (norms, exp) runs in
f32 — the engine-level flag ``EngineConfig.gram_dtype`` threads through
here.

Padding correctness notes:
* Gram: padded FEATURE columns are zero in both operands -> contribute 0
  to the dot and to the squared norms; padded SAMPLE rows produce extra
  rows/cols that are sliced off.
* decision: padded train rows carry coef = 0 -> contribute 0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import decision as _decision
from repro.kernels import rbf_gram as _gram

COMPUTE_DTYPES = ("fp32", "bf16")


def on_tpu() -> bool:
    """True when the default backend is a TPU: the kernels compile for
    it. Elsewhere they run in the Pallas interpreter (exact, slow)."""
    return jax.default_backend() == "tpu"


def _check_compute_dtype(compute_dtype: str) -> None:
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}; "
                         f"expected one of {COMPUTE_DTYPES}")


def _tile_cast(x: jax.Array, compute_dtype: str) -> jax.Array:
    """Cast padded operand tiles for the kernel (bf16 tile loads, f32
    accumulation happens inside the kernels)."""
    return x.astype(jnp.bfloat16) if compute_dtype == "bf16" else x


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths)


# --------------------------------------------------------------- rbf_gram
@partial(jax.jit, static_argnames=("gamma", "mode", "block_n", "block_m",
                                   "block_d", "compute_dtype", "interpret"))
def _rbf_gram_padded(a, b, *, gamma, mode, block_n, block_m, block_d,
                     compute_dtype, interpret):
    n, m = a.shape[0], b.shape[0]
    a = _pad_to(_pad_to(a.astype(jnp.float32), 1, block_d), 0, block_n)
    b = _pad_to(_pad_to(b.astype(jnp.float32), 1, block_d), 0, block_m)
    a = _tile_cast(a, compute_dtype)
    b = _tile_cast(b, compute_dtype)
    out = _gram.rbf_gram_pallas(a, b, gamma=gamma, mode=mode,
                                block_n=block_n, block_m=block_m,
                                block_d=block_d, interpret=interpret)
    return out[:n, :m]


def rbf_gram(a: jax.Array, b: jax.Array, *, gamma: float = 1.0,
             mode: str = "rbf", block_n: int | None = None,
             block_m: int | None = None, block_d: int | None = None,
             compute_dtype: str = "fp32") -> jax.Array:
    """K(a, b): (n, m) float32 Gram matrix (rbf or linear). Block sizes
    left as ``None`` resolve through the autotune cache."""
    _check_compute_dtype(compute_dtype)
    blocks = autotune.resolve_blocks(
        "rbf_gram", (a.shape[0], b.shape[0], a.shape[1]), compute_dtype,
        {"block_n": block_n, "block_m": block_m, "block_d": block_d})
    return _rbf_gram_padded(a, b, gamma=gamma, mode=mode,
                            compute_dtype=compute_dtype,
                            interpret=not on_tpu(), **blocks)


# ----------------------------------------------------------- rff_features
@partial(jax.jit, static_argnames=("scale", "block_n", "block_m",
                                   "block_d", "compute_dtype", "interpret"))
def _rff_features_padded(x, omega, phase, *, scale, block_n, block_m,
                         block_d, compute_dtype, interpret):
    from repro.kernels import feature_map as _fmap
    n, k = x.shape[0], omega.shape[1]
    xp = _pad_to(_pad_to(x.astype(jnp.float32), 1, block_d), 0, block_n)
    wp = _pad_to(_pad_to(omega.astype(jnp.float32), 0, block_d), 1, block_m)
    # padded frequency columns see omega = phase = 0 -> cos(0) = scale;
    # sliced off below. Padded d rows/cols are zero on both operands.
    php = _pad_to(phase.astype(jnp.float32)[None, :], 1, block_m)
    xp = _tile_cast(xp, compute_dtype)
    wp = _tile_cast(wp, compute_dtype)
    out = _fmap.rff_features_pallas(xp, wp, php, scale=scale,
                                    block_n=block_n, block_m=block_m,
                                    block_d=block_d, interpret=interpret)
    return out[:n, :k]


def rff_features(x: jax.Array, omega: jax.Array, phase: jax.Array, *,
                 scale: float, block_n: int | None = None,
                 block_m: int | None = None, block_d: int | None = None,
                 compute_dtype: str = "fp32") -> jax.Array:
    """Fused RFF transform ``scale * cos(x @ omega + phase)``: (n, k)
    float32 feature block (``repro.core.approx.RFFMap``'s TPU path).
    Block sizes left as ``None`` resolve through the autotune cache."""
    _check_compute_dtype(compute_dtype)
    blocks = autotune.resolve_blocks(
        "rff_features", (x.shape[0], omega.shape[1], x.shape[1]),
        compute_dtype,
        {"block_n": block_n, "block_m": block_m, "block_d": block_d})
    return _rff_features_padded(x, omega, phase, scale=float(scale),
                                compute_dtype=compute_dtype,
                                interpret=not on_tpu(), **blocks)


# --------------------------------------------------------------- decision
@partial(jax.jit, static_argnames=("gamma", "block_t", "block_n",
                                   "compute_dtype", "interpret"))
def _decision_padded(x_test, x_train, coef, b, *, gamma, block_t, block_n,
                     compute_dtype, interpret):
    nt = x_test.shape[0]
    d_mult = 128
    xt = _pad_to(_pad_to(x_test.astype(jnp.float32), 1, d_mult), 0, block_t)
    xr = _pad_to(_pad_to(x_train.astype(jnp.float32), 1, d_mult), 0, block_n)
    cf = _pad_to(coef.astype(jnp.float32), 0, block_n)
    xt = _tile_cast(xt, compute_dtype)
    xr = _tile_cast(xr, compute_dtype)
    out = _decision.decision_pallas(xt, xr, cf, gamma=gamma,
                                    block_t=block_t, block_n=block_n,
                                    interpret=interpret)
    return out[:nt] + b


def decision(x_test: jax.Array, x_train: jax.Array, coef: jax.Array,
             b: jax.Array | float = 0.0, *, gamma: float = 1.0,
             block_t: int | None = None, block_n: int | None = None,
             compute_dtype: str = "fp32") -> jax.Array:
    """f(z) = K(z, X) @ coef + b for a batch of test rows."""
    _check_compute_dtype(compute_dtype)
    blocks = autotune.resolve_blocks(
        "decision", (x_test.shape[0], x_train.shape[0], x_test.shape[1]),
        compute_dtype, {"block_t": block_t, "block_n": block_n})
    return _decision_padded(x_test, x_train, coef, b, gamma=gamma,
                            compute_dtype=compute_dtype,
                            interpret=not on_tpu(), **blocks)


# ----------------------------------------------------- multitask_decision
@partial(jax.jit, static_argnames=("gamma", "mode", "block_t", "block_n",
                                   "compute_dtype", "interpret"))
def _multitask_decision_padded(x_test, sv_x, coef, b, *, gamma, mode,
                               block_t, block_n, compute_dtype, interpret):
    nt = x_test.shape[0]
    d_mult = 128
    xt = _pad_to(_pad_to(x_test.astype(jnp.float32), 1, d_mult), 0, block_t)
    sv = _pad_to(_pad_to(sv_x.astype(jnp.float32), 2, d_mult), 1, block_n)
    cf = _pad_to(coef.astype(jnp.float32), 1, block_n)
    xt = _tile_cast(xt, compute_dtype)
    sv = _tile_cast(sv, compute_dtype)
    out = _decision.multitask_decision_pallas(
        xt, sv, cf, gamma=gamma, mode=mode, block_t=block_t,
        block_n=block_n, interpret=interpret)[:, :nt]
    return out if b is None else out + b[:, None].astype(jnp.float32)


def multitask_decision(x_test: jax.Array, sv_x: jax.Array, coef: jax.Array,
                       b: jax.Array | None = None, *, gamma: float = 1.0,
                       mode: str = "rbf", block_t: int | None = None,
                       block_n: int | None = None,
                       compute_dtype: str = "fp32") -> jax.Array:
    """f_t(z) = K(z, SV_t) @ coef_t + b_t for a stacked (T, w, d) SV bank.

    One fused grid over every task of a serving bucket (the batched
    inference hot spot); padded SV rows carry coef = 0 and padded test
    rows are sliced off, exactly like ``decision``. A width-0 bank (the
    empty-SV degenerate model) short-circuits to the broadcast bias.
    """
    if mode not in ("rbf", "linear"):
        raise ValueError(f"unknown multitask decision mode {mode!r}; "
                         "expected 'rbf' or 'linear'")
    _check_compute_dtype(compute_dtype)
    nt = x_test.shape[0]
    n_tasks, w, _ = sv_x.shape
    if w == 0:  # no support vectors anywhere: constant-bias predictor
        out = jnp.zeros((n_tasks, nt), jnp.float32)
        return out if b is None else out + b[:, None].astype(jnp.float32)
    blocks = autotune.resolve_blocks(
        "multitask_decision", (n_tasks, nt, w, x_test.shape[1]),
        compute_dtype, {"block_t": block_t, "block_n": block_n})
    return _multitask_decision_padded(x_test, sv_x, coef, b, gamma=gamma,
                                      mode=mode,
                                      compute_dtype=compute_dtype,
                                      interpret=not on_tpu(), **blocks)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256) -> jax.Array:
    """Flash attention over (B, S, H, D) tensors with GQA broadcast.

    Pads S to tile multiples (padded KV masked out via causality for
    causal=True; for the padded q rows the outputs are sliced off)."""
    from repro.kernels import flash_attn as _fa
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:  # GQA: broadcast kv heads to q heads
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    bq = min(block_q, max(128, sq))
    bk = min(block_k, max(128, k.shape[1]))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    qf = qp.transpose(0, 2, 1, 3).reshape(b * h, qp.shape[1], d)
    kf = kp.transpose(0, 2, 1, 3).reshape(b * h, kp.shape[1], d)
    vf = vp.transpose(0, 2, 1, 3).reshape(b * h, vp.shape[1],
                                          vp.shape[3])
    out = _fa.flash_attention_pallas(qf, kf, vf, causal=causal,
                                     block_q=bq, block_k=bk,
                                     interpret=not on_tpu(),
                                     kv_len=k.shape[1])
    out = out.reshape(b, h, qp.shape[1], vp.shape[3]).transpose(0, 2, 1, 3)
    return out[:, :sq]


def gram_row_fn(*, gamma: float, block: int | None = None,
                mode: str = "rbf", compute_dtype: str = "fp32"):
    """``(X, z) -> K(X, z)`` single-row closure for the SMO f-cache update
    (the on-the-fly, O(n d)-memory mode used by the chunked/Pallas
    ``KernelEngine`` backends; ``mode``/``compute_dtype`` mirror
    ``rbf_gram``)."""
    def row(x, z):
        return rbf_gram(x, z[None, :], gamma=gamma, mode=mode,
                        block_n=block, block_m=128,
                        compute_dtype=compute_dtype)[:, 0]
    return row
