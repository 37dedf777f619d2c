"""Per-kernel Pallas validation: shape/dtype sweeps vs the ref.py oracle
(interpret mode executes the kernel body on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n,m,d", [
    (64, 64, 4), (100, 80, 32), (128, 256, 102), (37, 129, 7),
    (256, 256, 130), (800, 800, 102), (1, 1, 1),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_rbf_gram_sweep(n, m, d, dtype):
    a = RNG.normal(size=(n, d)).astype(dtype)
    b = RNG.normal(size=(m, d)).astype(dtype)
    gamma = 0.37
    got = ops.rbf_gram(jnp.asarray(a, jnp.float32),
                       jnp.asarray(b, jnp.float32), gamma=gamma)
    want = ref.rbf_gram(jnp.asarray(a, jnp.float32),
                        jnp.asarray(b, jnp.float32), gamma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("n,m,d", [(64, 64, 16), (200, 100, 102)])
def test_linear_gram_sweep(n, m, d):
    a = RNG.normal(size=(n, d)).astype(np.float32)
    b = RNG.normal(size=(m, d)).astype(np.float32)
    got = ops.rbf_gram(jnp.asarray(a), jnp.asarray(b), gamma=1.0,
                       mode="linear")
    np.testing.assert_allclose(np.asarray(got), a @ b.T, rtol=2e-5,
                               atol=1e-4)


@pytest.mark.parametrize("nt,n,d", [(64, 64, 4), (200, 333, 102),
                                    (13, 1000, 32)])
def test_decision_sweep(nt, n, d):
    xt = RNG.normal(size=(nt, d)).astype(np.float32)
    xr = RNG.normal(size=(n, d)).astype(np.float32)
    coef = RNG.normal(size=(n,)).astype(np.float32)
    b = 0.73
    got = ops.decision(jnp.asarray(xt), jnp.asarray(xr), jnp.asarray(coef),
                       b, gamma=0.21)
    want = ref.decision(jnp.asarray(xt), jnp.asarray(xr),
                        jnp.asarray(coef), b, 0.21)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("n_tasks,w,nt,d", [
    (1, 40, 50, 7), (3, 11, 37, 4), (4, 128, 128, 32), (2, 200, 1, 102),
])
@pytest.mark.parametrize("mode", ["rbf", "linear"])
def test_multitask_decision_sweep(n_tasks, w, nt, d, mode):
    xt = RNG.normal(size=(nt, d)).astype(np.float32)
    sv = RNG.normal(size=(n_tasks, w, d)).astype(np.float32)
    coef = RNG.normal(size=(n_tasks, w)).astype(np.float32)
    b = RNG.normal(size=(n_tasks,)).astype(np.float32)
    got = ops.multitask_decision(jnp.asarray(xt), jnp.asarray(sv),
                                 jnp.asarray(coef), jnp.asarray(b),
                                 gamma=0.21, mode=mode)
    want = np.stack([
        np.asarray(ref.rbf_gram(jnp.asarray(xt), jnp.asarray(sv[t]), 0.21)
                   if mode == "rbf" else xt @ sv[t].T) @ coef[t] + b[t]
        for t in range(n_tasks)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-5)
    assert got.shape == (n_tasks, nt)


def test_multitask_decision_matches_per_task_kernel():
    # the fused grid must be BIT-identical to the single-task decision
    # kernel per stacked row (same block sizes, same (i, k) order)
    xt = RNG.normal(size=(37, 6)).astype(np.float32)
    sv = RNG.normal(size=(3, 50, 6)).astype(np.float32)
    coef = RNG.normal(size=(3, 50)).astype(np.float32)
    b = RNG.normal(size=(3,)).astype(np.float32)
    got = np.asarray(ops.multitask_decision(
        jnp.asarray(xt), jnp.asarray(sv), jnp.asarray(coef),
        jnp.asarray(b), gamma=0.37))
    want = np.stack([
        np.asarray(ops.decision(jnp.asarray(xt), jnp.asarray(sv[t]),
                                jnp.asarray(coef[t]), b[t], gamma=0.37))
        for t in range(3)])
    np.testing.assert_array_equal(got, want)


def test_multitask_decision_rejects_unknown_mode():
    z = jnp.zeros((4, 3), jnp.float32)
    sv = jnp.zeros((1, 8, 3), jnp.float32)
    cf = jnp.zeros((1, 8), jnp.float32)
    with pytest.raises(ValueError, match="mode"):
        ops.multitask_decision(z, sv, cf, gamma=1.0, mode="poly")


def test_gram_row_fn_matches_full():
    x = RNG.normal(size=(300, 32)).astype(np.float32)
    row = ops.gram_row_fn(gamma=0.5)(jnp.asarray(x), jnp.asarray(x[7]))
    full = ref.rbf_gram(jnp.asarray(x), jnp.asarray(x), 0.5)
    np.testing.assert_allclose(np.asarray(row), np.asarray(full[:, 7]),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (2, 256, 4, 4, 64), (1, 512, 4, 2, 64), (2, 300, 2, 2, 32),
    (1, 128, 8, 1, 16),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, hkv, d, causal):
    q = RNG.normal(size=(b, s, h, d)).astype(np.float32)
    k = RNG.normal(size=(b, s, hkv, d)).astype(np.float32)
    v = RNG.normal(size=(b, s, hkv, d)).astype(np.float32)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
    kk = np.repeat(k, h // hkv, axis=2)
    vv = np.repeat(v, h // hkv, axis=2)
    want = np.asarray(ref.flash_attention(
        jnp.asarray(q.transpose(0, 2, 1, 3).reshape(b * h, s, d)),
        jnp.asarray(kk.transpose(0, 2, 1, 3).reshape(b * h, s, d)),
        jnp.asarray(vv.transpose(0, 2, 1, 3).reshape(b * h, s, d)),
        causal)).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-5)


def test_flash_attention_matches_model_layer():
    """The Pallas kernel must agree with the model's XLA attention path
    (full_attention) — same math, different memory schedule."""
    from repro.models import layers as L
    b, s, h, d = 1, 128, 4, 32
    q = RNG.normal(size=(b, s, h, d)).astype(np.float32)
    k = RNG.normal(size=(b, s, h, d)).astype(np.float32)
    v = RNG.normal(size=(b, s, h, d)).astype(np.float32)
    got = np.asarray(ops.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=True))
    want = np.asarray(L.full_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("bc,h,q,n,p", [
    (2, 3, 32, 16, 8), (1, 4, 64, 32, 16), (3, 2, 128, 16, 32),
])
def test_ssd_diag_sweep(bc, h, q, n, p):
    from repro.kernels import ssd_diag as _sd
    cmat = RNG.normal(size=(bc, q, n)).astype(np.float32)
    bmat = RNG.normal(size=(bc, q, n)).astype(np.float32)
    x = RNG.normal(size=(bc, h, q, p)).astype(np.float32)
    dt = RNG.uniform(0.001, 0.1, size=(bc, h, q)).astype(np.float32)
    a = -RNG.uniform(1, 8, size=(h,)).astype(np.float32)
    cs = np.cumsum(dt * a[None, :, None], axis=2).astype(np.float32)
    got = _sd.ssd_diag_pallas(jnp.asarray(cmat), jnp.asarray(bmat),
                              jnp.asarray(x), jnp.asarray(dt),
                              jnp.asarray(cs), interpret=True)
    want = ref.ssd_diag(jnp.asarray(cmat), jnp.asarray(bmat),
                        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(cs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ssd_diag_matches_model_chunked_path():
    """Kernel output == the y_diag stage inside mamba2.ssd_chunked
    (zero initial state, single chunk -> y == y_diag)."""
    from repro.kernels import ssd_diag as _sd
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = RNG.normal(size=(b, s, h, p)).astype(np.float32)
    dt = RNG.uniform(0.001, 0.1, size=(b, s, h)).astype(np.float32)
    a = -RNG.uniform(1, 8, size=(h,)).astype(np.float32)
    bm = RNG.normal(size=(b, s, 1, n)).astype(np.float32)
    cm = RNG.normal(size=(b, s, 1, n)).astype(np.float32)
    y, _ = ssd_chunked(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
                       jnp.asarray(bm), jnp.asarray(cm), chunk=s)
    cs = np.cumsum(dt * a[None, None, :], axis=1)          # (B,S,H)
    got = _sd.ssd_diag_pallas(
        jnp.asarray(cm[:, :, 0, :]), jnp.asarray(bm[:, :, 0, :]),
        jnp.asarray(x.transpose(0, 2, 1, 3)),
        jnp.asarray(dt.transpose(0, 2, 1)),
        jnp.asarray(cs.transpose(0, 2, 1)), interpret=True)
    want = np.asarray(y).transpose(0, 2, 1, 3)             # (B,H,S,P)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-3,
                               atol=5e-4)


def test_blockspec_vmem_budget():
    """Default tiles must fit the ~16 MiB/core VMEM budget with double
    buffering (structural check on the BlockSpec sizes)."""
    bn = bm = bd = 128
    working_set = (bn * bd + bm * bd + bn * bm + bn + bm) * 4  # f32 bytes
    assert 2 * working_set < 16 * 2**20
