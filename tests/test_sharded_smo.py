"""Sharded single-problem SMO: data-parallel solves must reproduce the
single-device solution.

Three layers of evidence, cheapest first:

* the cross-shard working-set-selection reduction (``combine_selection``,
  the correctness-critical collective) equals the unsharded reduction
  BIT-FOR-BIT on random shards — hypothesis property, no mesh needed;
* the ``ShardedKernelEngine`` primitives (row / matvec / decide) match
  the dense engine through a real shard_map;
* the full equivalence matrix: {rbf, linear} x reference backend
  {dense, chunked} x shard count {1, 2, 4}, plus a non-divisible n
  (padding edge), a shrinking-enabled solve, and the n>=4096 acceptance
  problem — same support set, |b| within tol, identical predictions.

Device counts are forced by tests/conftest.py before jax initializes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import dist, kernel_engine as KE, kernels as K, smo
from repro.core.svm import SVC
from repro.data import make_blobs, normalize
from repro.launch.mesh import make_local_mesh, make_shard_mesh

SV_EPS = 1e-6


def _binary_problem(n, d=6, sep=2.0, seed=11):
    x, yc = make_blobs(n // 2 + n % 2, 2, d, sep=sep, seed=seed)
    x, yc = x[:n], yc[:n]
    yy = np.where(yc == 0, 1.0, -1.0).astype(np.float32)
    return normalize(x), yy


def _grid(x, n_test=64, seed=3):
    rng = np.random.default_rng(seed)
    idx = rng.choice(x.shape[0], size=min(n_test, x.shape[0]),
                     replace=False)
    return x[idx] + rng.normal(scale=0.05, size=x[idx].shape).astype(
        np.float32)


def _assert_equivalent(ref, got, *, x, yy, kp, b_tol=1e-2):
    """ISSUE acceptance criteria, solution-level: same support set, |b|
    within tol, identical predictions. (The TRAJECTORY is bit-identical
    only when the reference engine computes rows the same way — the SPMD
    partitioner may contract dots differently, so a cross-backend cell
    can take a slightly different path to the same optimum.)"""
    a_ref, a_got = np.asarray(ref.alpha), np.asarray(got.alpha)
    assert bool(got.converged)
    # same support set — modulo multipliers below the duality-gap
    # resolution (a tol-terminated solve does not pin down borderline
    # alphas of magnitude ~C*tol; they contribute nothing detectable to
    # the decision function)
    borderline = np.maximum(a_ref, a_got) < 5e-3
    assert bool(((a_ref > SV_EPS) == (a_got > SV_EPS))[~borderline].all())
    np.testing.assert_allclose(a_got, a_ref, rtol=5e-3, atol=5e-3)
    assert abs(float(ref.b) - float(got.b)) <= b_tol
    # identical predictions
    zt = _grid(x)
    df_ref = smo.decision_function(jnp.asarray(x), jnp.asarray(yy),
                                   ref.alpha, ref.b, jnp.asarray(zt),
                                   kernel=kp)
    df_got = smo.decision_function(jnp.asarray(x), jnp.asarray(yy),
                                   got.alpha, got.b, jnp.asarray(zt),
                                   kernel=kp)
    np.testing.assert_array_equal(np.sign(np.asarray(df_ref)),
                                  np.sign(np.asarray(df_got)))


# ------------------------------------------------------------------ matrix
@pytest.mark.requires_devices(4)
@pytest.mark.parametrize("kernel_name", ["rbf", "linear"])
@pytest.mark.parametrize("ref_backend", ["dense", "chunked"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_equivalence_matrix(kernel_name, ref_backend, n_shards):
    x, yy = _binary_problem(384)
    kp = K.resolve_gamma(K.KernelParams(name=kernel_name), jnp.asarray(x))
    cfg = smo.SMOConfig()
    ref = smo.binary_smo(jnp.asarray(x), jnp.asarray(yy), cfg=cfg,
                         kernel=kp,
                         engine=KE.EngineConfig(backend=ref_backend,
                                                chunk=128))
    mesh = make_shard_mesh(n_shards)
    got = smo.sharded_binary_smo(x, yy, mesh=mesh, cfg=cfg, kernel=kp)
    _assert_equivalent(ref, got, x=x, yy=yy, kp=kp)


@pytest.mark.requires_devices(4)
@pytest.mark.parametrize("axis_type", ["auto", "explicit"])
def test_non_divisible_n_padding_edge(axis_type):
    # 519 % 4 == 3: the sample axis is zero-padded to 520 and the pad
    # rows must stay masked with alpha identically 0. A caller's own
    # jax.make_mesh has Explicit axes: unpadding must work there too.
    x, yy = _binary_problem(519)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    ref = smo.binary_smo(jnp.asarray(x), jnp.asarray(yy), kernel=kp)
    mesh = (make_shard_mesh(4) if axis_type == "auto"
            else jax.make_mesh((4,), ("shards",)))
    got = smo.sharded_binary_smo(x, yy, mesh=mesh, kernel=kp)
    assert got.alpha.shape == (519,)
    _assert_equivalent(ref, got, x=x, yy=yy, kp=kp)


@pytest.mark.requires_devices(4)
def test_shrinking_enabled_single_problem():
    # shrinking is a scalar-jit feature: the sharded path is per-problem
    # (not vmapped), so it must work — including the collective un-shrunk
    # KKT re-check (sharded matvec + selection on the full mask)
    x, yy = _binary_problem(600)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    cfg = smo.SMOConfig(shrink_every=2)
    ref = smo.binary_smo(jnp.asarray(x), jnp.asarray(yy), cfg=cfg,
                         kernel=kp)
    got = smo.sharded_binary_smo(x, yy, mesh=make_shard_mesh(4), cfg=cfg,
                                 kernel=kp)
    assert int(got.n_active) <= 600
    _assert_equivalent(ref, got, x=x, yy=yy, kp=kp)


@pytest.mark.slow
@pytest.mark.requires_devices(4)
def test_acceptance_n4096_rbf_4shards():
    """The ISSUE acceptance problem: n >= 4096 RBF on 4 forced host
    devices reproduces the single-device solution."""
    x, yy = _binary_problem(4096, d=8, sep=4.0, seed=7)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    cfg = smo.SMOConfig(max_iter=40_000)
    ref = smo.binary_smo(jnp.asarray(x), jnp.asarray(yy), cfg=cfg,
                         kernel=kp)
    got = smo.sharded_binary_smo(x, yy, mesh=make_shard_mesh(4), cfg=cfg,
                                 kernel=kp)
    assert bool(ref.converged) and bool(got.converged)
    _assert_equivalent(ref, got, x=x, yy=yy, kp=kp)


# ----------------------------------------------- collective WSS reduction
def _split_selection(f, alpha, y, mask, c, n_shards):
    """Reference implementation of the sharded reduction on the host:
    per-shard local ``_selection`` (+ global index conversion), then the
    same ``combine_selection`` every shard would run on the all-gathered
    pairs."""
    n_local = f.shape[0] // n_shards
    ups, iups, lows, ilows = [], [], [], []
    for p in range(n_shards):
        sl = slice(p * n_local, (p + 1) * n_local)
        b_up, i_up, b_low, i_low = smo._selection(
            f[sl], alpha[sl], y[sl], mask[sl], 0.0, c)
        ups.append(b_up)
        iups.append(p * n_local + i_up)
        lows.append(b_low)
        ilows.append(p * n_local + i_low)
    return smo.combine_selection(jnp.stack(ups), jnp.stack(iups),
                                 jnp.stack(lows), jnp.stack(ilows))


def test_wss_reduction_matches_unsharded_seeded():
    """Seeded version of the hypothesis property below — runs even where
    hypothesis (optional dev dep) is absent, so the correctness-critical
    collective is never untested."""
    rng = np.random.default_rng(42)
    for case in range(40):
        n_shards = int(rng.choice([1, 2, 4, 8]))
        n_local = int(rng.integers(1, 25))
        n = n_shards * n_local
        f = rng.uniform(-4, 4, n).astype(np.float32)
        if case % 2:  # coarse grid -> duplicate extrema, exercising the
            f = np.round(f)  # first-occurrence tie-breaking
        f = jnp.asarray(f)
        alpha = jnp.asarray(rng.choice(
            [0.0, 1.0, 0.5, 1e-8, 1.0 - 1e-8], size=n), jnp.float32)
        y = jnp.asarray(rng.choice([-1.0, 1.0], size=n), jnp.float32)
        mask = jnp.asarray(rng.random(n) < 0.8)
        want = smo._selection(f, alpha, y, mask, 0.0, 1.0)
        got = _split_selection(f, alpha, y, mask, 1.0, n_shards)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                          err_msg=f"case {case}")


def test_wss_reduction_all_masked_shard():
    # one shard fully outside the index sets must never win the reduction
    n_shards, n_local = 4, 8
    n = n_shards * n_local
    f = jnp.asarray(np.linspace(-1, 1, n), jnp.float32)
    y = jnp.asarray(np.resize([1.0, -1.0], n), jnp.float32)
    alpha = jnp.zeros(n, jnp.float32)
    mask = jnp.asarray(np.r_[np.zeros(n_local, bool), np.ones(n - n_local,
                                                              bool)])
    want = smo._selection(f, alpha, y, mask, 0.0, 1.0)
    got = _split_selection(f, alpha, y, mask, 1.0, n_shards)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


try:
    from hypothesis import given, settings, strategies as st
    import hypothesis.extra.numpy as hnp
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dependency
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:

    @st.composite
    def wss_shards(draw):
        n_shards = draw(st.sampled_from([1, 2, 4, 8]))
        n_local = draw(st.integers(1, 24))
        n = n_shards * n_local
        # mix a coarse grid into f so cross-shard ties (the first-
        # occurrence tie-break) actually occur
        f = draw(hnp.arrays(np.float32, (n,),
                            elements=st.one_of(
                                st.floats(-4, 4, width=32),
                                st.sampled_from([-1.0, 0.0, 1.0]))))
        # alphas hit the bounds exactly with decent probability — the
        # index-set membership eps is where selection bugs hide
        alpha = draw(hnp.arrays(np.float32, (n,),
                                elements=st.sampled_from(
                                    [0.0, 1.0, 0.5, 1e-8, 1.0 - 1e-8])))
        y = draw(hnp.arrays(np.int8, (n,),
                            elements=st.sampled_from([-1, 1])))
        mask = draw(hnp.arrays(np.bool_, (n,)))
        return (n_shards, f, alpha,
                np.asarray(y, np.float32), mask)

    @given(wss_shards())
    @settings(max_examples=60, deadline=None)
    def test_wss_reduction_matches_unsharded_bit_for_bit(case):
        """For ANY f/alpha/mask sharding: the cross-shard b_up/b_low/
        argpair reduction equals the unsharded ``_selection`` exactly —
        values AND indices (first-occurrence tie semantics)."""
        n_shards, f, alpha, y, mask = case
        f, alpha = jnp.asarray(f), jnp.asarray(alpha)
        y, mask = jnp.asarray(y), jnp.asarray(mask)
        want = smo._selection(f, alpha, y, mask, 0.0, 1.0)
        got = _split_selection(f, alpha, y, mask, 1.0, n_shards)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


# ------------------------------------------------------- engine primitives
@pytest.mark.requires_devices(4)
def test_sharded_engine_row_matvec_decide_match_dense():
    rng = np.random.default_rng(0)
    n, d, t = 64, 5, 9
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    coef = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    kp = K.KernelParams(gamma=0.4)
    dense = KE.make_engine(x, kp, "dense")

    mesh = make_shard_mesh(4, axis="s")
    ecfg = KE.EngineConfig(backend="sharded", shard_axis="s", chunk=16)

    def body(x_l, v_l, coef_l):
        eng = KE.ShardedKernelEngine(x_l, kp, ecfg)
        row, _ = eng.row(jnp.asarray(37), None)
        return eng.matvec(v_l), eng.decide(z, coef_l, 0.25), row

    spec = P("s")
    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=(spec, P(), spec),
                               check_vma=False))
    mv, dec, row = fn(x, v, coef)
    np.testing.assert_allclose(np.asarray(mv),
                               np.asarray(dense.matvec(v)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(dense.decide(z, coef, 0.25)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(row),
                               np.asarray(dense.full()[37]),
                               rtol=1e-5, atol=1e-6)


def test_sharded_engine_requires_axis():
    x = jnp.zeros((8, 2), jnp.float32)
    with pytest.raises(ValueError, match="shard_axis"):
        KE.ShardedKernelEngine(x, K.KernelParams(), KE.EngineConfig())
    with pytest.raises(ValueError, match="bound engine"):
        smo._resolve_sharded_cfg(KE.make_engine(x, K.KernelParams(),
                                                "dense"), "s")


def test_make_shard_mesh_validates():
    with pytest.raises(ValueError, match="devices"):
        make_shard_mesh(10_000)


# ----------------------------------------------- dist / SVC integration
@pytest.mark.requires_devices(4)
def test_fit_taskset_data_parallel_matches_task_parallel():
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(loc=m, size=(80, 4))
                        for m in (-2.0, 0.0, 2.0)]).astype(np.float32)
    y = np.repeat(np.arange(3), 80)
    x = normalize(x)
    from repro.core import multiclass as MC
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    taskset = MC.get_strategy("ovo").build_taskset(x, y)
    mesh = make_local_mesh(4)
    ref = dist.fit_taskset(taskset, kernel=kp)  # local vmapped
    got = dist.fit_taskset(taskset, mesh=mesh, kernel=kp, shard="data")
    np.testing.assert_allclose(got.alpha, ref.alpha, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got.b, ref.b, atol=1e-2)
    assert got.converged.all()
    # auto with a low width threshold routes every bucket data-parallel
    # (3 tasks < 4 workers); result must not change
    auto = dist.fit_taskset(taskset, mesh=mesh, kernel=kp, shard="auto",
                            data_min_width=64)
    np.testing.assert_allclose(auto.alpha, ref.alpha, rtol=1e-4, atol=1e-5)


@pytest.mark.requires_devices(4)
def test_fit_taskset_data_parallel_validates():
    rng = np.random.default_rng(5)
    x = normalize(rng.normal(size=(60, 3)).astype(np.float32))
    y = np.repeat(np.arange(3), 20)
    from repro.core import multiclass as MC
    taskset = MC.get_strategy("ovo").build_taskset(x, y)
    mesh = make_local_mesh(4)
    with pytest.raises(ValueError, match="solver='smo'"):
        dist.fit_taskset(taskset, mesh=mesh, solver="gd", shard="data")
    with pytest.raises(ValueError, match="shard mode"):
        dist.fit_taskset(taskset, mesh=mesh, shard="bogus")


@pytest.mark.requires_devices(4)
def test_svc_shard_data_binary_and_multiclass():
    # binary: explicit data sharding must match the local fit
    x, yy = _binary_problem(300)
    yb = (yy > 0).astype(np.int64)
    mesh = make_shard_mesh(4, axis="workers")
    local = SVC(solver="smo").fit(x, yb)
    shard = SVC(solver="smo", mesh=mesh, shard="data").fit(x, yb)
    assert shard.converged_
    np.testing.assert_array_equal(local.predict(x), shard.predict(x))
    np.testing.assert_allclose(shard.alpha_, local.alpha_, rtol=1e-4,
                               atol=1e-5)

    # multiclass: hybrid auto must agree with the plain fit
    rng = np.random.default_rng(1)
    xm = np.concatenate([rng.normal(loc=m, size=(60, 4))
                         for m in (-2.0, 0.0, 2.0)]).astype(np.float32)
    ym = np.repeat(np.arange(3), 60)
    xm = normalize(xm)
    ref = SVC(solver="smo").fit(xm, ym)
    got = SVC(solver="smo", mesh=mesh, shard="auto").fit(xm, ym)
    np.testing.assert_array_equal(ref.predict(xm), got.predict(xm))
    assert got.score(xm, ym) >= 0.95


def test_svc_shard_validates():
    with pytest.raises(ValueError, match="shard mode"):
        SVC(shard="bogus")
    # explicit data sharding without a mesh must raise, not silently
    # fit on a single device
    x, yy = _binary_problem(40)
    yb = (yy > 0).astype(np.int64)
    with pytest.raises(ValueError, match="mesh"):
        SVC(shard="data").fit(x, yb)


@pytest.mark.requires_devices(2)
def test_svc_shard_data_axis_mismatch_raises():
    # make_shard_mesh defaults to a "shards" axis; SVC defaults to
    # worker_axes=("workers",) — the validator must catch the mismatch
    # instead of KeyError-ing deep inside the solver
    x, yy = _binary_problem(40)
    yb = (yy > 0).astype(np.int64)
    with pytest.raises(ValueError, match="axis"):
        SVC(mesh=make_shard_mesh(2), shard="data").fit(x, yb)


@pytest.mark.requires_devices(2)
def test_fit_taskset_data_without_mesh_raises():
    rng = np.random.default_rng(5)
    x = normalize(rng.normal(size=(60, 3)).astype(np.float32))
    y = np.repeat(np.arange(3), 20)
    from repro.core import multiclass as MC
    taskset = MC.get_strategy("ovo").build_taskset(x, y)
    with pytest.raises(ValueError, match="mesh"):
        dist.fit_taskset(taskset, shard="data")


@pytest.mark.requires_devices(2)
def test_shard_auto_axis_mismatch_raises_friendly():
    """Regression: shard="auto" (and "task" multiclass) on a mesh whose
    axis names don't match ``worker_axes`` used to crash with a raw
    ``KeyError`` from ``mesh.shape[axis]``; ``resolve_worker_count``
    now validates up front and names the mesh axes."""
    from repro.core.svm import SVR
    x, yy = _binary_problem(48)
    yb = (yy > 0).astype(np.int64)
    mesh = make_shard_mesh(2)   # axis "shards" vs default ("workers",)
    with pytest.raises(ValueError, match=r"mesh axes.*shards"):
        SVC(mesh=mesh, shard="auto").fit(x, yb)
    with pytest.raises(ValueError, match=r"mesh axes.*shards"):
        SVR(mesh=mesh, shard="auto").fit(x, yy.astype(np.float32))
    y3 = np.arange(len(yy)) % 3
    with pytest.raises(ValueError, match=r"mesh axes.*shards"):
        SVC(mesh=mesh, shard="task").fit(x, y3)


@pytest.mark.requires_devices(2)
def test_resolve_worker_count():
    assert dist.resolve_worker_count(None, ("workers",)) == 1
    mesh = make_shard_mesh(2)
    assert dist.resolve_worker_count(mesh, ("shards",)) == 2
    with pytest.raises(ValueError, match="worker axes"):
        dist.resolve_worker_count(mesh, ("workers",))
