"""OvO multiclass + the distributed (shard_map) MPI layer."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import dist, kernels as K, ovo
from repro.core.svm import SVC
from repro.data import load_iris, load_pavia_like, normalize
from repro.launch.mesh import make_local_mesh


def test_vote_matches_majority():
    # 3 classes, task decisions crafted so votes are unambiguous
    classes = np.array([0, 1, 2])
    pairs = np.array([[0, 1], [0, 2], [1, 2]])
    # sample 0: always favors first of pair -> class 0 wins
    dec = jnp.asarray(np.array([[+1.0], [+1.0], [+1.0]]))
    idx = ovo.vote(dec, pairs, classes, 3)
    assert int(idx[0]) == 0
    dec = jnp.asarray(np.array([[-1.0], [-5.0], [-1.0]]))  # favors 1,2,2
    idx = ovo.vote(dec, pairs, classes, 3)
    assert int(idx[0]) == 2


def test_sequential_vs_vmapped_same_result():
    x, y = load_iris()
    x = normalize(x)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    tasks = ovo.build_tasks(x, y)
    seq = dist.sequential_ovo_fit(tasks, solver="smo", kernel=kp)
    vm = dist.vmapped_ovo_fit(tasks, solver="smo", kernel=kp)
    np.testing.assert_allclose(np.asarray(seq.alpha), np.asarray(vm.alpha),
                               rtol=1e-4, atol=1e-5)


def test_svc_multiclass_accuracy():
    x, y = load_iris()
    x = normalize(x)
    clf = SVC(solver="smo").fit(x, y)
    assert clf.score(x, y) >= 0.96
    clf_gd = SVC(solver="gd", gd_steps=2000).fit(x, y)
    assert clf_gd.score(x, y) >= 0.90


def test_svc_binary_gd_and_smo_agree():
    x, y = load_iris()
    x = normalize(x)
    sel = y != 2
    a = SVC(solver="smo").fit(x[sel], y[sel])
    b = SVC(solver="gd", gd_steps=3000).fit(x[sel], y[sel])
    assert a.score(x[sel], y[sel]) == 1.0
    assert b.score(x[sel], y[sel]) == 1.0


@pytest.mark.requires_devices(4)
def test_distributed_equals_local_4workers():
    """The MPI layer (shard_map over 4 forced host devices) must produce
    bit-compatible results with the single-device vmapped fit. Runs
    in-process: conftest.py forces the multi-device host before jax
    initializes (the old subprocess respawn is gone)."""
    x, y = load_pavia_like(n_per_class=24, n_classes=5)
    x = normalize(x)
    kp = K.resolve_gamma(K.KernelParams(), jnp.asarray(x))
    mesh = make_local_mesh(4)
    tasks = ovo.build_tasks(x, y, pad_tasks_to=4)
    fit = dist.distributed_ovo_fit(tasks, mesh, ("workers",),
                                   solver="smo", kernel=kp)
    ref = dist.vmapped_ovo_fit(tasks, solver="smo", kernel=kp)
    np.testing.assert_allclose(np.asarray(fit.alpha),
                               np.asarray(ref.alpha), rtol=1e-4,
                               atol=1e-5)
    c = ovo.n_binary_tasks(5)
    assert bool(np.asarray(fit.converged)[:c].all())


def test_task_padding_for_worker_divisibility():
    x, y = load_iris()
    tasks = ovo.build_tasks(normalize(x), y, pad_tasks_to=4)
    assert tasks.x.shape[0] % 4 == 0
    assert tasks.x.shape[0] >= ovo.n_binary_tasks(3)
    # padded tasks fully masked
    for t in range(ovo.n_binary_tasks(3), tasks.x.shape[0]):
        assert not tasks.mask[t].any()
