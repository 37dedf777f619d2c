"""The benchmark's float64 reference against the program's own
certificate and serving oracle, at a small size on the CPU."""
import numpy as np
import pytest

from bench import data, reference
from bench.generators import open_loop


@pytest.fixture(scope="module")
def fitted():
    from repro.core.svm import SVC
    x, y = data.load_pavia_like(60, n_classes=3, seed=3)
    x = data.normalize(x)
    clf = SVC(kernel="rbf", gamma=1.0 / 102).fit(x, y)
    return clf, x, y


KERNEL = {"name": "rbf", "gamma": 1.0 / 102}


def _model(clf):
    from bench import system
    return system.banks(system.pack(clf))


def test_kkt_matches_the_programs_certificate(fitted):
    from repro.core import smo
    from repro.serve import reference as prog_ref
    clf, x, y = fitted
    model = _model(clf)
    classes, pairs = reference.routing(y)
    tasks = list(reference.task_rows(classes, pairs, x, y))
    for task_ids, sv_x, coef, b, counts in model["banks"]:
        for j, t in enumerate(task_ids):
            k = int(counts[j])
            xt, yt = tasks[t]
            got = reference.certify_task(KERNEL, 1.0, xt, yt,
                                         sv_x[j, :k], coef[j, :k], b[j])
            index = reference.row_index(xt)
            alpha = np.zeros(len(xt))
            for r in range(k):
                alpha[index[sv_x[j, r].tobytes()]] = abs(coef[j, r])
            f = prog_ref.gram64(clf.kernel_params, xt, sv_x[j, :k]) @ \
                coef[j, :k].astype(np.float64) - yt
            want = float(smo.kkt_violation(alpha, yt, f, 0.0, 1.0))
            assert got["kkt"] == pytest.approx(want, rel=1e-4, abs=1e-6)
            assert got["kkt"] <= clf.smo_cfg.tol
            assert got["bias_gap"] < 1e-4


def test_decisions_and_votes_match_the_programs(fitted):
    from repro import serve
    from repro.core import multiclass as MC
    from repro.serve import reference as prog_ref
    clf, x, y = fitted
    model = _model(clf)
    packed = serve.pack(clf)
    z = x[:40]
    got = reference.decision_values(KERNEL, model["banks"], z,
                                    model["n_tasks"])
    want = prog_ref.decision_values(packed, z)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(
        reference.tolerance(model["banks"], model["n_tasks"], 1e-5),
        prog_ref.tolerance(packed), rtol=1e-12)
    classes, pairs = reference.routing(y)
    assert reference.routing_faults(classes, pairs, model) == 0
    votes = reference.vote(got, pairs, len(classes))
    prog = np.asarray(MC.vote_decision(got.astype(np.float32),
                                       packed.pairs, packed.n_classes))
    np.testing.assert_array_equal(votes, prog)


def test_a_wrong_support_vector_reads_infinite(fitted):
    clf, x, y = fitted
    model = _model(clf)
    xt, yt = next(reference.task_rows(*reference.routing(y), x, y))
    task_ids, sv_x, coef, b, counts = model["banks"][0]
    j = list(task_ids).index(0)
    k = int(counts[j])
    bad = sv_x[j, :k].copy()
    bad[0] += 1.0
    r = reference.certify_task(KERNEL, 1.0, xt, yt, bad,
                               coef[j, :k], b[j])
    assert r["kkt"] == float("inf") and r["sv_fault"]


@pytest.mark.parametrize("labels,want", [
    ([3, 1, 3, 1], [[1, 0]]),
    ([2, 0, 5, 0, 2], [[0, 1], [0, 2], [1, 2]]),
])
def test_routing_follows_the_labels(labels, want):
    classes, pairs = reference.routing(np.array(labels))
    np.testing.assert_array_equal(classes, np.unique(labels))
    np.testing.assert_array_equal(pairs, want)


def test_a_model_routed_otherwise_reads_faults(fitted):
    clf, _, y = fitted
    model = _model(clf)
    classes, pairs = reference.routing(y)
    swapped = dict(model, pairs=model["pairs"][:, ::-1])
    assert reference.routing_faults(classes, pairs, swapped) > 0
    ids, *rest = model["banks"][0]
    twice = dict(model, banks=[(np.zeros_like(ids), *rest)]
                 + model["banks"][1:])
    assert reference.routing_faults(classes, pairs, twice) > 0


def test_schedule_offers_the_same_work_on_every_seed():
    params = {"rate_rps": 50.0, "rows": [1, 8, 32, 128],
              "weights": [0.4, 0.3, 0.2, 0.1], "values_share": 0.5}
    a = open_loop.schedule(params, 4.0, 500, seed=2**31 + 11)
    b = open_loop.schedule(params, 4.0, 500, seed=7)
    assert len(a[0]) == len(b[0]) == 200
    assert sorted(a[1]) == sorted(b[1])
    assert sorted(a[3]) == sorted(b[3])
    assert (a[2] + a[1] <= 500).all() and (np.diff(a[0]) >= 0).all()
    c = open_loop.schedule(params, 4.0, 500, seed=2**31 + 11)
    for u, v in zip(a, c):
        np.testing.assert_array_equal(u, v)
