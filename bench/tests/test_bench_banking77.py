"""The BANKING77 cell's own files at small sizes on the CPU: the data
generator, the shared-row reference and its certain votes, the readers
of the new per-layer metrics, and the generator end to end."""
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

from bench import embeddings, names, reference, reference_union, run
from bench.generators import open_loop_union

CELL = "banking77_ovo.serve_overload"
KERNEL = {"name": "rbf", "gamma": 1.0}


def test_the_data_has_the_published_sizes_and_depends_on_the_seed_alone():
    a = embeddings.banking77_like(2**31 + 11)
    b = embeddings.banking77_like(2**31 + 11)
    c = embeddings.banking77_like(5)
    xtr, ytr, xte, yte = a
    assert xtr.shape == (10_003, 768) and xte.shape == (3_080, 768)
    assert xtr.dtype == xte.dtype == np.float32
    assert np.array_equal(np.bincount(yte), np.full(77, 40))
    assert np.array_equal(np.bincount(ytr), embeddings.class_sizes(77, 10_003))
    assert np.bincount(ytr).min() < np.bincount(ytr).max()
    np.testing.assert_allclose(np.linalg.norm(xtr, axis=1), 1.0, atol=1e-6)
    for u, v in zip(a, b):
        assert np.array_equal(u, v)
    assert not np.array_equal(xtr, c[0])
    assert np.array_equal(ytr.sum(), c[1].sum())   # the same class sizes


def test_the_configuration_builds_its_data():
    cfg = names.config("banking77_ovo")
    xtr, ytr, xte, yte = open_loop_union.dataset(cfg, 3)
    assert xtr.shape == (cfg["data"]["n_train"], cfg["data"]["n_features"])
    assert len(xte) == cfg["data"]["n_classes"] * 40
    with pytest.raises(ValueError):
        embeddings.banking77_like(0, n_classes=10, n_topics=4)


@pytest.fixture(scope="module")
def wide():
    """A 12-class one-vs-one model at d = 16, a few rows a class."""
    from repro import serve
    from repro.core.svm import SVC
    xtr, ytr, xte, _ = embeddings.banking77_like(
        7, n_classes=12, d=16, n_train=120, n_test=4, n_topics=4)
    clf = SVC(kernel="rbf", C=1.0, gamma=1.0).fit(xtr, ytr)
    return serve.pack(clf), xtr, ytr, xte


def _banks(packed):
    from bench import system
    return system.banks(packed)


def test_union_reference_matches_the_per_bank_reference(wide):
    packed, xtr, _, xte = wide
    model = _banks(packed)
    n_tasks = model["n_tasks"]
    rows, where = reference_union.union_rows(model["banks"])
    # the banks repeat rows: fewer distinct rows than bank entries, all
    # of them training rows
    assert len(rows) < sum(int(c.sum()) for *_, c in model["banks"])
    index = reference.row_index(xtr)
    assert all(r.tobytes() in index for r in rows)
    want = reference.decision_values(KERNEL, model["banks"], xte, n_tasks)
    got = reference_union.decision_values(KERNEL, model["banks"], xte,
                                          n_tasks)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_77_classes_through_the_service_match_both_references():
    """77 classes (2,926 tasks) at d = 16 through ``SVC.fit``,
    ``serve.pack``, the default ``Predictor`` and ``ServingService``."""
    from repro import serve
    from repro.core.svm import SVC
    from repro.serve import reference as prog_ref
    xtr, ytr, xte, _ = embeddings.banking77_like(
        11, d=16, n_train=77 * 4, n_test=1)
    clf = SVC(kernel="rbf", C=1.0, gamma=1.0).fit(xtr, ytr)
    packed = serve.pack(clf)
    assert packed.n_tasks == 77 * 76 // 2
    z = xte[:24]
    with serve.ServingService(packed, window_ms=1.0) as svc:
        futs = [svc.submit(z[i:i + 3], op=op) for i in range(0, 24, 3)
                for op in ("values", "predict")]
        got = [f.result(timeout=120) for f in futs]
    values = np.concatenate(got[0::2], axis=1)
    labels = np.concatenate(got[1::2])
    want = prog_ref.decision_values(packed, z)
    assert np.all(np.abs(values - want) <= prog_ref.tolerance(packed))
    model = _banks(packed)
    ours = reference_union.decision_values(KERNEL, model["banks"], z,
                                           packed.n_tasks)
    np.testing.assert_allclose(ours, want, rtol=1e-12, atol=1e-12)
    classes, pairs = reference.routing(ytr)
    tol = reference.tolerance(model["banks"], packed.n_tasks, 1e-5)
    won, certain = reference_union.certain_labels(ours, tol, pairs, 77)
    assert np.array_equal(labels[certain], classes[won][certain])
    assert certain.mean() > 0.5


def test_certain_labels_marks_votes_that_a_rounding_can_flip():
    pairs = np.array([(0, 1), (0, 2), (1, 2)])
    tol = np.full((3, 1), 1e-3)
    df = np.array([[0.5, 0.9, 1e-4, 0.5],
                   [0.5, -0.2, 0.5, 1e-4],
                   [0.5, 0.5, -0.5, 0.5]])
    won, certain = reference_union.certain_labels(df, tol, pairs, 3)
    assert won.tolist() == reference.vote(df, pairs, 3).tolist()
    # row 0: class 0 wins twice for sure; row 1: a three-way tie on
    # votes, settled by margins far apart; row 2: the 0-1 value may flip
    # class 0's win into a tie with 2; row 3: 0 wins one for sure, one
    # maybe, and 1 could reach 1 vote as well
    assert certain.tolist() == [True, True, False, False]


def _layer(**kw):
    stats = {"n_requests": 10, "n_rows": 400, "n_batches": 4,
             "rows_per_batch": 100.0, "decide_evals": 4_000_000,
             "transfer_bytes": 12_000_000}
    ctx = {"kind": "serve", "stats": stats, "traced_stats": stats,
           "n_sv": [100, 50], "d": 8, "n_sv_union": 120,
           "trace": {"busy_s": 0.01, "window_s": 2.0},
           "chips": 1, "device_kind": "TPU v5 lite"}
    ctx.update(kw)
    return ctx


def test_the_new_readers_on_hand_made_layers():
    readers = names.metric_readers()
    read = {k: readers[k][1] for k in (
        "decide_evals_per_row.serve", "transfer_mb_per_batch.serve",
        "union_roofline.serve", "mfu_union.serve")}
    ctx = _layer()
    assert read["decide_evals_per_row.serve"](ctx) == 10_000.0
    assert read["transfer_mb_per_batch.serve"](ctx) == 3.0
    flops = 2.0 * 400 * (120 * 8 + 150)
    nbytes = 4.0 * (4 * (120 * 8 + 152) + 400 * 8 + 2 * 400)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read["union_roofline.serve"](ctx) == pytest.approx(
        100 * least / 0.01, rel=1e-12)
    assert read["mfu_union.serve"](ctx) == pytest.approx(
        100 * flops / (2.0 * 197e12), rel=1e-12)
    # a program without the counters, a generator without the union, a
    # fit, an empty window: nothing to read
    bare = {k: v for k, v in ctx["stats"].items()
            if k not in ("decide_evals", "transfer_bytes")}
    for name, r in read.items():
        assert r(dict(ctx, kind="fit")) is None, name
        assert r(dict(ctx, traced_stats=dict(ctx["stats"], n_rows=0,
                                             n_batches=0))) is None, name
    for name in ("decide_evals_per_row.serve",
                 "transfer_mb_per_batch.serve"):
        assert read[name](dict(ctx, traced_stats=bare)) is None
    for name in ("union_roofline.serve", "mfu_union.serve"):
        assert read[name](_layer(n_sv_union=None)) is None
        lacking = _layer()
        del lacking["n_sv_union"]
        assert read[name](lacking) is None


def tiny() -> dict:
    spec = names.resolve(CELL)
    spec["config"]["data"].update(n_classes=12, n_features=16, n_train=240,
                                  n_test_per_class=12, n_topics=4)
    spec["params"].update(rate_rps=40.0, ladder_log2=8, values_share=0.5)
    return spec


def test_the_cell_runs_end_to_end():
    spec = tiny()
    devices = run.start(1, allow_cpu=True)
    out = io.StringIO()
    with redirect_stdout(out):
        run.emit(run.run_cell(spec, seed=2**31 + 7, seconds=0.5,
                              trace=False, devices=devices, t_start=0.0))
    notes, line = [json.loads(s) for s in
                   out.getvalue().strip().splitlines()[-2:]]
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"serve_rows_per_s", "setup_s"}
    notes = notes["notes"]
    assert notes["n_sv_union"] <= 240 < notes["n_support"]
    assert 0 < notes["sv_share"] <= 1 and 0 < notes["test_accuracy"] <= 1


def test_the_control_is_not_correct():
    from bench import control
    out = io.StringIO()
    with redirect_stdout(out):
        assert control.main(["--workload", CELL, "--seeds", "5",
                             "--seconds", "0.5", "--control", "1"],
                            allow_cpu=True, spec=tiny()) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
