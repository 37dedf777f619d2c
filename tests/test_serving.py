"""Serving-path correctness: ``serve.Predictor`` and the legacy engine
path, each against the float64 reference.

Both served paths (the packed artifact + Predictor, and the
pre-predictor ``SVC._decision_function_engine`` / ``SVR._predict_engine``)
are held to ``serve.reference``: plain NumPy ``K(z, SV)·coef + b`` in
float64, within ``reference.tolerance`` per task, with labels equal to
the labels decoded from the reference decisions. The two paths are
different XLA programs (padded batch buckets, vmapped banks), so they
are not compared with each other bit for bit: their f32 accumulation
order is the compiler's to choose.
"""
import io

import numpy as np
import pytest

from repro import serve
from repro.serve import reference
from repro.core import kernels as K
from repro.core.svm import SVC, SVR
from repro.data.synth import make_blobs, make_imbalanced_blobs, \
    make_synth_regression

ENGINES = ["dense", "chunked", "pallas"]


@pytest.fixture(scope="module")
def binary_problem():
    x, y = make_blobs(30, 2, 4, sep=3.0, seed=0)
    return x, y, SVC(solver="smo", gamma=0.5).fit(x, y)


@pytest.fixture(scope="module")
def ovo_problem():
    x, y = make_imbalanced_blobs([40, 25, 12, 9, 6], 4, sep=4.0, seed=1)
    return x, y, SVC(solver="smo", gamma=0.5).fit(x, y)


@pytest.fixture(scope="module")
def ovr_problem():
    x, y = make_blobs(20, 3, 4, sep=4.0, seed=2)
    return x, y, SVC(solver="smo", strategy="ovr", gamma=0.5).fit(x, y)


@pytest.fixture(scope="module")
def svr_problem():
    x, y = make_synth_regression(70, 5, seed=3)
    return x, y, SVR(solver="smo", gamma=0.5, epsilon=0.05).fit(x, y)


def _reconfigure(model, engine):
    import dataclasses
    model.engine_cfg = dataclasses.replace(model.engine_cfg,
                                           backend=engine)
    return model


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("prob", ["binary_problem", "ovo_problem",
                                  "ovr_problem", "svr_problem"])
@pytest.mark.parametrize("nt", [1, 7, 32, 37])
def test_serve_matches_legacy_engine_path(engine, prob, nt, request):
    x, y, model = request.getfixturevalue(prob)
    model = _reconfigure(model, engine)
    xt = x[:nt]
    pred = model.predictor()
    want = reference.decision_values(pred.model, xt)
    tol = reference.tolerance(pred.model)
    if isinstance(model, SVR):
        served = {"predictor": model.predict(xt),
                  "engine": model._predict_engine(xt)}
    else:
        served = {"predictor": model.decision_function(xt),
                  "engine": model._decision_function_engine(xt)}
    for path, got in served.items():
        err = np.abs(np.reshape(got, want.shape) - want)
        assert (err <= tol).all(), (path, float(err.max()))
    if not isinstance(model, SVR):
        want_labels = pred.decode(want.astype(np.float32), "predict")
        np.testing.assert_array_equal(model.predict(xt), want_labels)


@pytest.mark.parametrize("kernel", ["linear", "poly"])
def test_pallas_predictor_serves_its_kernels_or_refuses(kernel):
    """engine='pallas' runs the fused decide kernel for rbf and linear
    models and refuses any other kernel instead of swapping engines."""
    x, y = make_imbalanced_blobs([20, 14, 9], 4, sep=4.0, seed=5)
    model = SVC(solver="smo", kernel=kernel, gamma=0.5).fit(x, y)
    packed = serve.pack(model)
    if kernel == "poly":
        with pytest.raises(ValueError, match="engine='chunked'"):
            serve.Predictor(packed, engine="pallas")
        return
    pred = serve.Predictor(packed, engine="pallas")
    xt = x[:13]
    want = reference.decision_values(packed, xt)
    err = np.abs(pred.decision_values(xt) - want)
    assert (err <= reference.tolerance(packed)).all(), float(err.max())
    np.testing.assert_array_equal(
        pred.predict(xt), pred.decode(want.astype(np.float32), "predict"))


@pytest.mark.parametrize("prob", ["binary_problem", "ovo_problem",
                                  "svr_problem"])
def test_micro_batch_slicing_matches_single_shot(prob, request):
    """max_batch streaming (many padded slices) serves the same values
    as one big batch through the default predictor."""
    x, y, model = request.getfixturevalue(prob)
    model = _reconfigure(model, "chunked")
    sliced = serve.Predictor(serve.pack(model), engine="chunked",
                             max_batch=8)
    whole = serve.Predictor(serve.pack(model), engine="chunked")
    xt = x[:30]
    np.testing.assert_array_equal(sliced.predict(xt), whole.predict(xt))
    np.testing.assert_array_almost_equal_nulp(
        sliced.decision_values(xt), whole.decision_values(xt), nulp=4)


# ---------------------------------------------------------------- decode
def _decode_pack(strategy: str, decision: str, m: int = 9):
    """A multiclass pack with Pavia's 9 classes and no support vectors:
    the decode reads only the credit table, not the banks."""
    if strategy == "ovo":
        pairs = np.array([(i, j) for i in range(m) for j in range(i + 1, m)])
    else:
        pairs = np.stack([np.arange(m), -np.ones(m, np.int64)], axis=1)
    n = len(pairs)
    bank = serve.TaskBucket(task_ids=np.arange(n),
                            sv_x=np.zeros((n, 0, 3), np.float32),
                            sv_coef=np.zeros((n, 0), np.float32),
                            b=np.zeros(n, np.float32),
                            sv_counts=np.zeros(n, np.int64))
    return serve.PackedModel(
        kind="svc", kernel=K.KernelParams(name="rbf", gamma=1.0),
        n_features=3, n_tasks=n, buckets=(bank,), strategy=strategy,
        decision=decision, classes=np.arange(m), pairs=pairs)


def _tied_ovo_df(pairs, m, nt, rng):
    """(C, nt) OvO decisions; every even column is a forced vote tie:
    three classes beat every other class and each other in a cycle, so
    they lead on equal votes and only their (distinct) margins decide."""
    wins = rng.random((nt, m, m)) < 0.5     # read at (p, q) only
    for j in range(0, nt, 2):
        a, b, c = rng.choice(m, 3, replace=False)
        for w in (a, b, c):
            wins[j, w, :] = True
            wins[j, :, w] = False
        for w, l in ((a, b), (b, c), (c, a)):
            wins[j, w, l], wins[j, l, w] = True, False
    mag = rng.uniform(0.05, 3.0, (len(pairs), nt))
    sign = np.where(wins[:, pairs[:, 0], pairs[:, 1]].T, 1.0, -1.0)
    return (sign * mag).astype(np.float32)


def _decode_f64(df, pairs, m, strategy, decision):
    """Float64 reference decode, written like ``bench.reference.vote``:
    OvR argmax; OvO summed tanh margins, or votes with the margin as the
    tie-break among the leaders and the lowest class index last."""
    df = np.asarray(df, np.float64)
    if strategy == "ovr":
        return np.argmax(df, axis=0), 0
    votes = np.zeros((df.shape[1], m))
    margin = np.zeros((df.shape[1], m))
    for t, (p, q) in enumerate(pairs):
        pos = df[t] > 0
        votes[pos, p] += 1
        votes[~pos, q] += 1
        margin[:, p] += np.tanh(df[t])
        margin[:, q] -= np.tanh(df[t])
    if decision == "margin":
        return np.argmax(margin, axis=1), 0
    lead = votes >= votes.max(1, keepdims=True) - 0.5
    return np.argmax(np.where(lead, margin, -np.inf), axis=1), \
        int((lead.sum(1) > 1).sum())


@pytest.mark.parametrize("strategy, decision", [("ovo", "vote"),
                                                ("ovo", "margin"),
                                                ("ovr", "vote")])
@pytest.mark.parametrize("nt", [1, 17, 1065, 2048])
def test_compiled_decode_matches_eager_and_f64_votes(strategy, decision,
                                                     nt):
    """The jitted decode program gives the labels of the eager
    ``MC.decide_from_pairs`` and of a float64 vote, at padded (1, 17,
    1065) and exact (2048) widths, with forced vote ties for OvO."""
    import jax.numpy as jnp
    from repro.core import multiclass as MC
    packed = _decode_pack(strategy, decision)
    pairs, m = packed.pairs, packed.n_classes
    rng = np.random.default_rng(1000 + nt)
    if strategy == "ovo":
        df = _tied_ovo_df(pairs, m, nt, rng)
    else:
        df = rng.standard_normal((m, nt)).astype(np.float32)
    pred = serve.Predictor(packed, engine="chunked")
    got = pred.decode(df, "predict")
    eager = np.asarray(MC.decide_from_pairs(jnp.asarray(df), pairs, m,
                                            strategy, decision))
    want, n_ties = _decode_f64(df, pairs, m, strategy, decision)
    np.testing.assert_array_equal(got, eager)
    np.testing.assert_array_equal(got, want)
    if (strategy, decision) == ("ovo", "vote"):
        assert n_ties >= (nt + 1) // 2
    assert pred.n_decode_programs == 1


# ------------------------------------------------------------- artifacts
def test_artifact_roundtrip_multiclass(ovo_problem, tmp_path):
    x, y, model = ovo_problem
    packed = serve.pack(model)
    path = tmp_path / "model.npz"
    serve.save(path, packed)
    loaded = serve.load(path)
    assert loaded.kind == "svc" and loaded.strategy == "ovo"
    assert loaded.n_tasks == packed.n_tasks
    assert loaded.kernel == packed.kernel
    np.testing.assert_array_equal(loaded.classes, packed.classes)
    np.testing.assert_array_equal(loaded.pairs, packed.pairs)
    assert len(loaded.buckets) == len(packed.buckets)
    for got, want in zip(loaded.buckets, packed.buckets):
        for f in got._fields:
            np.testing.assert_array_equal(getattr(got, f),
                                          getattr(want, f))
    pred = serve.Predictor(loaded, engine="chunked")
    np.testing.assert_array_equal(pred.predict(x[:32]),
                                  model.predict(x[:32]))


def test_artifact_roundtrip_string_labels(tmp_path):
    x, y_int = make_blobs(15, 2, 3, sep=3.0, seed=4)
    y = np.where(y_int == 0, "neg", "pos")
    clf = SVC(solver="smo", gamma=0.5).fit(x, y)
    path = tmp_path / "m.npz"
    serve.save(path, serve.pack(clf))
    pred = serve.Predictor(serve.load(path))
    got = pred.predict(x[:9])
    assert set(np.unique(got)) <= {"neg", "pos"}
    np.testing.assert_array_equal(got, clf.predict(x[:9]))


def test_save_load_roundtrip_without_npz_extension(binary_problem,
                                                   tmp_path):
    """save() must write the path VERBATIM (bare np.savez appends
    '.npz' to extension-less paths, breaking load(path))."""
    _, _, model = binary_problem
    path = tmp_path / "model-artifact"      # no extension
    serve.save(path, serve.pack(model))
    assert path.exists()
    assert serve.load(path).n_tasks == 1


def test_n_requests_counts_served_rows_not_warmup(binary_problem):
    x, _, model = binary_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.warmup(batch_sizes=(1, 32))
    assert pred.n_requests == 0             # synthetic rows excluded
    pred.predict(x[:13])
    pred.decision_values(x[:7])
    assert pred.n_requests == 20


def test_artifact_rejects_unknown_schema_and_version(binary_problem,
                                                     tmp_path):
    _, _, model = binary_problem
    packed = serve.pack(model)
    buf = io.BytesIO()
    serve.save(buf, packed)
    buf.seek(0)
    ok = serve.load(buf)
    assert ok.n_tasks == 1

    import json
    path = tmp_path / "bad.npz"
    with np.load(io.BytesIO(buf.getvalue())) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays["meta"]))
    meta["version"] = 999
    arrays["meta"] = np.array(json.dumps(meta))
    np.savez(path, **arrays)
    with pytest.raises(ValueError, match="version"):
        serve.load(path)

    meta["schema"] = "other.format"
    arrays["meta"] = np.array(json.dumps(meta))
    np.savez(path, **arrays)
    with pytest.raises(ValueError, match="schema"):
        serve.load(path)


def test_pack_requires_fitted_model():
    with pytest.raises(ValueError, match="fitted"):
        serve.pack(SVC())


def test_packed_model_validates_task_cover(binary_problem):
    _, _, model = binary_problem
    packed = serve.pack(model)
    with pytest.raises(ValueError, match="task ids"):
        serve.PackedModel(
            kind="svc", kernel=packed.kernel, n_features=4, n_tasks=2,
            buckets=packed.buckets, classes=packed.classes,
            pairs=packed.pairs)


# ----------------------------------------------------------- degenerates
def test_empty_sv_svr_serves_constant_bias():
    x, y = make_synth_regression(40, 4, noise=0.0, seed=5)
    reg = SVR(epsilon=50.0).fit(x, y)   # tube swallows every sample
    assert reg.n_support_ == 0
    got = reg.predict(x[:11])
    want = reg._predict_engine(x[:11])
    np.testing.assert_array_equal(got, want)
    assert np.all(got == got[0])        # the constant-bias predictor
    # and it survives the artifact roundtrip
    buf = io.BytesIO()
    serve.save(buf, serve.pack(reg))
    buf.seek(0)
    pred = serve.Predictor(serve.load(buf))
    np.testing.assert_array_equal(pred.predict(x[:11]), want)


@pytest.mark.parametrize("engine", ["chunked", "pallas"])
def test_empty_sv_bank_serves_bias_on_every_backend(engine):
    bank = serve.TaskBucket(task_ids=np.array([0]),
                            sv_x=np.zeros((1, 0, 3), np.float32),
                            sv_coef=np.zeros((1, 0), np.float32),
                            b=np.array([-0.75], np.float32),
                            sv_counts=np.array([0]))
    packed = serve.PackedModel(
        kind="svc", kernel=K.KernelParams(name="rbf", gamma=1.0),
        n_features=3, n_tasks=1, buckets=(bank,),
        classes=np.array([0, 1]), pairs=np.array([[1, 0]]))
    pred = serve.Predictor(packed, engine=engine)
    df = pred.decision_function(np.ones((5, 3), np.float32))
    np.testing.assert_array_equal(df, np.full(5, -0.75, np.float32))
    np.testing.assert_array_equal(
        pred.predict(np.ones((5, 3), np.float32)), np.zeros(5))


# ------------------------------------------------------------- jit cache
def test_predictor_program_cache_is_batch_bucketed(ovo_problem):
    x, _, model = ovo_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.warmup(batch_sizes=(32,))
    n0 = pred.n_programs
    assert n0 == len(model._serving_buckets)
    # every batch size in (16, 32] hits the warm 32-bucket programs
    for nt in (17, 25, 32):
        pred.decision_values(x[:nt])
    assert pred.n_programs == n0
    # a new batch bucket compiles exactly one more program per SV bucket
    pred.decision_values(x[:4])
    assert pred.n_programs == n0 + len(model._serving_buckets)


def test_predictor_replay_within_compile_budget(ovo_problem,
                                                compile_guard):
    """Runtime backstop for the pow2 padding ladder (analysis R001):
    after warmup at a bucket, every request size inside that bucket
    replays through the warm programs — zero fresh XLA compiles. The
    guard fails this test the day a change starts keying programs on
    raw request shapes again."""
    x, _, model = ovo_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.warmup(batch_sizes=(32,))
    assert pred.n_decode_programs == 1
    with compile_guard(budget=0, note="warm-bucket replay") as g:
        for nt in range(17, 33):
            pred.predict(x[:nt])
    assert g.count == 0 and pred.n_programs == len(model._serving_buckets)
    assert pred.n_decode_programs == 1


@pytest.mark.parametrize("prob, n_widths", [("ovo_problem", 3),
                                            ("ovr_problem", 3),
                                            ("binary_problem", 0),
                                            ("svr_problem", 0)])
def test_decode_programs_one_per_warmed_width(prob, n_widths, request,
                                              compile_guard):
    """Warm-up compiles one decode program per multiclass ladder width
    (binary and SVR decode on the host: none), counted apart from the
    decide programs; replays inside the warm widths compile nothing."""
    x, _, model = request.getfixturevalue(prob)
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.warmup(batch_sizes=(1, 5, 32))
    assert pred.n_decode_programs == n_widths
    n_programs = pred.n_programs
    with compile_guard(budget=0, note="warm decode replay") as g:
        for nt in (1, 5, 6, 7, 8, 17, 20, 32):
            pred.predict(x[:nt])
    assert g.count == 0
    assert pred.n_decode_programs == n_widths
    assert pred.n_programs == n_programs


def test_max_batch_rounds_down_to_pow2(binary_problem):
    """An off-ladder max_batch must not mint off-ladder program shapes:
    max_batch=1000 used to pad 600-row requests to a 1000-row program
    instead of a capped pow2 — one silent extra executable per such
    size class. The cap now rounds DOWN to a pow2 at construction."""
    x, _, model = binary_problem
    packed = serve.pack(model)
    pred = serve.Predictor(packed, engine="chunked", max_batch=1000)
    assert pred.max_batch == 512
    # already-pow2 caps are untouched
    assert serve.Predictor(packed, max_batch=256).max_batch == 256
    assert serve.Predictor(packed, max_batch=1).max_batch == 1
    # a 600-row request slices at 512 then buckets the 88-row tail to
    # 128 — exactly two on-ladder programs, nothing at width 1000/600
    xt = np.tile(np.asarray(x, np.float32), (600 // len(x) + 1, 1))[:600]
    df = pred.decision_values(xt)
    assert pred.n_programs == 2
    whole = serve.Predictor(packed, engine="chunked")
    np.testing.assert_array_almost_equal_nulp(
        df, whole.decision_values(xt), nulp=4)


def test_serving_config_strips_training_only_fields():
    """A sharded-trained engine config must pack to a serving config
    that cannot reference the training mesh axis (the serving host has
    no such axis); the LRU row cache is training-side too."""
    from repro.core import kernel_engine as KE
    cfg = KE.EngineConfig(backend="sharded", shard_axis="shards",
                          cache_slots=16)
    scfg = serve.serving_config(cfg)
    assert scfg.backend == "chunked"
    assert scfg.shard_axis is None
    assert scfg.cache_slots == 0
    # explicit pallas survives, but its shard_axis is still stripped
    scfg = serve.serving_config(
        KE.EngineConfig(backend="pallas", shard_axis="w"))
    assert scfg.backend == "pallas" and scfg.shard_axis is None


def test_predictor_rejects_bad_requests(binary_problem):
    _, _, model = binary_problem
    pred = model.predictor()
    with pytest.raises(ValueError, match="request"):
        pred.decision_values(np.zeros((3, 9), np.float32))
    with pytest.raises(ValueError, match="max_batch"):
        serve.Predictor(serve.pack(model), max_batch=0)


def test_refit_invalidates_predictor_cache(binary_problem):
    x, y, _ = binary_problem
    clf = SVC(solver="smo", gamma=0.5).fit(x, y)
    first = clf.predictor()
    assert clf.predictor() is first          # cached across calls
    clf.fit(x, y)
    assert clf.predictor() is not first      # repacked on refit
