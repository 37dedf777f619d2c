"""Service-layer contracts: dynamic batching, the model registry, SV-bank
quantization, and predictor thread-safety.

What PR 9 pins down:

* ``ServingService`` answers are EXACTLY what the underlying predictor
  would serve for the same rows — batching merges requests into one
  fused decide, and the scatter-back never mixes rows up, for any mix
  of ops, models and row counts;
* ``ModelRegistry`` eviction drops device residency but never changes
  served values: evict + re-admit is bit-identical (same pack, same
  programs);
* quantized packs (``sv_dtype="fp16"|"bf16"``) roundtrip through the
  v3 schema, stay within the accuracy gate (decision delta <= 3e-2
  against the fp32 pack) and keep label parity — while v1/v2 artifacts
  keep loading;
* concurrent ``decision_values`` callers on ONE predictor get exactly
  the values a serial caller gets, and the served-row counter stays
  exact.
"""
import io
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro import serve
from repro.serve import reference
from repro.core.svm import SVC, SVR
from repro.data.synth import (make_blobs, make_imbalanced_blobs,
                              make_synth_regression)

QUANT_GATE = 3e-2        # max decision-value delta vs the fp32 pack


@pytest.fixture(scope="module")
def binary_problem():
    x, y = make_blobs(30, 2, 4, sep=3.0, seed=0)
    return x, y, SVC(solver="smo", gamma=0.5).fit(x, y)


@pytest.fixture(scope="module")
def ovo_problem():
    x, y = make_imbalanced_blobs([40, 25, 12, 9], 4, sep=3.0, seed=1)
    return x, y, SVC(solver="smo", gamma=0.5).fit(x, y)


@pytest.fixture(scope="module")
def svr_problem():
    x, y = make_synth_regression(60, 5, seed=2)
    return x, y, SVR(solver="smo", gamma=0.5, epsilon=0.05).fit(x, y)


# ---------------------------------------------------------------- service
def test_service_matches_predictor_outputs(ovo_problem):
    x, _, model = ovo_problem
    packed = serve.pack(model)
    pred = serve.Predictor(packed, engine="chunked").warmup((1, 8, 32))
    with serve.ServingService(packed, engine="chunked",
                              window_ms=5.0) as svc:
        futs = [(svc.submit(x[i:i + 3], op="predict"), "predict", i, 3)
                for i in range(0, 24, 3)]
        futs += [(svc.submit(x[i], op="decision_function"),
                  "decision_function", i, 1) for i in range(24, 30)]
        futs += [(svc.submit(x[i:i + 2], op="values"), "values", i, 2)
                 for i in range(30, 40, 2)]
        tol = reference.tolerance(packed)
        ulps = 8 * np.spacing((tol / reference.RTOL).astype(np.float32))
        for fut, op, i, n in futs:
            got = fut.result(timeout=30)
            want = pred.decode(pred.decision_values(x[i:i + n]), op)
            ref = reference.decision_values(packed, x[i:i + n])
            if op == "predict":
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(
                    got, pred.decode(ref.astype(np.float32), op))
            else:
                # the merged batch pads to a different bucket than the
                # per-slice call, so a task's sum may round in another
                # order: hold the two to 8 f32 ulps of the sum's scale
                # 1 + ||coef_t||_1 (ulps of a value near 0 are far finer
                # than that rounding)
                got = np.reshape(got, ref.shape)
                gap = np.abs(got - np.reshape(want, ref.shape))
                assert (gap <= ulps).all(), (op, float(gap.max()))
                err = np.abs(got - ref)
                assert (err <= tol).all(), (op, float(err.max()))


def _one_window(svc, reqs):
    """Submit ``reqs`` ((rows, op) pairs) inside one window of ``svc``
    and return their answers."""
    n0 = svc.stats["n_batches"]
    futs = [svc.submit(xr, op=op) for xr, op in reqs]
    got = [f.result(timeout=60) for f in futs]
    assert svc.stats["n_batches"] == n0 + 1
    return got


def test_service_mixed_window_answers_each_request_its_own_rows(
        ovo_problem):
    """Label requests first, then values and decision_function requests,
    in one window: the service decides the batch with the values rows
    first, yet every future gets its own rows in its own order — the
    labels of a lone ``predict``, and exactly the values a lone
    ``decision_values`` gives those rows in the batch as served."""
    x, _, model = ovo_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked").warmup(
        (1, 2, 4, 8, 16, 32, 64))
    sizes = [(3, "predict"), (1, "predict"), (5, "predict"),
             (2, "values"), (4, "decision_function"), (1, "values"),
             (2, "predict")]
    starts = np.cumsum([0] + [n for n, _ in sizes])
    reqs = [(x[a:a + n], op) for a, (n, op) in zip(starts, sizes)]
    with serve.ServingService(pred, window_ms=500.0) as svc:
        got = _one_window(svc, reqs)
    served = [i for i, (_, op) in enumerate(reqs) if op != "predict"]
    served += [i for i, (_, op) in enumerate(reqs) if op == "predict"]
    df = pred.decision_values(np.concatenate([reqs[i][0] for i in served]))
    at = dict(zip(served, np.cumsum([0] + [len(reqs[i][0])
                                           for i in served])))
    for i, ((xr, op), ans) in enumerate(zip(reqs, got)):
        if op == "predict":
            np.testing.assert_array_equal(ans, pred.predict(xr))
        else:
            want = df[:, at[i]:at[i] + len(xr)]
            np.testing.assert_array_equal(ans, want)


def test_service_labels_only_window_fetches_no_values(ovo_problem):
    """A window of label requests alone moves its rows up and its labels
    down, and nothing else: its slice is voted on the device."""
    x, _, model = ovo_problem
    packed = serve.pack(model)
    pred = serve.Predictor(packed, engine="chunked").warmup((16,))
    with serve.ServingService(pred, window_ms=500.0) as svc:
        got = _one_window(svc, [(x[i:i + 3], "predict")
                                for i in range(0, 12, 3)])
    s = svc.stats                 # folded once the batch has ended
    np.testing.assert_array_equal(np.concatenate(got), pred.predict(x[:12]))
    bucket = 16                   # 12 rows pad to one 16-row slice
    assert s["transfer_bytes"] == 4 * bucket * packed.n_features + 4 * bucket
    assert s["resident_votes"] == 1


def test_service_batches_a_burst(binary_problem):
    x, _, model = binary_problem
    svc = serve.ServingService(serve.pack(model), engine="chunked",
                               window_ms=50.0)
    try:
        svc.predict(x[:1])                       # warm the programs
        futs = [svc.submit(x[i]) for i in range(20)]
        for f in futs:
            f.result(timeout=30)
        s = svc.stats
        assert s["n_requests"] == 21 and s["n_rows"] == 21
        # the burst of 20 coalesced into far fewer fused decides
        assert s["n_batches"] <= 1 + 4
        assert s["max_batch_rows"] >= 8
    finally:
        svc.close()


def test_service_flushes_when_bucket_fills(binary_problem):
    """A full max_batch window must dispatch immediately, not wait out
    the (long) batching window."""
    x, _, model = binary_problem
    svc = serve.ServingService(serve.pack(model), engine="chunked",
                               window_ms=10_000.0, max_batch=8)
    try:
        svc.predict(x[:8])                       # warm
        t0 = time.perf_counter()
        futs = [svc.submit(x[i]) for i in range(8)]
        for f in futs:
            f.result(timeout=30)
        assert time.perf_counter() - t0 < 5.0    # not the 10s window
        assert svc.stats["n_full_flushes"] >= 1
    finally:
        svc.close()


def test_service_multi_model_routing(binary_problem, svr_problem):
    xc, _, clf = binary_problem
    xr, _, reg_model = svr_problem
    models = {"clf": serve.pack(clf), "reg": serve.pack(reg_model)}
    with serve.ServingService(models, engine="chunked",
                              window_ms=5.0) as svc:
        fc = [svc.submit(xc[i], model="clf") for i in range(8)]
        fr = [svc.submit(xr[i], model="reg") for i in range(8)]
        got_c = np.concatenate([f.result(timeout=30) for f in fc])
        got_r = np.concatenate([f.result(timeout=30) for f in fr])
    np.testing.assert_array_equal(got_c, clf.predict(xc[:8]))
    np.testing.assert_array_equal(got_r, reg_model.predict(xr[:8]))


def test_service_submit_validation(binary_problem):
    x, _, model = binary_problem
    with serve.ServingService(serve.pack(model), engine="chunked",
                              window_ms=0.0) as svc:
        with pytest.raises(KeyError, match="unknown model"):
            svc.submit(x[:2], model="nope")
        with pytest.raises(ValueError, match="op"):
            svc.submit(x[:2], op="proba")
        with pytest.raises(ValueError, match="request"):
            svc.submit(np.zeros((2, 9), np.float32))
        with pytest.raises(ValueError, match="request"):
            svc.submit(np.zeros((0, x.shape[1]), np.float32))
        with pytest.raises(ValueError, match="window_ms"):
            serve.ServingService(serve.pack(model), window_ms=-1)


def test_service_close_flushes_and_rejects(binary_problem):
    x, _, model = binary_problem
    svc = serve.ServingService(serve.pack(model), engine="chunked",
                               window_ms=200.0)
    futs = [svc.submit(x[i]) for i in range(5)]
    svc.close()                      # mid-window: must flush, not drop
    got = np.concatenate([f.result(timeout=30) for f in futs])
    np.testing.assert_array_equal(got, model.predict(x[:5]))
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(x[:1])
    svc.close()                      # idempotent


def test_service_over_existing_predictor(binary_problem):
    x, _, model = binary_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    with serve.ServingService(pred, window_ms=1.0) as svc:
        np.testing.assert_array_equal(svc.predict(x[:7]),
                                      model.predict(x[:7]))
    assert pred.n_requests >= 7      # served through the shared predictor


def test_service_concurrent_submitters(ovo_problem):
    """Many submitter threads, one batcher: every future resolves to
    exactly its own rows' outputs."""
    x, _, model = ovo_problem
    want = model.predict(x)
    with serve.ServingService(serve.pack(model), engine="chunked",
                              window_ms=2.0) as svc:
        def one(i):
            j = i % (len(x) - 4)
            return j, svc.submit(x[j:j + 4]).result(timeout=60)

        with ThreadPoolExecutor(max_workers=8) as ex:
            for j, got in ex.map(one, range(64)):
                np.testing.assert_array_equal(got, want[j:j + 4])


# --------------------------------------------------------------- registry
def test_registry_lru_eviction_and_readmission(binary_problem,
                                               ovo_problem):
    xa, _, ma = binary_problem
    xb, _, mb = ovo_problem
    reg = serve.ModelRegistry(max_resident=2, engine="chunked",
                              warmup_sizes=(4,))
    reg.register("a", serve.pack(ma))
    reg.register("b", serve.pack(mb))
    reg.register("c", serve.pack(ma, sv_dtype="fp16"))
    va = reg.get("a").decision_values(xa[:4])
    reg.get("b")
    assert reg.resident == ("a", "b")
    reg.get("a")                              # refresh recency
    assert reg.resident == ("b", "a")
    reg.get("c")                              # evicts b (LRU), not a
    assert reg.resident == ("a", "c")
    assert reg.stats == {"hits": 1, "admissions": 3, "evictions": 1}
    # the satellite contract: evict + re-admit serves bit-identical
    # values (host pack unchanged, same programs)
    reg.get("b")                              # evicts a
    assert "a" not in reg.resident
    va2 = reg.get("a").decision_values(xa[:4])
    np.testing.assert_array_equal(va, va2)


def test_registry_explicit_evict_and_unregister(binary_problem):
    _, _, model = binary_problem
    reg = serve.ModelRegistry(max_resident=2, engine="chunked")
    reg.register("m", serve.pack(model))
    assert reg.evict("m") is False            # never admitted
    reg.get("m")
    assert reg.evict("m") is True and reg.resident == ()
    assert "m" in reg and len(reg) == 1       # host arrays survive
    reg.unregister("m")
    assert "m" not in reg
    with pytest.raises(KeyError, match="not registered"):
        reg.get("m")
    with pytest.raises(ValueError, match="max_resident"):
        serve.ModelRegistry(max_resident=0)


def test_registry_register_replace_and_path(binary_problem, tmp_path):
    x, y, model = binary_problem
    path = tmp_path / "m.npz"
    serve.save(path, serve.pack(model))
    reg = serve.ModelRegistry(engine="chunked")
    reg.register("m", path)                   # path form loads
    first = reg.get("m")
    with pytest.raises(ValueError, match="already registered"):
        reg.register("m", serve.pack(model))
    reg.register("m", serve.pack(model), replace=True)
    assert reg.resident == ()                 # replace evicts residency
    assert reg.get("m") is not first


def test_registry_thread_safe_admission(binary_problem):
    x, _, model = binary_problem
    reg = serve.ModelRegistry(max_resident=1, engine="chunked",
                              warmup_sizes=())
    reg.register("m", serve.pack(model))
    preds = []
    with ThreadPoolExecutor(max_workers=8) as ex:
        preds = list(ex.map(lambda _: reg.get("m"), range(32)))
    assert all(p is preds[0] for p in preds)  # admitted exactly once
    assert reg.stats["admissions"] == 1


# ----------------------------------------------------------- quantization
@pytest.mark.parametrize("sv_dtype", ["fp16", "bf16"])
@pytest.mark.parametrize("prob", ["binary_problem", "ovo_problem",
                                  "svr_problem"])
def test_quantized_pack_accuracy_gate(sv_dtype, prob, request):
    x, _, model = request.getfixturevalue(prob)
    full = serve.Predictor(serve.pack(model), engine="chunked")
    quant = serve.Predictor(serve.pack(model, sv_dtype=sv_dtype),
                            engine="chunked")
    df_full = full.decision_values(x)
    df_quant = quant.decision_values(x)
    assert np.max(np.abs(df_quant - df_full)) <= QUANT_GATE
    if isinstance(model, SVR):
        assert np.max(np.abs(quant.predict(x) - full.predict(x))) \
            <= QUANT_GATE
    else:
        np.testing.assert_array_equal(quant.predict(x), full.predict(x))


@pytest.mark.parametrize("sv_dtype", ["fp16", "bf16"])
def test_quantized_pack_schema_v3_roundtrip(ovo_problem, sv_dtype,
                                            tmp_path):
    x, _, model = ovo_problem
    packed = serve.pack(model, sv_dtype=sv_dtype)
    assert packed.sv_dtype == sv_dtype
    want_dt = serve.SV_DTYPES[sv_dtype]
    assert all(g.sv_x.dtype == want_dt and g.sv_coef.dtype == want_dt
               for g in packed.buckets)
    path = tmp_path / "q.npz"
    serve.save(path, packed)
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
    assert meta["version"] == serve.SCHEMA_VERSION_QUANT == 3
    assert meta["sv_dtype"] == sv_dtype
    loaded = serve.load(path)
    assert loaded.sv_dtype == sv_dtype
    for got, ref in zip(loaded.buckets, packed.buckets):
        assert got.sv_x.dtype == want_dt
        np.testing.assert_array_equal(
            np.asarray(got.sv_x, np.float32),
            np.asarray(ref.sv_x, np.float32))
        np.testing.assert_array_equal(got.b, ref.b)      # bias stays f32
        assert got.b.dtype == np.float32
    # served values identical pre/post roundtrip
    np.testing.assert_array_equal(
        serve.Predictor(loaded, engine="chunked").decision_values(x[:16]),
        serve.Predictor(packed, engine="chunked").decision_values(x[:16]))


def test_quantized_pack_serves_on_pallas(binary_problem):
    x, _, model = binary_problem
    full = serve.Predictor(serve.pack(model), engine="pallas")
    quant = serve.Predictor(serve.pack(model, sv_dtype="bf16"),
                            engine="pallas")
    delta = np.max(np.abs(quant.decision_values(x[:32])
                          - full.decision_values(x[:32])))
    assert delta <= QUANT_GATE
    np.testing.assert_array_equal(quant.predict(x[:32]),
                                  full.predict(x[:32]))


def test_fp32_pack_still_writes_v1(binary_problem):
    """Quantization must not bump unquantized writers: fp32 SV-bank
    packs keep schema v1 (old readers), low-rank keeps v2."""
    _, _, model = binary_problem
    buf = io.BytesIO()
    serve.save(buf, serve.pack(model))
    buf.seek(0)
    with np.load(buf, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
    assert meta["version"] == 1 and "sv_dtype" not in meta
    buf.seek(0)
    assert serve.load(buf).sv_dtype == "fp32"


def test_lowrank_pack_rejects_quantization():
    x, y = make_blobs(40, 2, 6, sep=3.0, seed=7)
    clf = SVC(engine="rff", rank=32, gamma=0.5).fit(x, y)
    with pytest.raises(ValueError, match="low-rank"):
        serve.pack(clf, sv_dtype="fp16")
    # and the v2 low-rank schema still roundtrips
    buf = io.BytesIO()
    serve.save(buf, serve.pack(clf))
    buf.seek(0)
    loaded = serve.load(buf)
    assert loaded.feature_map is not None and loaded.sv_dtype == "fp32"


def test_quantize_helper_and_validation(binary_problem):
    _, _, model = binary_problem
    packed = serve.pack(model)
    q = serve.quantize(packed, "fp16")
    assert q.sv_dtype == "fp16" and packed.sv_dtype == "fp32"
    assert serve.quantize(q, "fp16") is q            # no-op re-quantize
    with pytest.raises(ValueError, match="sv_dtype"):
        serve.quantize(packed, "int8")
    with pytest.raises(ValueError, match="sv_dtype"):
        serve.pack(model, sv_dtype="fp64")


# ---------------------------------------------------------- thread safety
def test_predictor_concurrent_decision_values(ovo_problem):
    """Concurrent callers must not corrupt n_requests nor interleave
    partially-written outputs: every thread's values and labels match
    the serial reference exactly, and the served-row and work counters
    (``resident_votes`` of the labels included) are the exact totals."""
    x, _, model = ovo_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.warmup(batch_sizes=(4, 16))
    slices = [(i % 40, 4 + (i % 3) * 12) for i in range(48)]
    want, labels, work = {}, {}, {}
    for s, n in set(slices):
        before = pred.work
        want[(s, n)] = pred.decision_values(x[s:s + n])
        labels[(s, n)] = pred.predict(x[s:s + n])
        work[(s, n)] = {k: v - before[k] for k, v in pred.work.items()}
    served0, work0 = pred.n_requests, pred.work
    barrier = threading.Barrier(8)
    errors = []

    def worker(idx):
        try:
            barrier.wait(timeout=30)
            for k in range(idx, len(slices), 8):
                s, n = slices[k]
                np.testing.assert_array_equal(
                    pred.decision_values(x[s:s + n]), want[(s, n)])
                np.testing.assert_array_equal(
                    pred.predict(x[s:s + n]), labels[(s, n)])
        except Exception as e:                       # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert pred.n_requests == served0 + 2 * sum(n for _, n in slices)
    assert pred.work["resident_votes"] > work0["resident_votes"]
    for k, v in pred.work.items():
        assert v == work0[k] + sum(work[sl][k] for sl in slices), k


def test_predictor_decode_validates_op(binary_problem):
    _, _, model = binary_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    df = pred.decision_values(np.zeros((2, 4), np.float32))
    with pytest.raises(ValueError, match="op"):
        pred.decode(df, "proba")


# ----------------------------------------------- lock-discipline regressions
# pinned after the R004 (lock-discipline) sweep: these are the races the
# static rule flagged in serve/, fixed by putting the shared state under
# the declared locks. Each test fails on the pre-fix code.
def test_registry_stats_is_a_snapshot(binary_problem):
    """`stats` used to be the live dict the admission path mutates on
    other threads; it is now a copy taken under the registry lock."""
    _, _, model = binary_problem
    reg = serve.ModelRegistry(engine="chunked", warmup_sizes=())
    reg.register("m", serve.pack(model))
    reg.get("m")
    s = reg.stats
    s["admissions"] = 999                    # caller scribbles on copy
    s["bogus"] = 1
    assert reg.stats == {"hits": 0, "admissions": 1, "evictions": 0}
    assert reg.stats is not reg.stats        # fresh snapshot per read


def test_service_racing_closers_enqueue_one_sentinel(binary_problem):
    """Two racing close() calls used to both pass the unlocked _closed
    check and both enqueue the worker-stop sentinel; the first-closer
    election now happens under the stats lock, so exactly one does."""
    from repro.serve import service as service_mod
    _, _, model = binary_problem
    packed = serve.pack(model)
    for _ in range(4):                       # give the race some chances
        svc = serve.ServingService(packed, engine="chunked",
                                   window_ms=0.0)
        sentinels = []
        orig_put = svc._q.put

        def put(item, *a, _orig=orig_put, _log=sentinels, **k):
            if item is service_mod._SENTINEL:
                _log.append(item)
            return _orig(item, *a, **k)

        svc._q.put = put
        barrier = threading.Barrier(6)

        def closer():
            barrier.wait(timeout=30)
            svc.close()

        threads = [threading.Thread(target=closer) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(sentinels) == 1
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(np.zeros((1, packed.n_features), np.float32))


def test_service_submitters_racing_close_never_hang(binary_problem):
    """Futures issued around a racing close() must all terminate: a real
    result, a closed-service rejection at submit, or the fail-fast
    'closed before dispatch' error — never a silent hang."""
    x, _, model = binary_problem
    svc = serve.ServingService(serve.pack(model), engine="chunked",
                               window_ms=1.0)
    svc.predict(x[:1])                       # warm the programs
    futs: list = []
    barrier = threading.Barrier(5)

    def submitter(i):
        barrier.wait(timeout=30)
        for j in range(25):
            try:
                futs.append((svc.submit(x[(i + j) % len(x)]), i, j))
            except RuntimeError:             # service closed: expected
                return

    def closer():
        barrier.wait(timeout=30)
        time.sleep(0.005)
        svc.close()

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(4)] + [threading.Thread(target=closer)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    for fut, i, j in futs:
        try:
            got = fut.result(timeout=30)     # resolves one way or other
            np.testing.assert_array_equal(
                got, model.predict(x[(i + j) % len(x)][None]))
        except RuntimeError as e:
            assert "closed" in str(e)


def test_warmup_concurrent_requests_keep_their_counts(binary_problem):
    """warmup() used to snapshot-and-restore n_requests, erasing the
    rows real callers served while warmup ran; it now subtracts exactly
    its own synthetic rows under the lock."""
    x, _, model = binary_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked")
    pred.decision_values(x[:3])
    assert pred.n_requests == 3
    rows = [0]
    stop = threading.Event()
    started = threading.Event()

    def real_traffic():
        started.set()
        while not stop.is_set():
            pred.decision_values(x[:2])
            rows[0] += 2

    t = threading.Thread(target=real_traffic)
    t.start()
    try:
        started.wait(timeout=30)
        pred.warmup((1, 4, 16, 64))          # overlaps the live traffic
    finally:
        stop.set()
        t.join(timeout=60)
    assert pred.n_requests == 3 + rows[0]


# ------------------------------------------------------------ compile guard
def test_service_replay_stays_within_compile_budget(ovo_problem,
                                                    compile_guard):
    """Open-loop replay with mixed request sizes through the service
    must reuse the warm bucketed programs: after warmup at the covering
    buckets, a burst of odd-sized requests compiles NOTHING new."""
    x, _, model = ovo_problem
    packed = serve.pack(model)
    with serve.ServingService(packed, engine="chunked",
                              window_ms=2.0) as svc:
        # warm every bucket the burst below can land in — merged
        # windows reach ~120 rows, the 128 bucket — plus the decode path
        for t in (1, 2, 4, 8, 16, 32, 64, len(x)):
            svc.predict(x[:t])
        with compile_guard(budget=0, note="mixed-size replay") as g:
            futs = [svc.submit(x[i % 30:i % 30 + 1 + i % 5])
                    for i in range(40)]
            for f in futs:
                f.result(timeout=60)
        assert g.count == 0


# ------------------------------------------------------- spans and counters
STAGE_KEYS = ("collect_s", "merge_s", "decide_s", "decode_s", "scatter_s")
TIME_KEYS = STAGE_KEYS + ("batch_s", "batcher_cpu_s", "queue_wait_s")


def _held_predictor(model, gate: threading.Event, fail: bool = False):
    """A predictor whose decide waits for ``gate`` (and then raises, if
    ``fail``): the batcher is held inside its first batch."""
    pred = serve.Predictor(serve.pack(model), engine="chunked").warmup(
        (1, 2, 4, 8))
    decide = pred.decision_values

    def held(xt):
        assert gate.wait(timeout=30)
        if fail:
            raise RuntimeError("planted decide failure")
        return decide(xt)

    pred.decision_values = held
    return pred


def test_service_stage_counters(ovo_problem):
    x, _, model = ovo_problem
    with serve.ServingService(serve.pack(model), engine="chunked",
                              window_ms=2.0) as svc:
        futs = [svc.submit(x[i:i + 1 + i % 3],
                           op=("predict", "values")[i % 2])
                for i in range(24)]
        for f in futs:
            f.result(timeout=30)
    s = svc.stats
    for k in TIME_KEYS + ("n_failed_requests",):
        assert k in s and s[k] >= 0, k
    assert s["n_failed_requests"] == 0
    assert s["batch_s"] > 0 and s["decide_s"] > 0 and s["decode_s"] > 0
    # the stages are disjoint parts of the batches
    assert sum(s[k] for k in STAGE_KEYS) <= s["batch_s"]
    assert s["batcher_cpu_s"] <= s["batch_s"] * 1.05 + 1e-3


def test_service_queue_wait_grows_when_the_batcher_is_held(binary_problem):
    x, _, model = binary_problem
    gate = threading.Event()
    svc = serve.ServingService(_held_predictor(model, gate), window_ms=0.0)
    try:
        first = svc.submit(x[:1])
        time.sleep(0.1)                # the batcher takes it and blocks
        later = [svc.submit(x[i]) for i in range(1, 4)]
        held_s = 0.3
        time.sleep(held_s)
        gate.set()
        for f in [first] + later:
            f.result(timeout=30)
    finally:
        svc.close(timeout=30)
    s = svc.stats
    # each of the three later requests waited out the hold in the queue
    assert s["queue_wait_s"] >= 3 * held_s
    assert s["n_requests"] == 4


def test_service_counts_failed_requests(binary_problem):
    x, _, model = binary_problem
    gate = threading.Event()
    gate.set()
    svc = serve.ServingService(_held_predictor(model, gate, fail=True),
                               window_ms=50.0)
    try:
        futs = [svc.submit(x[i]) for i in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="planted"):
                f.result(timeout=30)
    finally:
        svc.close(timeout=30)
    s = svc.stats
    assert s["n_failed_requests"] == 3
    assert s["n_requests"] == 0 and s["n_batches"] == 0


def test_service_spans_nest_on_the_batcher_thread(ovo_problem, tmp_path):
    """A profiler trace on the CPU holds ``serve.batch`` with its stages
    nested in it, on the batcher's thread and no other."""
    import glob
    import jax
    from jax.profiler import ProfileData
    x, _, model = ovo_problem
    svc = serve.ServingService(serve.pack(model), engine="chunked",
                               window_ms=2.0)
    try:
        svc.predict(x[:4])                 # warm before the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            futs = [svc.submit(x[i:i + 2], op=("predict", "values")[i % 2])
                    for i in range(10)]
            for f in futs:
                f.result(timeout=30)
            # a batch's spans close after its futures resolve: join the
            # batcher before the trace stops
            svc.close(timeout=30)
            assert not svc._worker.is_alive()
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close(timeout=30)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               {k: v for k, v in e.stats}) for e in ln.events
              if e.name.startswith("serve.")]
             for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:") for ln in pl.lines]
    lines = [ln for ln in lines if ln]
    # one thread holds every serve.* span: the batcher's
    assert len(lines) == 1
    (events,) = lines
    batches = [e for e in events if e[0] == "serve.batch"]
    assert batches
    assert all({"batch", "requests", "rows", "full"} <= set(b[3])
               for b in batches)
    assert sum(b[3]["requests"] for b in batches) == 10
    assert sum(b[3]["rows"] for b in batches) == 20
    inner = {}
    for name, s, e, _ in events:
        if name == "serve.batch":
            continue
        # every stage lies inside one batch
        assert any(bs <= s and e <= be for _, bs, be, _ in batches), name
        inner[name] = inner.get(name, 0) + 1
    assert {"serve.collect", "serve.merge", "serve.decide", "serve.decode",
            "serve.scatter", "serve.upload", "serve.launch",
            "serve.fetch"} <= set(inner)
    # the predictor uploads and launches inside serve.decide, and
    # fetches (values, then labels) inside serve.decode
    for stage, names in (("serve.decide", ("serve.upload", "serve.launch")),
                         ("serve.decode", ("serve.fetch",))):
        outer = [(s, e) for n, s, e, _ in events if n == stage]
        for n, s, e, _ in events:
            if n in names:
                assert any(ds <= s and e <= de for ds, de in outer), n


def test_predictor_work_counts_padded_kernel_values_and_bytes(ovo_problem):
    """``Predictor.work`` counts what the calls launched: per slice and
    bank the padded rows times the bank's width times its tasks, and
    every byte that crossed the link — rows up, then labels alone when
    the device votes, or the pow2 prefix of decision values that holds
    the rows asked for."""
    x, _, model = ovo_problem
    packed = serve.pack(model)
    pred = serve.Predictor(packed, engine="chunked", max_batch=8)
    d, n_tasks = packed.n_features, packed.n_tasks
    before = pred.work
    pred.predict(x[:11])          # two slices: 8 rows and 3 padded to 4
    got = {k: v - before[k] for k, v in pred.work.items()}
    per_row = sum(g.sv_x.shape[0] * g.sv_x.shape[1] for g in packed.buckets)
    assert got["decide_evals"] == (8 + 4) * per_row
    labels = 4 * (8 + 4)          # int32 class indices of both slices
    assert got["transfer_bytes"] == 4 * (8 + 4) * d + labels
    assert got["resident_votes"] == 2
    before = pred.work
    pred.decode(pred.decision_values(x[:3]), "values")
    got = {k: v - before[k] for k, v in pred.work.items()}
    assert got["transfer_bytes"] == 4 * 4 * d + 4 * n_tasks * 4
    assert got["resident_votes"] == 0
    # three rows of values in an 11-row batch: the first slice's pow2
    # prefix of 4 columns comes down, then both slices' labels; only the
    # second slice was voted with none of its values fetched
    before = pred.work
    dec = pred.decide(x[:11])
    pred.decision_values(dec.head(3))
    pred.decode(dec, "predict")
    got = {k: v - before[k] for k, v in pred.work.items()}
    assert got["transfer_bytes"] == (4 * (8 + 4) * d + 4 * n_tasks * 4
                                     + labels)
    assert got["resident_votes"] == 1


def test_service_stats_fold_the_predictors_work(ovo_problem):
    x, _, model = ovo_problem
    pred = serve.Predictor(serve.pack(model), engine="chunked").warmup(
        (1, 2, 4, 8, 16, 32))
    before = pred.work
    with serve.ServingService(pred, window_ms=2.0) as svc:
        futs = [svc.submit(x[i:i + 1 + i % 3]) for i in range(12)]
        for f in futs:
            f.result(timeout=30)
    s = svc.stats
    for k, v in pred.work.items():
        assert s[k] == v - before[k] > 0, k
    # labels alone, one slice a batch: each batch's slice was voted on
    # the device and none of its decision values came down
    assert s["resident_votes"] == s["n_batches"]
