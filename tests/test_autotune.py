"""Autotune layer: candidate enumeration, hillclimb, on-disk cache, and
the runtime fast path ``kernels.ops`` consults.

Correctness contract under test:
* a missing / corrupted / version-mismatched cache NEVER changes
  behavior — lookups fall back to the hardcoded defaults;
* a present cache entry changes ONLY the tile configuration — the op
  results stay numerically identical to the default-tile results;
* the default config is always evaluated by ``tune``, so the tuned
  result is never worse than the default under the chosen objective;
* direct (non-``ops``) Pallas kernel calls with block-misaligned shapes
  raise a ``ValueError`` naming the offending axis, not a bare assert;
* importing the roofline CLI modules does not mutate ``XLA_FLAGS``.
"""
import importlib
import json
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import autotune, ops
from repro.kernels import rbf_gram as G
from repro.kernels import decision as D
from repro.roofline.collect import V5E


@pytest.fixture
def isolated_cache(tmp_path):
    """Pin the runtime tuning cache to a per-test path; restore after."""
    path = str(tmp_path / "autotune.json")
    autotune.set_cache_path(path)
    yield path
    autotune.set_cache_path(None)


@pytest.fixture
def priced_as_v5e(monkeypatch):
    """``tune`` prices the attached device; a CPU has no published
    peaks, so stand the attached device in for the v5e. Both module
    objects: the import-purity test below re-imports ``autotune``."""
    import repro.kernels
    for mod in {autotune, repro.kernels.autotune}:
        monkeypatch.setattr(mod, "device_kind", lambda: V5E)


def _tune_tiny(kernel="rbf_gram", shape=(256, 256, 128)):
    return autotune.tune(kernel, shape, dtype="fp32", budget=4,
                         objective="roofline")


# ------------------------------------------------------------- candidates
def test_candidates_include_default_and_fit_vmem():
    for kernel, shape in [("rbf_gram", (2048, 2048, 256)),
                          ("decision", (512, 4096, 128)),
                          ("multitask_decision", (8, 256, 1024, 128))]:
        cands = autotune.candidates(kernel, shape)
        default = autotune.clip_to_candidates(
            kernel, autotune.DEFAULTS[kernel], shape)
        assert default in cands
        for cfg in cands:
            used = autotune._vmem_bytes(kernel, cfg, shape, "fp32")
            assert 2 * used <= autotune.VMEM_BUDGET_BYTES, (cfg, used)


def test_candidates_clip_to_small_shapes():
    # a tiny problem must not propose tiles beyond its pow2-rounded shape
    for cfg in autotune.candidates("rbf_gram", (100, 100, 10)):
        assert cfg["block_n"] <= 128 and cfg["block_m"] <= 128
    assert autotune.candidates("rbf_gram", (100, 100, 10))


def test_bf16_admits_wider_tiles_than_fp32():
    # halving the operand element size must never shrink the ladder
    big = (4096, 4096, 512)
    n_fp32 = len(autotune.candidates("rbf_gram", big, "fp32"))
    n_bf16 = len(autotune.candidates("rbf_gram", big, "bf16"))
    assert n_bf16 >= n_fp32


def test_shape_bucket_and_cache_key():
    assert autotune.shape_bucket("rbf_gram", (1000, 1024, 100)) == \
        "n1024_m1024_d128"
    assert autotune.shape_bucket("rff_features", (5000, 1000, 102)) == \
        "n8192_k1024_d128"
    key = autotune.cache_key("cpu", "rbf_gram", "bf16", (1000, 1024, 100))
    assert key == "cpu|rbf_gram|bf16|n1024_m1024_d128"
    with pytest.raises(ValueError):
        autotune.shape_bucket("rbf_gram", (10, 10))


# -------------------------------------------------------------- hillclimb
def test_tune_refuses_a_device_without_peaks(monkeypatch):
    monkeypatch.setattr(autotune, "device_kind", lambda: "cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        autotune.tune("rbf_gram", (256, 256, 128), objective="roofline")


def test_tune_roofline_never_worse_than_default(priced_as_v5e):
    for kernel, shape in [("rbf_gram", (1024, 1024, 128)),
                          ("decision", (256, 2048, 128))]:
        res = autotune.tune(kernel, shape, budget=6, objective="roofline")
        assert res.objective == "roofline"
        assert res.best.score <= res.default.score
        assert res.best.roofline_s <= res.default.roofline_s
        assert 1 <= len(res.trace) <= 6
        assert res.best.config in autotune.candidates(kernel, shape)


def test_tune_wall_objective_measures_and_improves(priced_as_v5e):
    # tiny shape so interpret-mode timing stays cheap; the guarantee is
    # structural (default evaluated first), not a perf claim on CPU
    res = autotune.tune("rbf_gram", (128, 128, 64), budget=2,
                        objective="wall", warmup=0, iters=1)
    assert res.objective == "wall"
    assert all(ev.wall_s is not None for ev in res.trace)
    assert res.best.score <= res.default.score


def test_roofline_estimate_rewards_bigger_tiles_and_bf16():
    shape = (4096, 4096, 256)
    small = autotune.roofline_estimate("rbf_gram", shape, "fp32",
                                       {"block_n": 128, "block_m": 128,
                                        "block_d": 128},
                                       device_kind=V5E)
    big = autotune.roofline_estimate("rbf_gram", shape, "fp32",
                                     {"block_n": 512, "block_m": 512,
                                      "block_d": 128},
                                     device_kind=V5E)
    assert big["hbm_bytes"] < small["hbm_bytes"]
    assert big["flops"] == small["flops"]
    bf16 = autotune.roofline_estimate("rbf_gram", shape, "bf16",
                                      {"block_n": 128, "block_m": 128,
                                       "block_d": 128},
                                      device_kind=V5E)
    assert bf16["hbm_bytes"] < small["hbm_bytes"]


# ------------------------------------------------------------- disk cache
def test_cache_roundtrip(isolated_cache, priced_as_v5e):
    res = _tune_tiny()
    cache = autotune.TuningCache()
    key = autotune.cache_key("cpu", "rbf_gram", "fp32", (256, 256, 128))
    cache.put(key, res)
    cache.save(isolated_cache)

    loaded = autotune.TuningCache.load(isolated_cache)
    assert loaded.get(key) == res.best.config
    raw = json.load(open(isolated_cache))
    assert raw["version"] == autotune.CACHE_VERSION
    assert raw["entries"][key]["n_evaluated"] == len(res.trace)


def test_missing_cache_falls_back_to_defaults(isolated_cache):
    assert not os.path.exists(isolated_cache)
    assert autotune.lookup("rbf_gram", (256, 256, 128)) is None
    blocks = autotune.resolve_blocks(
        "rbf_gram", (256, 256, 128), "fp32",
        {"block_n": None, "block_m": None, "block_d": None})
    assert blocks == autotune.DEFAULTS["rbf_gram"]


def test_corrupted_cache_falls_back_to_defaults(isolated_cache):
    with open(isolated_cache, "w") as f:
        f.write("{not json at all")
    assert autotune.TuningCache.load(isolated_cache).entries == {}
    autotune.reset()
    assert autotune.lookup("rbf_gram", (256, 256, 128)) is None


def test_version_mismatch_falls_back_to_defaults(isolated_cache):
    key = autotune.cache_key(autotune.device_kind(), "rbf_gram", "fp32",
                             (256, 256, 128))
    stale = {"version": autotune.CACHE_VERSION + 1,
             "entries": {key: {"config": {"block_n": 512, "block_m": 512,
                                          "block_d": 128}}}}
    with open(isolated_cache, "w") as f:
        json.dump(stale, f)
    assert autotune.TuningCache.load(isolated_cache).entries == {}
    autotune.reset()
    assert autotune.lookup("rbf_gram", (256, 256, 128)) is None


def test_malformed_entries_are_dropped(isolated_cache):
    good_key = autotune.cache_key(autotune.device_kind(), "rbf_gram",
                                  "fp32", (256, 256, 128))
    raw = {"version": autotune.CACHE_VERSION,
           "entries": {good_key: {"config": {"block_n": 256,
                                             "block_m": 128,
                                             "block_d": 128}},
                       "bad1": "not a dict",
                       "bad2": {"no_config_key": 1}}}
    with open(isolated_cache, "w") as f:
        json.dump(raw, f)
    loaded = autotune.TuningCache.load(isolated_cache)
    assert set(loaded.entries) == {good_key}
    autotune.reset()
    assert autotune.lookup("rbf_gram", (256, 256, 128)) == {
        "block_n": 256, "block_m": 128, "block_d": 128}


# ------------------------------------------------------ runtime fast path
def test_ops_pick_up_tuned_entry_and_stay_correct(isolated_cache,
                                                  priced_as_v5e):
    """A tuned non-default tile must change only the schedule: the Gram
    values from the tuned path match the default-tile values exactly."""
    shape = (256, 200, 64)
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(shape[0], shape[2]))
                    .astype(np.float32))
    b = jnp.asarray(rng.normal(size=(shape[1], shape[2]))
                    .astype(np.float32))
    baseline = np.asarray(ops.rbf_gram(a, b, gamma=0.25))

    res = _tune_tiny("rbf_gram", shape)
    cache = autotune.TuningCache()
    cache.put(autotune.cache_key(autotune.device_kind(), "rbf_gram",
                                 "fp32", shape), res)
    # force a non-default winner so the test is meaningful either way
    cache.entries[list(cache.entries)[0]]["config"] = {
        "block_n": 256, "block_m": 256, "block_d": 128}
    cache.save(isolated_cache)
    autotune.reset()

    assert autotune.lookup("rbf_gram", shape) == {
        "block_n": 256, "block_m": 256, "block_d": 128}
    tuned = np.asarray(ops.rbf_gram(a, b, gamma=0.25))
    np.testing.assert_allclose(tuned, baseline, rtol=0, atol=1e-6)


def test_explicit_blocks_override_tuned_entry(isolated_cache, priced_as_v5e):
    shape = (256, 256, 128)
    cache = autotune.TuningCache()
    cache.put(autotune.cache_key(autotune.device_kind(), "rbf_gram",
                                 "fp32", shape), _tune_tiny())
    cache.entries[list(cache.entries)[0]]["config"] = {
        "block_n": 256, "block_m": 256, "block_d": 128}
    cache.save(isolated_cache)
    autotune.reset()
    blocks = autotune.resolve_blocks(
        "rbf_gram", shape, "fp32",
        {"block_n": 64, "block_m": None, "block_d": None})
    assert blocks == {"block_n": 64, "block_m": 256, "block_d": 128}


def test_env_var_overrides_cache_location(tmp_path, monkeypatch):
    p = str(tmp_path / "alt.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", p)
    assert autotune.default_cache_path() == p


def test_default_cache_lives_in_the_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert autotune.default_cache_path() == os.path.join(root,
                                                         ".autotune.json")


def test_lookup_propagates_device_errors(isolated_cache, priced_as_v5e,
                                         monkeypatch):
    # a readable cache with entries, then a failing device query: the
    # error surfaces instead of silently meaning "untuned"
    cache = autotune.TuningCache()
    cache.put(autotune.cache_key("cpu", "rbf_gram", "fp32",
                                 (256, 256, 128)), _tune_tiny())
    cache.save(isolated_cache)
    autotune.reset()

    def no_device():
        raise RuntimeError("no device")
    monkeypatch.setattr(autotune, "device_kind", no_device)
    with pytest.raises(RuntimeError, match="no device"):
        autotune.lookup("rbf_gram", (256, 256, 128))


# ------------------------------------- uniform misaligned-shape ValueErrors
def test_direct_pallas_calls_raise_on_misaligned_shapes():
    z = jnp.zeros
    with pytest.raises(ValueError, match="pre-padded to block multiples"):
        G.rbf_gram_pallas(z((130, 128)), z((128, 128)), gamma=1.0,
                          interpret=True)
    with pytest.raises(ValueError, match="n=130"):
        G.rbf_gram_pallas(z((130, 128)), z((128, 128)), gamma=1.0,
                          interpret=True)
    with pytest.raises(ValueError, match="pre-padded to block multiples"):
        D.decision_pallas(z((100, 128)), z((128, 128)), z(128), gamma=1.0,
                          interpret=True)
    with pytest.raises(ValueError, match="pre-padded to block multiples"):
        D.multitask_decision_pallas(z((128, 128)), z((2, 100, 128)),
                                    z((2, 100)), gamma=1.0, interpret=True)
    with pytest.raises(ValueError, match="feature dims"):
        G.rbf_gram_pallas(z((128, 128)), z((128, 256)), gamma=1.0,
                          interpret=True)


def test_ops_wrappers_accept_misaligned_shapes():
    # the padding-aware public wrappers keep accepting anything
    a = jnp.ones((130, 7))
    out = ops.rbf_gram(a, jnp.ones((65, 7)), gamma=0.1)
    assert out.shape == (130, 65)


# --------------------------------------------- import-time purity (roofline)
def test_roofline_imports_do_not_mutate_xla_flags():
    before = os.environ.get("XLA_FLAGS")
    for mod in ("repro.roofline.hillclimb", "repro.roofline.differential",
                "repro.roofline.inspect_hlo", "repro.roofline.svm_tune",
                "repro.kernels.autotune"):
        sys.modules.pop(mod, None)
        importlib.import_module(mod)
    assert os.environ.get("XLA_FLAGS") == before


def test_setup_env_is_idempotent(monkeypatch):
    from repro.roofline import hillclimb
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    hillclimb.setup_env(4)
    first = os.environ["XLA_FLAGS"]
    assert "xla_force_host_platform_device_count=4" in first
    hillclimb.setup_env(4)          # second call must not stack flags
    assert os.environ["XLA_FLAGS"] == first


# ------------------------------------------------------------- CLI driver
def test_svm_tune_cli_writes_cache(tmp_path, priced_as_v5e):
    from repro.roofline import svm_tune
    out = str(tmp_path / "cli.json")
    rc = svm_tune.main(["--kernel", "rbf_gram", "--shape", "256x256x128",
                        "--budget", "2", "--objective", "roofline",
                        "--out", out])
    assert rc == 0
    raw = json.load(open(out))
    assert raw["version"] == autotune.CACHE_VERSION
    assert len(raw["entries"]) == 1
    (rec,) = raw["entries"].values()
    assert set(rec["config"]) == {"block_n", "block_m", "block_d"}
    autotune.reset()  # CLI reset() left the runtime pinned to defaults


def test_svm_tune_cli_rejects_bad_shape():
    from repro.roofline import svm_tune
    with pytest.raises(ValueError, match="positive 'x'-separated"):
        svm_tune.parse_shape("rbf_gram", "256x256")
    with pytest.raises(ValueError):
        svm_tune.parse_shape("decision", "0x128x128")
