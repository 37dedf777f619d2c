"""Substrate tests: optimizer, checkpoint, data pipeline, loss, roofline
parsing utilities."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt as CK
from repro.data import (load_breast_cancer_like, load_iris,
                        load_pavia_like, normalize, train_test_split)
from repro.data.lm import token_batches
from repro.data.pipeline import subsample_per_class
from repro.optim.adamw import AdamW, SGD, cosine_schedule, global_norm
from repro.roofline.collect import (V5E, collective_bytes, peaks,
                                    roofline_terms)
from repro.training.train import cross_entropy


class TestOptim:
    def test_adamw_minimizes_quadratic(self):
        opt = AdamW(lr=0.1, weight_decay=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = jax.tree.map(lambda p: 2 * p, params)
            params, state = opt.update(grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_grad_clip(self):
        opt = AdamW(lr=0.0, grad_clip=1.0)
        g = {"w": jnp.asarray([1e6, 1e6])}
        assert float(global_norm(g)) > 1.0
        p, _ = opt.update(g, opt.init(g), {"w": jnp.zeros(2)})
        assert np.all(np.isfinite(np.asarray(p["w"])))

    def test_cosine_schedule(self):
        lr = cosine_schedule(peak_lr=1.0, warmup=10, total=100)
        assert float(lr(jnp.asarray(0))) == 0.0
        assert float(lr(jnp.asarray(10))) == pytest.approx(1.0, abs=1e-3)
        assert float(lr(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-2)

    def test_sgd(self):
        opt = SGD(lr=0.5)
        p = {"w": jnp.asarray(4.0)}
        s = opt.init(p)
        p, s = opt.update({"w": jnp.asarray(2.0)}, s, p)
        assert float(p["w"]) == 3.0


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        path = str(tmp_path / "ck.npz")
        CK.save(path, tree, step=7)
        out = CK.restore(path, tree)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        assert CK.latest_step(path) == 7

    def test_shape_mismatch_raises(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        CK.save(path, {"a": jnp.ones((2,))})
        with pytest.raises(ValueError):
            CK.restore(path, {"a": jnp.ones((3,))})


class TestData:
    def test_iris_shape(self):
        x, y = load_iris()
        assert x.shape == (150, 4) and len(np.unique(y)) == 3
        assert all((y == c).sum() == 50 for c in range(3))

    def test_pavia_like(self):
        x, y = load_pavia_like(n_per_class=20)
        assert x.shape == (180, 102) and len(np.unique(y)) == 9

    def test_cancer_like(self):
        x, y = load_breast_cancer_like()
        assert x.shape == (569, 32) and len(np.unique(y)) == 2

    def test_normalize(self):
        x, _ = load_iris()
        z = normalize(x)
        np.testing.assert_allclose(z.mean(0), 0.0, atol=1e-5)
        np.testing.assert_allclose(z.std(0), 1.0, atol=1e-4)

    def test_split_disjoint(self):
        x, y = load_iris()
        xtr, ytr, xte, yte = train_test_split(x, y, test_frac=0.2)
        assert len(ytr) + len(yte) == 150 and len(yte) == 30

    def test_subsample_per_class(self):
        x, y = load_pavia_like(n_per_class=50)
        xs, ys = subsample_per_class(x, y, 10)
        assert all((ys == c).sum() == 10 for c in np.unique(y))

    def test_token_batches_learnable_structure(self):
        bs = list(token_batches(vocab_size=64, batch=2, seq_len=32,
                                n_batches=3, seed=0))
        assert len(bs) == 3
        assert bs[0]["tokens"].shape == (2, 32)
        # shift-by-one consistency
        np.testing.assert_array_equal(bs[0]["tokens"][:, 1:],
                                      bs[0]["labels"][:, :-1])


class TestLoss:
    def test_cross_entropy_uniform(self):
        v = 16
        logits = jnp.zeros((2, 3, v))
        labels = jnp.zeros((2, 3), jnp.int32)
        assert float(cross_entropy(logits, labels)) == pytest.approx(
            np.log(v), abs=1e-5)

    def test_cross_entropy_mask(self):
        logits = jnp.zeros((1, 4, 8))
        labels = jnp.zeros((1, 4), jnp.int32)
        mask = jnp.asarray([[1.0, 1.0, 0.0, 0.0]])
        assert float(cross_entropy(logits, labels, mask=mask)) == \
            pytest.approx(np.log(8), abs=1e-5)


class TestRooflineParsing:
    HLO = """
  %ag = bf16[8,128] all-gather(%x), replica_groups=[2,16]<=[32]
  %ar = f32[1024] all-reduce(%y), channel_id=1
  %rs = f32[64,32] reduce-scatter(%z), channel_id=2
  %cp = bf16[16] collective-permute(%w)
  %a2a = (f32[8], f32[8]) all-to-all(%u, %v)
"""

    def test_collective_bytes(self):
        out = collective_bytes(self.HLO)
        pk = out["per_kind_bytes"]
        assert pk["all-gather"] == 8 * 128 * 2
        assert pk["all-reduce"] == 1024 * 4
        assert pk["reduce-scatter"] == 64 * 32 * 4
        assert pk["collective-permute"] == 16 * 2
        assert pk["all-to-all"] == 2 * 8 * 4
        assert out["total_bytes"] == sum(pk.values())

    def test_roofline_dominance(self):
        t = roofline_terms(flops=197e12, hbm_bytes=1.0,
                           collective_bytes_total=1.0, device_kind=V5E)
        assert t["dominant"] == "compute"
        assert t["t_compute_s"] == pytest.approx(1.0)
        t = roofline_terms(flops=1.0, hbm_bytes=819e9,
                           collective_bytes_total=1.0, device_kind=V5E)
        assert t["dominant"] == "memory"
        t = roofline_terms(flops=1.0, hbm_bytes=1.0,
                           collective_bytes_total=200e9, device_kind=V5E)
        assert t["dominant"] == "collective"

    def test_peaks_unknown_device_kind_is_an_error(self):
        assert peaks(V5E)["flops_bf16"] == 197e12
        with pytest.raises(ValueError, match="no published peaks"):
            peaks("cpu")
        with pytest.raises(ValueError, match="no published peaks"):
            roofline_terms(flops=1.0, hbm_bytes=1.0,
                           collective_bytes_total=0.0, device_kind="cpu")


class TestCompileCache:
    @pytest.fixture
    def restore_dir(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_var_wins_and_nothing_is_set(self, monkeypatch, tmp_path,
                                            restore_dir):
        from repro.launch import compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable("/elsewhere") == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_dir_in_the_checkout(self, monkeypatch,
                                                  tmp_path, restore_dir):
        from repro.launch import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path.resolve() / ".jax_cache")
        assert compile_cache.enable(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
