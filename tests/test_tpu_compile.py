"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret-mode tests (tests/test_kernels_pallas.py) check values; they
cannot see what the TPU compiler refuses — block shapes that break the
(8, 128) tiling rule, unsupported ops, too much VMEM. Each test here
lowers one raw kernel with ``interpret=False`` at a real width for one
chip of a described ``v5e:2x2`` topology (no chip attached), compiles
it, and checks that the kernel is in the program (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, and every xdist worker imports this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decision as D
from repro.kernels import feature_map as FM
from repro.kernels import rbf_gram as G


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rbf_gram_compiles(one_chip, dtype):
    n = m = 4096
    d = 128
    _compile(partial(G.rbf_gram_pallas, gamma=0.5, interpret=False),
             one_chip, ((n, d), dtype), ((m, d), dtype))


def test_rff_features_compiles(one_chip):
    n, k, d = 16384, 1024, 128
    _compile(partial(FM.rff_features_pallas, scale=(2.0 / k) ** 0.5,
                     interpret=False),
             one_chip, ((n, d), jnp.float32), ((d, k), jnp.float32),
             ((1, k), jnp.float32))


def test_decision_compiles(one_chip):
    nt, n, d = 256, 4096, 128
    _compile(partial(D.decision_pallas, gamma=0.5, interpret=False),
             one_chip, ((nt, d), jnp.float32), ((n, d), jnp.float32),
             ((n,), jnp.float32))


@pytest.mark.parametrize("mode", ["rbf", "linear"])
def test_multitask_decision_compiles(one_chip, mode):
    tasks, nt, w, d = 36, 256, 512, 128
    _compile(partial(D.multitask_decision_pallas, gamma=0.5, mode=mode,
                     interpret=False),
             one_chip, ((nt, d), jnp.float32), ((tasks, w, d), jnp.float32),
             ((tasks, w), jnp.float32))
