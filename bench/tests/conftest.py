"""The benchmark's tests run on the CPU at tiny sizes, beside the
repo's own suite: the same forced host devices as ``tests/conftest.py``
(set before JAX starts), the checkout's root and ``src`` on the path."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count="
        f"{os.environ.get('REPRO_TEST_DEVICES', '8')}").strip()

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

# the harness points JAX's persistent compilation cache into the
# checkout; put the settings back so later tests in the same worker run
# as they would alone
_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _restore_compile_cache_settings():
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
