"""Pallas kernel micro-benchmarks (interpret-mode correctness cost is
not meaningful perf; this reports the jnp-reference path wall time and
the kernels' structural roofline estimates for the TPU target).

``tile_sweep`` additionally runs the autotuner (``kernels.autotune``)
over the hot kernels and emits tuned-vs-default JSON lines — the tuned
config can never score worse than the default because the default is
always the hillclimb's first evaluation."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, emit_json, timeit
from repro.core import kernels as K
from repro.roofline.collect import V5E, peaks

# (kernel, shape, dtype) sweep points; quick mode keeps only the first
# per kernel and shrinks the hillclimb budget for CI smoke
SWEEP = [
    ("rbf_gram", (1024, 1024, 128), "fp32"),
    ("rbf_gram", (4096, 4096, 128), "fp32"),
    ("rbf_gram", (4096, 4096, 128), "bf16"),
    ("multitask_decision", (8, 256, 512, 128), "fp32"),
    ("multitask_decision", (8, 256, 512, 128), "bf16"),
]


def tile_sweep(quick: bool = False) -> None:
    """Tuned-vs-default tile configs as JSON lines (one per sweep
    point), under the ``auto`` objective (measured wall time). Needs a
    TPU: the roofline terms price the attached device, and interpret
    mode times nothing of the chip's. Elsewhere it emits one skip line."""
    from repro.kernels import autotune

    if jax.default_backend() != "tpu":
        emit_json({"bench": "tile_sweep",
                   "skipped": f"no TPU ({jax.default_backend()})"})
        return

    points = SWEEP
    if quick:
        seen: set[str] = set()
        points = [p for p in SWEEP
                  if p[0] not in seen and not seen.add(p[0])]
    budget = 3 if quick else 12
    for kernel, shape, dtype in points:
        res = autotune.tune(kernel, shape, dtype=dtype, budget=budget,
                            objective="auto")
        emit_json({
            "bench": "tile_sweep",
            "kernel": kernel,
            "shape": list(shape),
            "dtype": dtype,
            "objective": res.objective,
            "device": autotune.device_kind(),
            "default_config": res.default.config,
            "tuned_config": res.best.config,
            "default_roofline_us": res.default.roofline_s * 1e6,
            "tuned_roofline_us": res.best.roofline_s * 1e6,
            "default_wall_us": (res.default.wall_s or 0) * 1e6 or None,
            "tuned_wall_us": (res.best.wall_s or 0) * 1e6 or None,
            "n_evaluated": len(res.trace),
            "ge_default": res.best.score <= res.default.score,
        })


def main():
    print("# Gram-matrix hot spot: jnp reference wall time + TPU roofline")
    rng = np.random.default_rng(0)
    for n, d in [(800, 102), (1600, 102), (4096, 128)]:
        a = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        fn = jax.jit(lambda x: K.rbf_gram(x, x, gamma=0.1))
        t = timeit(fn, a)
        flops = 2.0 * n * n * d
        bytes_ = (2 * n * d + n * n) * 4
        pk = peaks(V5E)
        t_tpu = max(flops / pk["flops_bf16"], bytes_ / pk["hbm_bytes_per_s"])
        emit(f"gram_{n}x{d}_jnp_cpu", t,
             f"tpu_roofline_est={t_tpu * 1e6:.1f}us "
             f"ai={flops / bytes_:.1f}flop/B")


if __name__ == "__main__":
    main()
