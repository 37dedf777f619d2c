"""Roofline-driven tile autotuning for the SVM Pallas kernels.

The hot kernels (``rbf_gram``, ``rff_features``, ``decision``,
``multitask_decision``) ship MXU-aligned default tiles that are correct
everywhere but optimal nowhere in particular. This module makes every
tile/block knob tunable per (device kind, kernel, dtype, shape bucket):

* ``candidates(kernel, shape, dtype)`` enumerates the feasible tile
  configurations — powers of two per axis, clipped to the shape, lane /
  sublane aligned, and filtered against the ~16 MiB/core VMEM budget
  with double buffering (the same structural constraint
  ``tests/test_kernels_pallas.py::test_blockspec_vmem_budget`` pins for
  the defaults);
* ``roofline_estimate(...)`` prices a configuration with the published
  peaks of one device kind (``repro.roofline.collect.PEAKS``; a kind
  missing from that table is an error) — per-tile HBM
  traffic (bigger output tiles re-stream fewer operand bytes) vs MXU
  FLOPs, the collect/differential cost model pointed at the SVM kernels
  instead of the transformer stack;
* ``tune(...)`` hillclimbs from the default configuration: evaluate the
  current config and its single-axis x2 / /2 neighbours (timed jitted
  calls and/or the roofline estimate, see ``objective``), move to the
  best, stop when no neighbour improves or the evaluation budget is
  spent. The default config is ALWAYS evaluated, so the tuned result is
  never worse than the default under the chosen objective;
* ``TuningCache`` persists results as versioned JSON keyed by
  ``device|kernel|dtype|bucket``. A missing, corrupted or
  version-mismatched cache file falls back to the defaults — tuning
  is an optimization, never a correctness dependency;
* ``lookup(kernel, shape, dtype)`` is the runtime fast path
  ``kernels.ops`` consults when a caller does not pass explicit block
  sizes: tuned config if the cache has this bucket, ``None`` (-> the
  hardcoded defaults) otherwise. Any other error (no device, say)
  propagates.

Objectives
----------
``wall``      median wall seconds of the jitted kernel call (the honest
              metric on real TPU hardware).
``roofline``  the analytic estimate alone — deterministic and cheap; the
              right choice for CPU/interpret-mode smoke runs, where wall
              time measures the Pallas interpreter, not the kernel.
``auto``      ``wall`` on TPU; elsewhere ranks by the roofline estimate
              and breaks ties with measured wall time.

The cache location is ``$REPRO_TUNE_CACHE`` when set, else
``<repo>/.autotune.json`` inside the checkout (gitignored);
``repro.roofline.svm_tune`` is the CLI driver that fills it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Callable, Optional

CACHE_VERSION = 1
_ENV_CACHE = "REPRO_TUNE_CACHE"
# src/repro/kernels/autotune.py -> the checkout root
_REPO_ROOT = Path(__file__).resolve().parents[3]

# ~16 MiB/core VMEM; a candidate's double-buffered working set must fit
VMEM_BUDGET_BYTES = 16 * 2 ** 20

DEFAULTS: dict[str, dict[str, int]] = {
    "rbf_gram": {"block_n": 128, "block_m": 128, "block_d": 128},
    "rff_features": {"block_n": 128, "block_m": 128, "block_d": 128},
    "decision": {"block_t": 128, "block_n": 128},
    "multitask_decision": {"block_t": 128, "block_n": 128},
}

# per-axis candidate ladders (powers of two). Lane-mapped axes (the last
# block dimension on TPU) stay >= 128; sublane axes may drop to 64.
_LADDERS: dict[str, dict[str, tuple[int, ...]]] = {
    "rbf_gram": {"block_n": (64, 128, 256, 512),
                 "block_m": (128, 256, 512),
                 "block_d": (128, 256, 512)},
    "rff_features": {"block_n": (64, 128, 256, 512),
                     "block_m": (128, 256, 512),
                     "block_d": (128, 256, 512)},
    "decision": {"block_t": (64, 128, 256, 512),
                 "block_n": (128, 256, 512, 1024)},
    # block_t is the lane axis of this kernel's output block: >= 128
    "multitask_decision": {"block_t": (128, 256, 512),
                           "block_n": (128, 256, 512, 1024)},
}

_DTYPE_BYTES = {"fp32": 4, "bf16": 2}


def _next_pow2(v: int) -> int:
    return 1 << max(int(v) - 1, 0).bit_length()


def _ceil_div(a: int, b: int) -> int:
    return -(-a) // b


# --------------------------------------------------------------- buckets
def shape_bucket(kernel: str, shape: tuple[int, ...]) -> str:
    """Shape -> cache-bucket string: every axis rounded up to a power of
    two, so one tuning run generalizes to its whole pow2 neighbourhood
    (the serving layer already pads batches to pow2 buckets)."""
    axes = {
        "rbf_gram": ("n", "m", "d"),
        "rff_features": ("n", "k", "d"),
        "decision": ("t", "n", "d"),
        "multitask_decision": ("tasks", "t", "w", "d"),
    }[kernel]
    if len(shape) != len(axes):
        raise ValueError(
            f"{kernel} expects a {len(axes)}-axis shape {axes}, got "
            f"{shape}")
    return "_".join(f"{a}{_next_pow2(s)}" for a, s in zip(axes, shape))


def cache_key(device: str, kernel: str, dtype: str,
              shape: tuple[int, ...]) -> str:
    return "|".join((device, kernel, dtype, shape_bucket(kernel, shape)))


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.replace("|", "_")


# ------------------------------------------------------------ candidates
def _block_dims(kernel: str, shape: tuple[int, ...]) -> dict[str, int]:
    """Map each tunable block axis to the shape axis it tiles."""
    if kernel in ("rbf_gram", "rff_features"):
        n, m, d = shape
        return {"block_n": n, "block_m": m, "block_d": d}
    if kernel == "decision":
        t, n, _ = shape
        return {"block_t": t, "block_n": n}
    if kernel == "multitask_decision":
        _, t, w, _ = shape
        return {"block_t": t, "block_n": w}
    raise ValueError(f"unknown tunable kernel {kernel!r}; expected "
                     f"one of {sorted(_LADDERS)}")


def _vmem_bytes(kernel: str, cfg: dict, shape: tuple[int, ...],
                dtype: str) -> int:
    """Per-grid-step VMEM working set (bytes, single-buffered)."""
    es = _DTYPE_BYTES[dtype]
    if kernel == "rbf_gram":
        bn, bm, bd = cfg["block_n"], cfg["block_m"], cfg["block_d"]
        return (bn * bd + bm * bd) * es + (bn * bm + bn + bm) * 4
    if kernel == "rff_features":
        bn, bm, bd = cfg["block_n"], cfg["block_m"], cfg["block_d"]
        return (bn * bd + bd * bm) * es + (bn * bm + bm) * 4
    d = shape[-1]
    bt, bn = cfg["block_t"], cfg["block_n"]
    return (bt * d + bn * d) * es + (bn + bt) * 4


def candidates(kernel: str, shape: tuple[int, ...],
               dtype: str = "fp32") -> list[dict[str, int]]:
    """Feasible tile configs: ladder values clipped to the (pow2-rounded)
    shape, VMEM-budget filtered, defaults always included."""
    dims = _block_dims(kernel, shape)
    ladders = {}
    for axis, ladder in _LADDERS[kernel].items():
        cap = max(_next_pow2(dims[axis]), ladder[0])
        vals = tuple(v for v in ladder if v <= cap) or (ladder[0],)
        ladders[axis] = vals
    out: list[dict[str, int]] = []

    def expand(axes, partial):
        if not axes:
            out.append(dict(partial))
            return
        axis, rest = axes[0], axes[1:]
        for v in ladders[axis]:
            partial[axis] = v
            expand(rest, partial)

    expand(list(ladders), {})
    default = clip_to_candidates(kernel, DEFAULTS[kernel], shape)
    if default not in out:
        out.insert(0, default)
    feasible = [c for c in out
                if 2 * _vmem_bytes(kernel, c, shape, dtype)
                <= VMEM_BUDGET_BYTES]
    return feasible or [default]


def clip_to_candidates(kernel: str, cfg: dict[str, int],
                       shape: tuple[int, ...]) -> dict[str, int]:
    """Clip a config onto the per-shape ladder (the default config for a
    tiny problem clips down to the largest feasible tile)."""
    dims = _block_dims(kernel, shape)
    out = {}
    for axis, ladder in _LADDERS[kernel].items():
        cap = max(_next_pow2(dims[axis]), ladder[0])
        v = min(cfg.get(axis, DEFAULTS[kernel][axis]), cap)
        out[axis] = max(lv for lv in ladder if lv <= max(v, ladder[0]))
    return out


# ------------------------------------------------------ roofline pricing
def roofline_estimate(kernel: str, shape: tuple[int, ...],
                      dtype: str, cfg: dict[str, int], *,
                      device_kind: str) -> dict:
    """Analytic per-call roofline terms for one tile configuration on
    ``device_kind``.

    HBM traffic follows the kernels' actual pipelining: an operand tile
    is re-fetched whenever its block index changes along the grid
    iteration order, so larger output tiles amortize operand streaming
    (the classic tiled-matmul I/O model); dtype sets the operand element
    size (the bf16 payoff). FLOPs are tile-independent.
    """
    es = _DTYPE_BYTES[dtype]
    if kernel == "rbf_gram":
        n, m, d = shape
        bn, bm = cfg["block_n"], cfg["block_m"]
        flops = 2.0 * n * m * d + 8.0 * n * m
        hbm = (_ceil_div(m, bm) * n * d * es      # A re-streamed per j
               + _ceil_div(n, bn) * m * d * es    # B re-streamed per i
               + n * m * 4                        # output written once
               + _ceil_div(m, bm) * n * 4 + _ceil_div(n, bn) * m * 4)
    elif kernel == "rff_features":
        n, k, d = shape
        bn, bm = cfg["block_n"], cfg["block_m"]
        flops = 2.0 * n * k * d + 12.0 * n * k   # matmul + cos epilogue
        hbm = (_ceil_div(k, bm) * n * d * es      # X re-streamed per j
               + _ceil_div(n, bn) * k * d * es    # Omega re-streamed per i
               + n * k * 4                        # features written once
               + _ceil_div(n, bn) * k * 4)        # phase per i
    elif kernel == "decision":
        t, n, d = shape
        bt = cfg["block_t"]
        flops = 2.0 * t * n * d + 10.0 * t * n
        hbm = (t * d * es                          # test tile: reused per i
               + _ceil_div(t, bt) * n * (d * es + 4)  # train+coef per i
               + t * 4)
    elif kernel == "multitask_decision":
        tasks, t, w, d = shape
        bt = cfg["block_t"]
        flops = tasks * (2.0 * t * w * d + 10.0 * t * w)
        hbm = (t * d * es
               + tasks * _ceil_div(t, bt) * w * (d * es + 4)
               + tasks * t * 4)
    else:
        raise ValueError(f"unknown tunable kernel {kernel!r}")
    from repro.roofline.collect import roofline_terms
    terms = roofline_terms(flops=flops, hbm_bytes=hbm,
                           collective_bytes_total=0.0,
                           device_kind=device_kind)
    terms["flops"] = flops
    terms["hbm_bytes"] = hbm
    return terms


# ------------------------------------------------------------ measuring
def _timeit(fn: Callable, warmup: int = 1, iters: int = 3) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _bench_closure(kernel: str, shape: tuple[int, ...], dtype: str,
                   cfg: dict[str, int]) -> Callable:
    """A zero-arg closure running the real ops wrapper with explicit
    blocks (imports deferred: ops imports this module for lookup())."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    if kernel == "rbf_gram":
        n, m, d = shape
        a = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        return lambda: ops.rbf_gram(a, b, gamma=0.5, compute_dtype=dtype,
                                    **cfg)
    if kernel == "rff_features":
        n, k, d = shape
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        omega = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
        phase = jnp.asarray(
            rng.uniform(0, 2 * np.pi, size=k).astype(np.float32))
        scale = float(np.sqrt(2.0 / k))
        return lambda: ops.rff_features(x, omega, phase, scale=scale,
                                        compute_dtype=dtype, **cfg)
    if kernel == "decision":
        t, n, d = shape
        xt = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        xr = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        coef = jnp.asarray(rng.normal(size=n).astype(np.float32))
        return lambda: ops.decision(xt, xr, coef, 0.0, gamma=0.5,
                                    compute_dtype=dtype, **cfg)
    if kernel == "multitask_decision":
        tasks, t, w, d = shape
        xt = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        sv = jnp.asarray(rng.normal(size=(tasks, w, d)).astype(np.float32))
        coef = jnp.asarray(rng.normal(size=(tasks, w)).astype(np.float32))
        return lambda: ops.multitask_decision(xt, sv, coef, gamma=0.5,
                                              compute_dtype=dtype, **cfg)
    raise ValueError(f"unknown tunable kernel {kernel!r}")


# ------------------------------------------------------------- hillclimb
@dataclasses.dataclass
class Evaluation:
    config: dict[str, int]
    roofline_s: float
    wall_s: Optional[float]
    score: tuple


@dataclasses.dataclass
class TuneResult:
    kernel: str
    shape: tuple[int, ...]
    dtype: str
    objective: str
    best: Evaluation
    default: Evaluation
    trace: list[Evaluation]

    @property
    def config(self) -> dict[str, int]:
        return self.best.config


def _resolve_objective(objective: str) -> str:
    if objective != "auto":
        return objective
    import jax
    return "wall" if jax.default_backend() == "tpu" else "combined"


def _score(objective: str, roofline_s: float,
           wall_s: Optional[float]) -> tuple:
    if objective == "wall":
        return (wall_s,)
    if objective == "roofline":
        return (roofline_s,)
    # combined: roofline leads (2 significant digits), wall breaks ties
    rounded = float(f"{roofline_s:.1e}") if roofline_s > 0 else 0.0
    return (rounded, wall_s if wall_s is not None else 0.0)


def _neighbours(cfg: dict[str, int], space: list[dict[str, int]]
                ) -> list[dict[str, int]]:
    """Single-axis x2 / /2 steps that land inside the candidate space."""
    out = []
    for axis, v in cfg.items():
        for nv in (v * 2, v // 2):
            cand = dict(cfg, **{axis: nv})
            if cand in space and cand not in out:
                out.append(cand)
    return out


def tune(kernel: str, shape: tuple[int, ...], *, dtype: str = "fp32",
         budget: int = 12, objective: str = "auto",
         warmup: int = 1, iters: int = 3) -> TuneResult:
    """Hillclimb the tile configuration for one (kernel, shape, dtype).

    The roofline terms price the configuration on the attached device,
    which is an error where that device has no entry in
    ``repro.roofline.collect.PEAKS`` (a CPU).

    Starts from the (shape-clipped) default, evaluates its single-axis
    x2 / /2 neighbours, moves to the strict best, and repeats until no
    neighbour improves or ``budget`` configurations have been evaluated.
    The default is always evaluated first, so ``result.best`` is never
    worse than the default under the chosen objective.
    """
    obj = _resolve_objective(objective)
    kind = device_kind()
    space = candidates(kernel, shape, dtype)
    measure_wall = obj in ("wall", "combined")

    evaluated: dict[tuple, Evaluation] = {}

    def key(cfg):
        return tuple(sorted(cfg.items()))

    def evaluate(cfg) -> Evaluation:
        k = key(cfg)
        if k in evaluated:
            return evaluated[k]
        roofline_s = roofline_estimate(
            kernel, shape, dtype, cfg,
            device_kind=kind)["t_total_est_s"]
        wall = (_timeit(_bench_closure(kernel, shape, dtype, cfg),
                        warmup=warmup, iters=iters)
                if measure_wall else None)
        ev = Evaluation(config=dict(cfg), roofline_s=roofline_s,
                        wall_s=wall, score=_score(obj, roofline_s, wall))
        evaluated[k] = ev
        return ev

    start = clip_to_candidates(kernel, DEFAULTS[kernel], shape)
    default_ev = evaluate(start)
    best = default_ev
    while len(evaluated) < budget:
        moved = False
        for cand in _neighbours(best.config, space):
            if len(evaluated) >= budget:
                break
            ev = evaluate(cand)
            if ev.score < best.score:
                best = ev
                moved = True
        if not moved:
            break
    return TuneResult(kernel=kernel, shape=tuple(shape), dtype=dtype,
                      objective=obj, best=best, default=default_ev,
                      trace=list(evaluated.values()))


# ----------------------------------------------------------- disk cache
def default_cache_path() -> str:
    """``$REPRO_TUNE_CACHE`` when set, else a file inside the checkout:
    nothing outside it is read unless a caller names it."""
    return os.environ.get(_ENV_CACHE) or str(_REPO_ROOT / ".autotune.json")


class TuningCache:
    """Versioned on-disk tuning cache.

    JSON schema (version 1)::

        {"version": 1,
         "entries": {"<device>|<kernel>|<dtype>|<bucket>": {
             "config": {"block_n": 256, ...},
             "objective": "wall", "wall_s": ..., "roofline_s": ...,
             "n_evaluated": 7}}}

    ``load`` NEVER raises on a bad file: a missing, unreadable,
    corrupted, or version-mismatched cache yields an empty cache, which
    makes every lookup fall back to the hardcoded defaults.
    """

    def __init__(self, entries: Optional[dict] = None):
        self.entries: dict[str, dict] = dict(entries or {})

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        try:
            with open(path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
                return cls()
            entries = raw.get("entries")
            if not isinstance(entries, dict):
                return cls()
            good = {k: v for k, v in entries.items()
                    if isinstance(v, dict)
                    and isinstance(v.get("config"), dict)}
            return cls(good)
        except (OSError, ValueError):
            return cls()

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": self.entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    def get(self, key: str) -> Optional[dict]:
        rec = self.entries.get(key)
        return dict(rec["config"]) if rec else None

    def put(self, key: str, result: TuneResult) -> None:
        self.entries[key] = {
            "config": dict(result.best.config),
            "objective": result.objective,
            "wall_s": result.best.wall_s,
            "roofline_s": result.best.roofline_s,
            "default_wall_s": result.default.wall_s,
            "default_roofline_s": result.default.roofline_s,
            "n_evaluated": len(result.trace),
        }


# ---------------------------------------------------- runtime fast path
_runtime_cache: Optional[TuningCache] = None
_runtime_path: Optional[str] = None


def reset() -> None:
    """Drop the loaded in-process cache so the next lookup reloads from
    disk (tests; or after an external tune run). A path pinned with
    ``set_cache_path`` stays pinned."""
    global _runtime_cache
    _runtime_cache = None


def set_cache_path(path: Optional[str]) -> None:
    """Pin the runtime cache to ``path`` (``None`` -> back to default
    resolution) and reload lazily on next lookup."""
    global _runtime_path
    reset()
    _runtime_path = path


def _runtime(path: Optional[str] = None) -> TuningCache:
    global _runtime_cache
    if _runtime_cache is None:
        p = path or _runtime_path or default_cache_path()
        _runtime_cache = TuningCache.load(p)
    return _runtime_cache


def lookup(kernel: str, shape: tuple[int, ...],
           dtype: str = "fp32") -> Optional[dict[str, int]]:
    """Tuned config for this (device, kernel, dtype, shape bucket) or
    ``None`` when untuned (callers then use ``DEFAULTS``). A missing or
    unreadable cache file is "untuned" (``TuningCache.load``); any other
    error propagates."""
    cache = _runtime()
    if not cache.entries:
        return None
    return cache.get(cache_key(device_kind(), kernel, dtype, shape))


def resolve_blocks(kernel: str, shape: tuple[int, ...], dtype: str,
                   given: dict[str, Optional[int]]) -> dict[str, int]:
    """Merge caller-specified block sizes over tuned-or-default values:
    explicit args always win; ``None`` slots fill from the tuning cache
    when this bucket was tuned, else from ``DEFAULTS``."""
    tuned = (lookup(kernel, shape, dtype)
             if any(v is None for v in given.values()) else None)
    base = DEFAULTS[kernel]
    out = {}
    for k, v in given.items():
        if v is not None:
            out[k] = int(v)
        elif tuned and k in tuned:
            out[k] = int(tuned[k])
        else:
            out[k] = base[k]
    return out
