#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/repro``)
beside ``bench/``, on a machine with as many TPU chips as the cell asks
for. Everything is found by name (``bench/names.py``): the cell file
``bench/cells/<cell>.json`` names its configuration and traffic mix,
the mix its generator, and every file in ``bench/metrics/`` is one
per-layer metric.

A run makes its data from ``--seed``, warms every program the window
runs (set-up, reported as ``setup_s``), drives the system for
``--seconds``, then checks what the window produced against the
float64 reference in ``bench/reference.py``. With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` the
window is traced with the JAX profiler and the result holds the
per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced) and last ``checks``, each compared number beside its limit;
the same numbers end standard error. A line before it holds the run's
own readings (``notes``: latency percentiles, how late the load ran).
Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits 3; without
the program beside it, 2.

The persistent compilation cache is ``<checkout>/.jax_cache``, so only
the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
UNITS = {"fit_s": "s", "serve_p99_ms": "ms", "serve_rows_per_s": "rows/s",
         "setup_s": "s"}


class Context:
    """What a generator gets: the cell's configuration and parameters,
    the run's seed and length, the devices, and the window's hooks (``window_open``, ``tracing``, ``stop_tracing``,
    ``window_close``)."""

    def __init__(self, spec: dict, *, seed: int, seconds: float,
                 trace: bool, devices, t_start: float,
                 control: bool = False):
        self.config, self.params = spec["config"], spec["params"]
        self.limits = spec["cell"].get("limits", {})
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.control = devices, control
        self.t_start = t_start
        self.setup_s = self.window_s = None
        self.trace_dir = None
        self._tracing = False
        self.memory_peak_bytes = None
        self._compiles = None

    def window_open(self) -> None:
        from bench.compile_count import CompileCounter
        self.setup_s = time.perf_counter() - self.t_start
        self._t0 = time.perf_counter()
        self._compiles = CompileCounter().__enter__()
        if self.trace:
            import jax
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True

    def tracing(self) -> bool:
        return self._tracing

    def stop_tracing(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    def window_close(self) -> None:
        self.window_s = time.perf_counter() - self._t0
        self.stop_tracing()
        self._compiles.__exit__(None, None, None)
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)

    @property
    def window_compiles(self) -> int:
        return self._compiles.count


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, control: bool = False) -> dict:
    """One run of a cell in this process; returns the result object."""
    from bench import names, xtrace
    ctx = Context(spec, seed=seed, seconds=seconds, trace=trace,
                  devices=devices, t_start=t_start, control=control)
    out = names.generator(spec["generator"]).run(ctx)
    checks = list(out.checks) + [("window_compiles", ctx.window_compiles,
                                  0)]
    correct = all(isinstance(v, (int, float)) and v <= lim
                  for _, v, lim in checks)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        summary = xtrace.reduce_dir(ctx.trace_dir, len(devices))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        layer = dict(out.layer, trace=summary, chips=len(devices),
                     device_kind=d.device_kind)
        metrics = {}
        for name, (unit, read) in names.metric_readers().items():
            v = read(layer)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown=summary["breakdown"])
    else:
        # the cell's file names the end-to-end metrics it reports
        keep = spec["cell"].get("end_to_end", list(out.metrics))
        e2e = {k: v for k, v in out.metrics.items() if k in keep}
        e2e["setup_s"] = ctx.setup_s
        result.update(metrics={k: {"value": _finite(v), "unit": UNITS[k]}
                               for k, v in e2e.items()}, device=device)
    result["checks"] = {n: {"value": _finite(v) if _finite(v) is not None
                            else str(v), "limit": lim}
                        for n, v, lim in checks}
    result["_notes"] = dict(out.notes, metrics=out.metrics,
                            window_s=ctx.window_s,
                            setup_s=ctx.setup_s)
    return result


def emit(result: dict) -> None:
    notes = json.dumps({"notes": result.pop("_notes", {})})
    # the run's own readings (latency percentiles, how late the load
    # ran) on an earlier line of both streams
    print(notes, flush=True)
    print(notes, file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start(chips: int, *, allow_cpu: bool = False):
    """Put the program on the path, point the compile cache into the
    checkout, and return the devices the cell uses, or None when this
    machine lacks them."""
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro in {ROOT}; run from a checkout of the "
              f"repo", file=sys.stderr)
        raise SystemExit(2)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        print(f"bench: no TPU (JAX reports {devices[0].platform!r}); the "
              f"benchmark measures the chip only", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


def main(argv=None, *, allow_cpu: bool = False) -> int:
    args = parse(argv)
    # the package, not its directory, goes on the path
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path.insert(0, str(ROOT))
    from bench import names
    spec = names.resolve(args.workload)
    devices = start(int(spec["cell"]["chips"]), allow_cpu=allow_cpu)
    if devices is None:
        return 3
    emit(run_cell(spec, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices,
                  t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
