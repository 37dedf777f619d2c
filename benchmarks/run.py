"""Benchmark runner — one section per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Emits ``name,us_per_call,derived`` CSV lines per benchmark, and writes
every executed suite's records to a repo-root ``BENCH_<suite>.json``
(stable sorted-keys schema collected through ``common.set_sink`` — the
machine-consumable trajectory successive commits diff):
  Table III & V -> bench_binary      (binary SMO vs GD training time)
  Table IV      -> bench_multiclass  (9-class OvO parallel vs sequential,
                                      + bucketed-vs-padded scheduler JSON)
  Table VI      -> bench_portability (same program jit vs eager)
  kernels       -> bench_kernels     (hot-spot roofline estimates)
  beyond-paper  -> bench_large_n     (chunked-engine large-n trajectory,
                                      JSON lines; --only large_n — also
                                      runs the approx-vs-exact sweep)
  beyond-paper  -> --only approx     (Nystrom/RFF accuracy-vs-rank and
                                      wall-clock vs the exact SMO, plus
                                      a million-sample approx-only
                                      point; --quick is the CI parity
                                      smoke at small n)
  beyond-paper  -> --only scheduler  (bucketed-vs-padded multiclass
                                      scheduler JSON alone; CI smoke)
  beyond-paper  -> bench_sharded     (single-problem strong scaling vs
                                      shard count, JSON lines; --only
                                      sharded — needs a multi-device
                                      process, e.g. XLA_FLAGS=
                                      --xla_force_host_platform_device_count=8)
  beyond-paper  -> bench_svr         (epsilon-SVR SMO vs projected-GD
                                      wall time + MSE, JSON lines;
                                      --only svr)
  beyond-paper  -> bench_serving     (batched Predictor vs per-call
                                      engine serving, requests/s at
                                      batch {1, 32, 256}, JSON lines;
                                      --only serving)
  beyond-paper  -> bench_serving_load (open-loop Poisson arrivals vs the
                                      dynamic-batching service: p50/p99
                                      latency + sustained requests/s per
                                      offered rate, dynamic vs
                                      per-request dispatch, plus the
                                      fp16/bf16 quantization gate;
                                      --only serving_load — --quick is
                                      the CI smoke asserting the
                                      speedup + accuracy gates)
  beyond-paper  -> bench_cascade     (hierarchical cascade training:
                                      wall clock / accuracy / KKT
                                      certificate vs shard count, JSON
                                      lines; --only cascade — --quick
                                      is the CI parity smoke)
  beyond-paper  -> tile_sweep        (autotuner tuned-vs-default tile
                                      configs for the Pallas kernels,
                                      JSON lines; part of the kernels
                                      section, or --only tile_sweep for
                                      the sweep alone; CI smoke uses
                                      --quick --only tile_sweep)
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks import common

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_suite(name: str, fn) -> None:
    """Run one suite with the record sink attached; write the collected
    records to ``<repo>/BENCH_<name>.json`` (skipped when a suite emits
    nothing, e.g. on an early error path)."""
    records: list = []
    common.set_sink(records)
    try:
        fn()
    finally:
        common.set_sink(None)
    if not records:
        return
    path = os.path.join(_REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump({"bench": name, "records": records}, f,
                  indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {len(records)} records -> {path}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="drop the largest sample sizes")
    ap.add_argument("--only", default="",
                    help="comma list: binary,multiclass,portability,"
                         "kernels; opt-in extras: large_n,approx,"
                         "scheduler,sharded,svr,serving,serving_load,"
                         "tile_sweep,cascade")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable(_REPO_ROOT)

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")

    from benchmarks import (bench_binary, bench_kernels, bench_large_n,
                            bench_multiclass, bench_portability)
    if args.quick:
        bench_binary.GD_STEPS = 300
        bench_multiclass.GD_STEPS = 300

    if only is None or "binary" in only:
        _run_suite("binary", bench_binary.main)
    if only is None or "multiclass" in only:
        def _multiclass():
            bench_multiclass.main()
            bench_multiclass.bucketed(quick=args.quick)
            if not args.quick:
                bench_multiclass.scaling()
        _run_suite("multiclass", _multiclass)
    if only is not None and "scheduler" in only:
        # the bucketed-vs-padded JSON comparison alone (CI smoke)
        _run_suite("scheduler",
                   lambda: bench_multiclass.bucketed(quick=args.quick))
    if only is None or "portability" in only:
        _run_suite("portability", bench_portability.main)
    if only is None or "kernels" in only:
        def _kernels():
            bench_kernels.main()
            bench_kernels.tile_sweep(quick=args.quick)
        _run_suite("kernels", _kernels)
    if only is not None and "tile_sweep" in only:
        # the autotuner tuned-vs-default JSON alone (CI smoke)
        _run_suite("tile_sweep",
                   lambda: bench_kernels.tile_sweep(quick=args.quick))
    if only is not None and "large_n" in only:
        # opt-in: minutes-long at full size (JSON lines, not CSV)
        def _large_n():
            bench_large_n.main(quick=args.quick)
            bench_large_n.approx_sweep(quick=args.quick)
        _run_suite("large_n", _large_n)
    if only is not None and "approx" in only:
        # opt-in: the approx-vs-exact sweep alone (CI smoke: --quick
        # asserts the small-n accuracy parity gate)
        _run_suite("approx",
                   lambda: bench_large_n.approx_sweep(quick=args.quick))
    if only is not None and "sharded" in only:
        # opt-in: single-problem strong scaling over forced host devices
        from benchmarks import bench_sharded
        _run_suite("sharded", lambda: bench_sharded.main(quick=args.quick))
    if only is not None and "svr" in only:
        # opt-in: the regression analog of the SMO-vs-GD comparison
        from benchmarks import bench_svr
        _run_suite("svr", lambda: bench_svr.main(quick=args.quick))
    if only is not None and "cascade" in only:
        # opt-in: cascade shard-solve-reduce scaling (CI smoke: --quick
        # asserts the certificate + accuracy parity gate)
        from benchmarks import bench_cascade
        _run_suite("cascade", lambda: bench_cascade.main(quick=args.quick))
    if only is not None and "serving" in only:
        # opt-in: batched Predictor vs the per-call engine serving path
        from benchmarks import bench_serving
        _run_suite("serving", lambda: bench_serving.main(quick=args.quick))
    if only is not None and "serving_load" in only:
        # opt-in: open-loop Poisson load on the dynamic-batching service
        # (asserts the batching speedup + quantization accuracy gates)
        from benchmarks import bench_serving_load
        _run_suite("serving_load",
                   lambda: bench_serving_load.main(quick=args.quick))


if __name__ == "__main__":
    main()
