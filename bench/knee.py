#!/usr/bin/env python3
"""Sweep of offered rates for a serving cell, to find its knee once.

    python3 bench/knee.py --workload pavia_ovo.serve_poisson --seed 7 \
        --seconds 10 --rates 100,200,400,800 [--set gc_freeze=false]

Runs the cell at each offered rate in one process and prints one JSON
line per rate: p50 and p99 latency, rows per second completed against
rows per second offered, and how far the median latency of the last
fifth of the requests lies above that of the first fifth. The knee is
the highest rate at which that growth stays near zero: the backlog does
not grow over the window. The cell's file then offers a fixed rate at
about four fifths of it; the benchmark's runs never sweep.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, *, allow_cpu: bool = False, spec=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated rps, one run each (repeat a "
                         "rate to see how far its runs spread)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON",
                    help="override one of the mix's parameters")
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path.insert(0, str(ROOT))
    from bench import names, run
    base = spec or names.resolve(args.workload)
    for kv in args.set:
        key, _, value = kv.partition("=")
        base["params"][key] = json.loads(value)
    devices = run.start(int(base["cell"]["chips"]), allow_cpu=allow_cpu)
    if devices is None:
        return 3
    for rate in (float(r) for r in args.rates.split(",")):
        spec = copy.deepcopy(base)
        spec["params"]["rate_rps"] = rate
        r = run.run_cell(spec, seed=args.seed, seconds=args.seconds,
                         trace=False, devices=devices,
                         t_start=time.perf_counter())
        notes = r["_notes"]
        print(json.dumps({
            "rate_rps": rate, "correct": r["correct"],
            "p50_ms": notes["p50_ms"], "p95_ms": notes["p95_ms"],
            "p99_ms": notes["p99_ms"],
            "rows_per_s": notes["metrics"]["serve_rows_per_s"],
            "offered_rows_per_s": notes["rows"] / args.seconds,
            "lag_growth_ms": notes["lag_growth_ms"],
            "rows_per_batch": notes["rows_per_batch"],
            "submit_late_p99_ms": notes["submit_late_p99_ms"],
            "pacer_late_max_ms": notes["pacer_late_max_ms"],
            "setup_s": notes["setup_s"], "set": args.set}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
