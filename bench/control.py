#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison with the reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--control 1] [--fault <one of FAULTS>]

Runs the cell once per seed in one process (programs compile once) and
prints one JSON line per run: the numbers compared, each beside its
limit, and ``correct``. ``--control 1`` runs the program's own
lower-precision path (the bfloat16 Gram, ``bench/system.py``), which
the comparison has to fail. ``--fault`` plants one fault in the timed
path's output before the check:

* ``unchanged``: every fit returns its starting state, no multipliers;
* ``half``: every fit sees every other row only;
* ``no_exchange``: the sharded fit's working-set selection takes the
  first chip's candidates and none of the others' (the chips still
  agree, so the loop ends as it would);
* ``altered_bias``: the first task's bias moved by 0.05;
* ``altered_value`` / ``altered_label``: the first served value moved
  by 1e-3, or the first served label replaced by another class;
* ``wrong_gamma``, ``swapped_pair``, ``dropped_sv``, ``dropped_bias``:
  the packed model, served and checked alike, with its kernel's gamma
  doubled, the first task's vote routing reversed, the last support
  vector of the first bank's first task left out, or that task's bias
  set to 0.

The benchmark's own runs (``bench/run.py``) never run any of this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
FAULTS = ("unchanged", "half", "no_exchange", "altered_bias",
          "altered_value", "altered_label", "wrong_gamma", "swapped_pair",
          "dropped_sv", "dropped_bias")


def _repacked(fault: str, packed):
    """A copy of the packed model with ``fault`` planted in it."""
    import dataclasses
    import numpy as np
    if fault == "wrong_gamma":
        k = packed.kernel
        k = (k._replace(gamma=2 * k.gamma) if hasattr(k, "_replace")
             else dataclasses.replace(k, gamma=2 * k.gamma))
        return dataclasses.replace(packed, kernel=k)
    if fault == "swapped_pair":
        pairs = np.array(packed.pairs, copy=True)
        pairs[0] = pairs[0, ::-1]
        return dataclasses.replace(packed, pairs=pairs)
    g = packed.buckets[0]
    if fault == "dropped_sv":
        k = int(g.sv_counts[0]) - 1
        coef = np.array(g.sv_coef, copy=True)
        x = np.array(g.sv_x, copy=True)
        coef[0, k], x[0, k] = 0, 0
        counts = np.array(g.sv_counts, copy=True)
        counts[0] = k
        g = g._replace(sv_coef=coef, sv_x=x, sv_counts=counts)
    else:
        b = np.array(g.b, copy=True)
        b[0] = 0
        g = g._replace(b=b)
    return dataclasses.replace(packed, buckets=(g,) + packed.buckets[1:])


def plant(fault: str) -> None:
    """Break the timed path's output in ``bench.system`` (this process
    only)."""
    import numpy as np
    from bench import system
    banks, svc, predictor = system.banks, system.svc, system.predictor
    pack = system.pack
    if fault in ("wrong_gamma", "swapped_pair", "dropped_sv",
                 "dropped_bias"):
        system.pack = lambda clf: _repacked(fault, pack(clf))
    elif fault == "unchanged":
        def no_multipliers(packed):
            m = banks(packed)
            m["banks"] = [(ids, x[:, :0], c[:, :0], np.zeros_like(b),
                           np.zeros_like(n)) for ids, x, c, b, n in m["banks"]]
            return m
        system.banks = no_multipliers
    elif fault == "half":
        class Half:
            def __init__(self, inner):
                self.inner = inner

            def fit(self, x, y):
                return self.inner.fit(x[::2], y[::2])
        system.svc = lambda *a, **k: Half(svc(*a, **k))
    elif fault == "no_exchange":
        import jax
        from repro.core import smo

        def first_chip_only(f, alpha, y, mask, lo, hi, axis):
            b_up, i_up, b_low, i_low = smo._selection(f, alpha, y, mask,
                                                      lo, hi)
            base = jax.lax.axis_index(axis) * f.shape[0]
            got = jax.lax.all_gather(
                jax.numpy.stack([b_up, b_low]), axis)[0]
            idx = jax.lax.all_gather(
                jax.numpy.stack([base + i_up, base + i_low]), axis)[0]
            return got[0], idx[0], got[1], idx[1]
        smo._sharded_selection = first_chip_only
        smo._sharded_smo_program.cache_clear()
    elif fault == "altered_bias":
        def moved(packed):
            m = banks(packed)
            ids, x, c, b, n = m["banks"][0]
            m["banks"][0] = (ids, x, c, b + np.where(
                np.arange(len(b)) == 0, 0.05, 0.0).astype(b.dtype), n)
            return m
        system.banks = moved
    elif fault in ("altered_value", "altered_label"):
        def broken(*a, **k):
            pred = predictor(*a, **k)
            decide, decode = pred.decision_values, pred.decode

            def values(xt):
                df = decide(xt)
                if fault == "altered_value":
                    df[0, 0] += 1e-3
                return df

            def labels(df, op="predict"):
                out = decode(df, op)
                if fault == "altered_label" and op == "predict":
                    out = out.copy()
                    classes = pred.model.classes
                    out[0] = classes[(np.searchsorted(classes, out[0]) + 1)
                                     % len(classes)]
                return out
            pred.decision_values, pred.decode = values, labels
            return pred
        system.predictor = broken
    else:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")


def main(argv=None, *, allow_cpu: bool = False, spec=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path.insert(0, str(ROOT))
    from bench import names, run
    spec = spec or names.resolve(args.workload)
    devices = run.start(int(spec["cell"]["chips"]), allow_cpu=allow_cpu)
    if devices is None:
        return 3
    if args.fault:
        plant(args.fault)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(spec, seed=seed, seconds=args.seconds, trace=False,
                         devices=devices, t_start=t_start,
                         control=bool(args.control))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "fault": args.fault,
                          "correct": r["correct"], "checks": r["checks"],
                          "metrics": r["metrics"],
                          "notes": r["_notes"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
