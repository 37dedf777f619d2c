"""Resolution of the benchmark's names to its files.

Everything is found by name: a cell ``bench/cells/<cell>.json`` names
its configuration ``bench/configs/<config>.json`` and its traffic mix
``bench/traffic/<mix>.json``; the mix names its generator
``bench/generators/<generator>.py``; every file in ``bench/metrics/``
is one per-layer metric, named by its file. A new cell, configuration,
mix or metric is a new file.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
MODULE = re.compile(r"^[a-z][a-z0-9_]{0,63}$")


def _json(kind: str, name: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (BENCH / kind).glob("*.json"))
        raise KeyError(f"no {kind[:-1]} {name!r}; known: {known}")
    return json.loads(path.read_text())


def cell(name: str) -> dict:
    return _json("cells", name)


def config(name: str) -> dict:
    return _json("configs", name)


def mix(name: str) -> dict:
    return _json("traffic", name)


def generator(name: str):
    """The generator module ``bench.generators.<name>``."""
    if not MODULE.match(name):
        raise ValueError(f"not a generator name: {name!r}")
    return importlib.import_module(f"bench.generators.{name}")


def metric_readers() -> dict:
    """{metric name: read(ctx) -> float | None} for every file in
    ``bench/metrics/``; a reader that finds nothing returns None."""
    readers = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        name = path.name[:-3]
        if name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers[name] = (mod.UNIT, mod.read)
    return readers


def resolve(workload: str) -> dict:
    """The cell with its configuration, mix and merged parameters: the
    mix's ``params`` overlaid by the cell's own."""
    c = cell(workload)
    m = mix(c["traffic"])
    params = dict(m.get("params", {}))
    params.update(c.get("params", {}))
    return {"name": workload, "cell": c, "config": config(c["config"]),
            "mix": m, "generator": m["generator"], "params": params}
