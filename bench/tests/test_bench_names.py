"""Name resolution, and BENCHMARK.json against the benchmark's files."""
import json
import re

import pytest

from bench import names

SPEC = json.loads((names.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec = names.resolve(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    for key in ("config", "traffic", "chips", "why"):
        assert spec["cell"][key] == entry[key]
    assert spec["config"]["name"] == entry["config"]
    assert names.generator(spec["generator"]).run


def test_configs_are_their_files():
    for c in SPEC["configs"]:
        cfg = json.loads((names.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/configs/")


def test_every_per_layer_metric_has_one_reader():
    readers = names.metric_readers()
    for m in SPEC["per_layer"]:
        unit, read = readers[m["name"]]
        assert unit == m["unit"]
    for unit, read in readers.values():
        assert read({"kind": "neither"}) is None


def test_every_cell_file_resolves():
    for path in sorted((names.BENCH / "cells").glob("*.json")):
        spec = names.resolve(path.stem)
        assert len(spec["cell"]["why"]) <= 200
        assert spec["cell"]["chips"] in (1, 4)


def test_names_and_units_keep_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
            for key in ("why", "source", "layer"):
                if key in entry and group != "end_to_end":
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            owner = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
            assert w in owner.get("workloads", CELLS)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_unknown_and_malformed_names_are_refused():
    with pytest.raises(KeyError):
        names.cell("no_such_cell")
    with pytest.raises(ValueError):
        names.cell("../BENCHMARK")
    with pytest.raises(ValueError):
        names.generator("Bad-Name")
