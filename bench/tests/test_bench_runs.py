"""Each traffic generator end to end at a tiny size on the CPU, through
the harness's test-only path (``allow_cpu``); the real command refuses
the CPU. The control and the planted faults make ``correct`` false."""
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

from bench import control, names, run

KEYS = ("correct", "attempted", "failed", "metrics", "device", "checks")


def tiny(cell: str) -> dict:
    spec = names.resolve(cell)
    shape = spec["config"]["data"]
    if "n_per_class" in shape:
        if "rate_rps" in spec["params"]:
            # every class, and a held-out pool that holds the largest
            # request (128 rows)
            shape["n_per_class"] = 100
        else:
            shape["n_per_class"], shape["n_classes"] = 60, 3
    if "n_rows" in shape:
        shape["n_rows"] = 1200
    if "rate_rps" in spec["params"]:
        spec["params"]["rate_rps"] = 40.0
        spec["params"]["ladder_log2"] = 8
        # a few requests only: enough of them ask for values to compare
        spec["params"]["values_share"] = 0.5
    return spec


def one_run(cell, seed=2**31 + 3, seconds=0.5):
    spec = tiny(cell)
    devices = run.start(spec["cell"]["chips"], allow_cpu=True)
    out = io.StringIO()
    with redirect_stdout(out):
        run.emit(run.run_cell(spec, seed=seed, seconds=seconds, trace=False,
                              devices=devices, t_start=0.0))
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["pavia_ovo.fit", "ijcnn1.fit",
                                  "pavia_ovo.serve_poisson",
                                  "pavia_ovo.serve_overload"])
def test_last_line_is_well_formed(cell):
    line = one_run(cell)
    assert list(line)[:len(KEYS) - 1] == list(KEYS[:-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    e2e = {m["name"] for m in json.loads(
        (names.ROOT / "BENCHMARK.json").read_text())["end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert e2e <= set(line["metrics"]) <= set(run.UNITS)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_the_command_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pavia_ovo.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=names.ROOT, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == ""


def test_the_command_refuses_a_tree_without_the_program(tmp_path):
    shutil.copytree(names.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(names.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pavia_ovo.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _control(cell, *flags):
    out = io.StringIO()
    with redirect_stdout(out):
        assert control.main(["--workload", cell, "--seeds", "5",
                             "--seconds", "0.5", *flags],
                            allow_cpu=True, spec=tiny(cell)) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def restore():
    from bench import system
    from repro.core import smo
    saved = dict(vars(system)), smo._sharded_selection
    yield
    vars(system).update(saved[0])
    smo._sharded_selection = saved[1]
    smo._sharded_smo_program.cache_clear()


@pytest.mark.parametrize("cell,flags", [
    ("pavia_ovo.fit", ("--control", "1")),
    ("pavia_ovo.fit", ("--fault", "unchanged")),
    ("pavia_ovo.fit", ("--fault", "half")),
    ("pavia_ovo.fit", ("--fault", "altered_bias")),
    ("pavia_ovo.serve_poisson", ("--control", "1")),
    ("pavia_ovo.serve_poisson", ("--fault", "altered_value")),
    ("pavia_ovo.serve_poisson", ("--fault", "altered_label")),
    ("pavia_ovo.serve_poisson", ("--fault", "wrong_gamma")),
    ("pavia_ovo.serve_poisson", ("--fault", "swapped_pair")),
    ("pavia_ovo.serve_poisson", ("--fault", "dropped_sv")),
    ("pavia_ovo.serve_poisson", ("--fault", "dropped_bias")),
    ("pavia_ovo.serve_overload", ("--control", "1")),
])
def test_control_and_faults_are_not_correct(cell, flags, restore):
    line = _control(cell, *flags)
    assert line["correct"] is False, line["checks"]


def test_sharded_fit_without_the_exchange_is_not_correct(restore):
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    spec = tiny("ijcnn1.fit_data4")
    sound = one_run("ijcnn1.fit_data4")
    assert sound["correct"] is True, sound["checks"]
    out = io.StringIO()
    with redirect_stdout(out):
        control.main(["--workload", "ijcnn1.fit_data4", "--seeds", "5",
                      "--seconds", "0.5", "--fault", "no_exchange"],
                     allow_cpu=True, spec=spec)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]


def test_the_pacer_sends_every_request_once_in_order():
    import time

    import numpy as np

    from bench.generators import open_loop
    arrivals = np.sort(np.random.default_rng(3).uniform(0.0, 0.3, 200))
    pacer = open_loop.Pacer(arrivals)
    try:
        t0 = time.perf_counter()
        pacer.start(t0)
        got, at = [], []
        for i in pacer.indices():
            got.append(i)
            at.append(time.perf_counter() - t0)
    finally:
        pacer.close()
    assert got == list(range(len(arrivals)))
    # never early; the pacer's own lateness comes back after the last
    assert (np.asarray(at) >= arrivals).all()
    assert 0.0 <= pacer.late[0] <= pacer.late[1] < 1.0
    assert pacer.late[2] in arrivals and pacer.late[3] >= 0
    assert pacer.proc.returncode == 0
