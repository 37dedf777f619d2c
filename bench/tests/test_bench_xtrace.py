"""The trace reduction, on hand-made planes and on a small trace
recorded on a TPU v5e (``data/``)."""
import os

import pytest

from bench import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_idle_collectives_and_gaps_by_hand():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [("XLA Ops", [("fusion.1", 0, 2 * ms),
                                        ("all-reduce.3", 1 * ms, 2 * ms),
                                        ("fusion.1", 6 * ms, 1 * ms)]),
                           ("Steps", [("0", 0, 10 * ms)])]),
        ("/device:TPU:1", [("XLA Ops", [("fusion.1", 0, 1 * ms)])]),
        ("/host:CPU", [("python", [("fit", 0, 8 * ms),
                                   ("certify", 8 * ms, 2 * ms),
                                   ("unrelated", 0, 10 * ms)])]),
    ]
    r = xtrace.reduce_planes(planes, 2)
    assert r["window_s"] == pytest.approx(0.010)
    # chip 0 busy [0, 3) and [6, 7); chip 1 busy [0, 1)
    assert r["busy_s_per_device"] == pytest.approx([0.004, 0.001])
    assert r["busy_s"] == pytest.approx(0.0025)
    assert r["collective_s_per_device"] == pytest.approx([0.002, 0.0])
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.002)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["fit", pytest.approx(0.009)]       # chip 1, [1, 10)
    assert ["fit", pytest.approx(0.003)] in gaps          # chip 0, [3, 6)
    assert ["certify", pytest.approx(0.003)] in gaps      # chip 0, [7, 10)
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_only_the_cells_chips_count():
    planes = [("/device:TPU:0", [("XLA Ops", [("a", 0, 10)])]),
              ("/device:TPU:1", [("XLA Ops", [("a", 0, 30)])])]
    assert xtrace.reduce_planes(planes, 1)["busy_s_per_device"] == [1e-8]


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        xtrace.reduce_planes([("/host:CPU", [("python", [("fit", 0, 5)])])], 1)



def test_a_recorded_tpu_trace(tmp_path):
    """A 0.3-s window of ``pavia_ovo.serve_poisson`` traced on a TPU v5e:
    the reduction reads its device and host spans and gives back the
    numbers that run reported."""
    import gzip
    import json
    raw = tmp_path / "serve.xplane.pb"
    with gzip.open(os.path.join(DATA, "serve.xplane.pb.gz"), "rb") as f:
        raw.write_bytes(f.read())
    with open(os.path.join(DATA, "serve.result.json")) as f:
        want = json.load(f)["device"]
    r = xtrace.reduce_planes(xtrace.read_xplane(str(raw)), 1)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
    spans = {name for name, _ in r["breakdown"]["idle_gaps"]}
    assert spans & {"submit", "generate"}
