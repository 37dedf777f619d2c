"""The program's own ``serve.*`` spans in a trace leave the reduction's
device numbers as they were: the window, busy and collective time,
operation seconds and the gaps' labels."""
import pytest

from bench import xtrace

MS = 1_000_000


def _planes(program):
    return [
        ("/device:TPU:0", [("XLA Ops", [("fusion.1", 0, 1 * MS),
                                        ("fusion.2", 4 * MS, 1 * MS),
                                        ("fusion.1", 9 * MS, 1 * MS)])]),
        ("/host:CPU", [("receive", [("submit", 0, 10 * MS)]),
                       ("batcher", program)]),
    ]


@pytest.mark.parametrize("program", [
    # a batch inside the device's window, its stages nested in it
    [("serve.batch#batch=1,requests=3,rows=5,full=0#", 1 * MS, 7 * MS),
     ("serve.collect", 1 * MS, 1 * MS), ("serve.decide", 2 * MS, 4 * MS),
     ("serve.launch", 2 * MS, 1 * MS), ("serve.fetch", 3 * MS, 3 * MS),
     ("serve.decode", 6 * MS, 1 * MS)],
    # batches before and after the device's first and last operation
    [("serve.batch", 0, 20 * MS), ("serve.decode", 12 * MS, 2 * MS),
     ("serve.collect", -5 * MS, 3 * MS)],
], ids=["inside", "outside"])
def test_program_spans_leave_the_reduction_unchanged(program):
    plain = xtrace.reduce_planes(_planes([]), 1)
    r = xtrace.reduce_planes(_planes(program), 1)
    assert r == plain
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.003)
    assert r["breakdown"]["idle_gaps"] == [
        ["submit", pytest.approx(0.004)], ["submit", pytest.approx(0.003)]]
