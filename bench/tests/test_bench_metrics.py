"""The readers of the program's serving counters, on a fake context and
on the ``stats`` of a program that has no such counters."""
import pytest

from bench import names

# ServingService.stats over a traced window: 4 batches, 10 requests
STATS = {"n_requests": 10, "n_rows": 400, "n_batches": 4,
         "n_window_flushes": 1, "n_full_flushes": 3, "max_batch_rows": 130,
         "rows_per_batch": 100.0, "n_failed_requests": 0,
         "queue_wait_s": 0.5, "batch_s": 0.08, "batcher_cpu_s": 0.06,
         "collect_s": 0.004, "merge_s": 0.002, "decide_s": 0.04,
         "decode_s": 0.02, "scatter_s": 0.006}
PARENT = {k: STATS[k] for k in ("n_requests", "n_rows", "n_batches",
                                "n_window_flushes", "n_full_flushes",
                                "max_batch_rows", "rows_per_batch")}
WANT = {"service_host_us.serve": 3000.0, "decide_host_us.serve": 10000.0,
        "decode_host_us.serve": 5000.0, "queue_wait_ms.serve": 50.0,
        "batcher_cpu.serve": 75.0}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_serving_counter_readers(metric):
    unit, read = names.metric_readers()[metric]
    ctx = {"kind": "serve", "stats": dict(STATS, n_batches=99),
           "traced_stats": STATS}
    assert read(ctx) == pytest.approx(WANT[metric], rel=1e-12)
    assert read(dict(ctx, traced_stats=PARENT)) is None
    assert read(dict(ctx, traced_stats=dict(STATS, n_batches=0,
                                            n_requests=0, batch_s=0.0))) is None
    assert read(dict(ctx, kind="fit")) is None
