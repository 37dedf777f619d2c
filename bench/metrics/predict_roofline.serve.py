"""Share of the roofline in serving (kernels: the decide, whatever
implements it): the least time of the window's decide work over the
device busy time. The least time is the larger of its operations over
the peak FLOP/s and its bytes over the peak HBM bandwidth
(``bench/work.py``, ``bench/peaks.py``): 2 * rows * n_sv * d operations
per task, and per batch the banks read once plus the rows in and the
values out. Moves ``serve_rows_per_s``."""
from bench import peaks, work

UNIT = "%"


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["traced_stats"]["n_batches"]:
        return None
    s, n_sv, d = ctx["traced_stats"], ctx["n_sv"], ctx["d"]
    flops = work.decide_flops(s["n_rows"], n_sv, d)
    nbytes = work.decide_bytes(s["n_rows"], n_sv, d, s["n_batches"])
    least = work.least_seconds(flops, nbytes, peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / ctx["trace"]["busy_s"]
