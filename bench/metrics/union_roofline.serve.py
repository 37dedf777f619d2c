"""Share of the roofline in serving, each distinct support vector
counted once (kernels: the decide, whatever implements it): the least
time of the traced window's decide work (``bench/work_union.py``,
``bench/peaks.py``) over the device busy time. Reads where the
generator gives ``n_sv_union``. Moves ``serve_rows_per_s``."""
from bench import peaks, work, work_union

UNIT = "%"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_batches")
            or ctx.get("n_sv_union") is None or not ctx["trace"]["busy_s"]):
        return None
    args = (s["n_rows"], ctx["n_sv"], ctx["n_sv_union"], ctx["d"])
    least = work.least_seconds(
        work_union.decide_flops(*args),
        work_union.decide_bytes(*args, s["n_batches"]),
        peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / ctx["trace"]["busy_s"]
