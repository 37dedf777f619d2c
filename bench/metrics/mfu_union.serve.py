"""Share of the chip's peak over the whole serving window, each
distinct support vector counted once: the decide operations of the
traced window (``bench/work_union.py``) over the window's length times
the peak FLOP/s of the chips. Reads where the generator gives
``n_sv_union``. Moves ``serve_rows_per_s``."""
from bench import peaks, work_union

UNIT = "%"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_rows")
            or ctx.get("n_sv_union") is None):
        return None
    flops = work_union.decide_flops(s["n_rows"], ctx["n_sv"],
                                    ctx["n_sv_union"], ctx["d"])
    peak = peaks.peaks(ctx["device_kind"])["flops_bf16"] * ctx["chips"]
    return 100.0 * flops / (ctx["trace"]["window_s"] * peak)
