"""Share of the chip's peak over the whole serving window: the decide
operations served in the traced window (``bench/work.py``) over the
window's length times the peak FLOP/s of the chips. Bounds what taking
the decide off the device path could hide from
``predict_roofline.serve``. Moves ``serve_rows_per_s``."""
from bench import peaks, work

UNIT = "%"


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["traced_stats"]["n_rows"]:
        return None
    flops = work.decide_flops(ctx["traced_stats"]["n_rows"], ctx["n_sv"], ctx["d"])
    peak = peaks.peaks(ctx["device_kind"])["flops_bf16"] * ctx["chips"]
    return 100.0 * flops / (ctx["trace"]["window_s"] * peak)
