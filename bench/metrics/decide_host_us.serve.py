"""Host time per batch in the predictor's decide (predictor,
``serve/predictor.py``): ``decide_s`` of ``ServingService.stats`` (span
``serve.decide``: upload, launch, fetch) over the traced window's
batches, beside the device's ``batch_device_us.serve``. Moves
``serve_rows_per_s``."""
UNIT = "us"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_batches")
            or "decide_s" not in s):
        return None
    return s["decide_s"] / s["n_batches"] * 1e6
