"""Device busy time per served batch (predictor, ``serve/predictor.py``):
the traced window's device busy time over the batches served in it.
Moves ``serve_rows_per_s``."""
UNIT = "us"


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["traced_stats"]["n_batches"]:
        return None
    return ctx["trace"]["busy_s"] / ctx["traced_stats"]["n_batches"] * 1e6
