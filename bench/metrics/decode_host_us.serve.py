"""Host time per batch in the predictor's decode (predictor (decode),
``Predictor.decode``, the eager vote): ``decode_s`` of
``ServingService.stats`` (span ``serve.decode``) over the traced
window's batches. Moves ``serve_rows_per_s``."""
UNIT = "us"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_batches")
            or "decode_s" not in s):
        return None
    return s["decode_s"] / s["n_batches"] * 1e6
