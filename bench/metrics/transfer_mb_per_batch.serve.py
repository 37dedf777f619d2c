"""Megabytes that crossed between host and device per served batch
(predictor, ``serve/predictor.py``): ``transfer_bytes`` of
``ServingService.stats`` (the decide's uploads and fetches, the
decode's upload and fetch) over the traced window's batches. Moves
``serve_rows_per_s``."""
UNIT = "MB"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_batches")
            or "transfer_bytes" not in s):
        return None
    return s["transfer_bytes"] / s["n_batches"] / 1e6
