"""Rows per merged batch (service, ``serve/service.py``):
``ServingService.stats`` over the window. Moves ``serve_rows_per_s``."""
UNIT = "rows"


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["stats"]["n_batches"]:
        return None
    return ctx["stats"]["rows_per_batch"]
