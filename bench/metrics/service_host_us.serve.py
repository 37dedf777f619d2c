"""Host time per batch in the service's own stages (service,
``serve/service.py``): ``collect_s + merge_s + scatter_s`` of
``ServingService.stats`` (spans ``serve.collect``, ``serve.merge``,
``serve.scatter``) over the traced window's batches. Moves
``serve_rows_per_s``."""
UNIT = "us"
KEYS = ("collect_s", "merge_s", "scatter_s")


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_batches")
            or any(k not in s for k in KEYS)):
        return None
    return sum(s[k] for k in KEYS) / s["n_batches"] * 1e6
