"""Time a request waited in the service's queue (service,
``serve/service.py``): ``queue_wait_s`` of ``ServingService.stats``,
from each ``submit`` to the batcher taking the request, over the
requests served in the traced window. Above the knee it reads the
backlog. Moves ``serve_rows_per_s``."""
UNIT = "ms"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_requests")
            or "queue_wait_s" not in s):
        return None
    return s["queue_wait_s"] / s["n_requests"] * 1e3
