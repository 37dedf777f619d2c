"""The batcher thread's CPU share inside its batches (service host,
``serve/service.py``): ``batcher_cpu_s`` (``time.thread_time()``) over
``batch_s`` (span ``serve.batch``) of ``ServingService.stats`` in the
traced window. Under 100% the batcher waited inside a batch: on the
interpreter lock, the device, or a stall of the machine. Moves
``serve_rows_per_s``."""
UNIT = "%"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("batch_s")
            or "batcher_cpu_s" not in s):
        return None
    return 100.0 * s["batcher_cpu_s"] / s["batch_s"]
