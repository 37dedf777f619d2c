"""Idle share of the device while serving: 1 - busy / traced window.
Moves ``serve_rows_per_s``."""
UNIT = "%"


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
