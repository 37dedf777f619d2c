"""Idle share of the device while fitting: 1 - busy / traced window,
busy being the union of the device's operation intervals, averaged over
the chips. Moves ``fit_s``."""
UNIT = "%"


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
