"""Device busy time per pair update (solver loop): the traced fits'
device busy time, averaged over the chips, over their pair updates.
Moves ``fit_s``."""
UNIT = "us"


def read(ctx):
    if ctx.get("kind") != "train" or not ctx["traced_updates"]:
        return None
    return ctx["trace"]["busy_s"] / ctx["traced_updates"] * 1e6
