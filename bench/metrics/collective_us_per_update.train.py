"""Cross-chip collective time per pair update (device; the sharded SMO
collectives of ``_sharded_smo_iteration``): the summed duration of the
all-reduce, all-gather and other collective operations in the trace,
averaged over the chips, over the traced fits' pair updates. Read only
where the fit spans more than one chip. Moves ``fit_s``."""
UNIT = "us"


def read(ctx):
    if (ctx.get("kind") != "train" or ctx["chips"] < 2
            or not ctx["traced_updates"]):
        return None
    return ctx["trace"]["collective_s"] / ctx["traced_updates"] * 1e6
