"""Pair updates per fit (solver loop, ``core/smo.py``): ``SVC.n_iter_``,
for one-vs-one the longest task, which sets the vmapped loop's length.
The mean over the run's distinct datasets, each counted once: the count
repeats exactly. Moves ``fit_s``."""
UNIT = "updates"


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    return ctx["pair_updates"]
