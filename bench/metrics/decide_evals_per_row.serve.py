"""Kernel values the decide launched per served row (predictor,
``serve/predictor.py``): ``decide_evals`` of ``ServingService.stats``
(padded rows x bank width x tasks of every launch) over the rows served
in the traced window. Against the least count, one value per distinct
support vector, it reads the padding and the rows the banks repeat.
Moves ``serve_rows_per_s``."""
UNIT = "evals/row"


def read(ctx):
    s = ctx.get("traced_stats") or {}
    if (ctx.get("kind") != "serve" or not s.get("n_rows")
            or "decide_evals" not in s):
        return None
    return s["decide_evals"] / s["n_rows"]
