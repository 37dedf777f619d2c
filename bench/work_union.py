"""Operations and bytes of the served decide work when every distinct
support vector is counted once.

A one-vs-one model's banks hold the same training rows many times
over. The least work a batch of ``rows`` rows asks for is one kernel
value per row and distinct support-vector row (``n_sv_union`` of them,
width ``d``: ``2 * d`` operations each), then per task one multiply-add
per row and support vector (``2 * rows * sum(n_sv)``). A batch reads
the distinct rows once, each task's coefficients and bias once, its
rows in and one value per task and row out, 4 bytes each.
``bench/work.py`` counts every bank entry as its own row instead; this
count is the one a decide that shares rows across tasks can approach.
"""
from __future__ import annotations

F32_BYTES = 4


def decide_flops(rows: int, n_sv, n_sv_union: int, d: int) -> float:
    return 2.0 * rows * (n_sv_union * d + sum(int(k) for k in n_sv))


def decide_bytes(rows: int, n_sv, n_sv_union: int, d: int,
                 batches: int = 1) -> float:
    """Bytes of ``rows`` rows decided in ``batches`` batches."""
    bank = n_sv_union * d + sum(int(k) + 1 for k in n_sv)
    return float(F32_BYTES * (batches * bank + rows * d + len(n_sv) * rows))
