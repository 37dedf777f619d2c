"""Operations and bytes of the served decide work, from shapes alone.

One decide of a batch of ``rows`` against a packed model evaluates, for
every task ``t``, ``rows x n_sv[t]`` kernel values of width ``d`` (a
dot product: ``2 * d`` operations each) and reads the task's bank once:
``n_sv[t] * d`` support-vector values, ``n_sv[t]`` coefficients and one
bias, 4 bytes each. The batch's rows are read once and one value per
task and row is written back. The exponentials and the padding up to
the batch ladder are not counted: this is the least work the request
asks for, whatever implements it.
"""
from __future__ import annotations

F32_BYTES = 4


def decide_flops(rows: int, n_sv, d: int) -> float:
    return float(sum(2.0 * rows * int(k) * d for k in n_sv))


def decide_bytes(rows: int, n_sv, d: int, batches: int = 1) -> float:
    """Bytes of ``rows`` rows decided in ``batches`` batches: the banks
    read once a batch, the rows in and the values out once."""
    bank = sum(int(k) * d + int(k) + 1 for k in n_sv)
    return float(F32_BYTES * (batches * bank + rows * d + len(n_sv) * rows))


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of compute and memory."""
    return max(flops / peak["flops_bf16"],
               nbytes / peak["hbm_bytes_per_s"])
