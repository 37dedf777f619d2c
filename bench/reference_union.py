"""Float64 reference for one-vs-one models whose banks share their rows.

In a one-vs-one model every training row of a class can be a support
vector of each of the class's tasks, so the banks hold the same rows
many times over (with 77 classes up to 76 times). The per-bank
reference (``bench.reference.decision_values``) computes a kernel row
per bank entry; this one computes ``K(z, r)`` once per distinct row
``r`` of all the banks (``union_rows``), then each task's values are the
columns at its support vectors times its coefficients plus its bias.
It imports nothing of the program and takes the same output under test
as ``bench.reference``: per task the support vectors, their
coefficients and the bias.

Labels: with thousands of pair values a row, a value within rounding
of 0 flips one vote among 76, and may change the winner where the vote
is close. ``certain_labels`` marks the rows whose reference vote no
move of every value within its tolerance can change; only there may a
served label be counted as wrong.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference

BLOCK = 1024
THREADS = 8


def union_rows(banks):
    """The distinct support-vector rows of ``banks`` (exact float32
    rows), and per bank and task the indices of its support vectors in
    them. ``banks`` yields (task_ids, sv_x, coef, b, counts)."""
    index, rows, where = {}, [], []
    for _, sv_x, _, _, counts in banks:
        sv_x = np.ascontiguousarray(sv_x, np.float32)
        bank = []
        for j in range(len(counts)):
            ids = np.empty(int(counts[j]), np.int64)
            for r in range(len(ids)):
                row = sv_x[j, r]
                ids[r] = index.setdefault(row.tobytes(), len(index))
                if ids[r] == len(rows):
                    rows.append(row)
            bank.append(ids)
        where.append(bank)
    d = banks[0][1].shape[-1] if banks else 0
    return (np.stack(rows) if rows else np.zeros((0, d), np.float32)), where


def decision_values(kernel: dict, banks, z: np.ndarray, n_tasks: int,
                    union=None) -> np.ndarray:
    """(n_tasks, len(z)) float64 decisions, as
    ``bench.reference.decision_values`` gives them; ``union`` is
    ``union_rows(banks)`` when the caller has it."""
    rows, where = union_rows(banks) if union is None else union
    z = np.asarray(z, np.float64)
    # K(rows, z), one block of distinct rows per worker
    kt = np.empty((len(rows), len(z)))

    def block(s):
        kt[s:s + BLOCK] = reference.gram64(kernel, rows[s:s + BLOCK], z)

    out = np.full((n_tasks, len(z)), np.nan)
    jobs = [(ids, coef[j, :len(ids)], b[j], t)
            for (task_ids, _, coef, b, _), bank in zip(banks, where)
            for j, (t, ids) in enumerate(zip(task_ids, bank))
            if 0 <= t < n_tasks]

    def task(job):
        ids, coef, b, t = job
        out[t] = np.asarray(coef, np.float64) @ kt[ids] + float(b)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(block, range(0, len(rows), BLOCK)))
        list(pool.map(task, jobs))
    return out


def certain_labels(df: np.ndarray, tol: np.ndarray, pairs: np.ndarray,
                   n_classes: int):
    """The reference's vote of ``df`` (``bench.reference.vote``) and,
    per row, whether every move of each value ``df[t]`` within
    ``tol[t]`` leaves that vote unchanged.

    A value within its tolerance of 0 may vote either way. The winner
    is certain where every other class, given all such votes, still
    falls short of the winner's sure votes; or where the winner ties
    other classes on votes none of which can move, and leads them on
    the summed tanh margins by more than the margins can move (tanh
    moves no more than its argument)."""
    df = np.asarray(df, np.float64)
    tol = np.asarray(tol, np.float64).reshape(-1)
    n = df.shape[1]
    sure = np.zeros((n, n_classes))
    maybe = np.zeros((n, n_classes))
    margin = np.zeros((n, n_classes))
    slack = np.zeros(n_classes)
    for t, (p, q) in enumerate(pairs):
        fixed = np.abs(df[t]) > tol[t]
        pos = df[t] > 0
        sure[fixed & pos, p] += 1
        sure[fixed & ~pos, q] += 1
        maybe[~fixed, p] += 1
        maybe[~fixed, q] += 1
        margin[:, p] += np.tanh(df[t])
        margin[:, q] -= np.tanh(df[t])
        slack[p] += tol[t]
        slack[q] += tol[t]
    won = reference.vote(df, pairs, n_classes)
    at = np.arange(n)
    floor = sure[at, won]
    reach = sure + maybe
    reach[at, won] = -np.inf
    certain = reach.max(1) < floor
    # a tie on unmovable votes, settled by a margin gap wider than both
    # margins' slack
    tied = (reach >= floor[:, None]) & (maybe == 0) & (sure == floor[:, None])
    gap = margin[at, won][:, None] - margin
    settled = ~tied | (gap > slack[won][:, None] + slack[None, :])
    contested = (reach >= floor[:, None]) & ~tied
    certain |= ((maybe[at, won] == 0) & settled.all(1)
                & ~contested.any(1))
    return won, certain
