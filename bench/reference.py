"""The benchmark's plain float64 reference.

It imports nothing of the program and takes nothing the program made
but the output under test: per task of the fitted model the support
vectors, their coefficients ``alpha_i y_i`` and the bias. The kernel
and its gamma come from the configuration, the classes and the vote
routing from the training labels (``routing``); the model's own class
table, routing and bank task ids are compared with those
(``routing_faults``), never used. From the training rows the reference
recomputes, in float64:

* the gradient ``f_i = sum_j alpha_j y_j K(x_i, x_j) - y_i`` over every
  training row of the task, and from it the KKT violation of the
  C-SVC dual, ``max(0, (b_low - b_up) / 2)``: the solver-independent
  certificate, at most ``tol`` for a solved QP;
* the bias the multipliers imply, ``-(b_up + b_low) / 2``, against the
  model's own;
* the decision values ``K(z, SV_t) coef_t + b_t`` and the one-vs-one
  vote of served rows.

Kernel rows are computed in blocks, on a few host threads (NumPy
releases the GIL inside its loops), so that a large bank fits and the
check stays shorter than the window it checks.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 2048
THREADS = 8
# bound membership: alpha within this share of C of a bound counts as at
# the bound (the solver's own rule)
BOUND_EPS = 1e-6


def gram64(kernel: dict, z: np.ndarray, sv: np.ndarray) -> np.ndarray:
    """float64 K(z, sv); ``kernel`` is {"name", "gamma"}."""
    z = np.asarray(z, np.float64)
    sv = np.asarray(sv, np.float64)
    dot = z @ sv.T
    if kernel["name"] == "linear":
        return dot
    if kernel["name"] == "rbf":
        d2 = (z * z).sum(1)[:, None] + (sv * sv).sum(1)[None, :] - 2.0 * dot
        return np.exp(-kernel["gamma"] * np.maximum(d2, 0.0))
    raise ValueError(f"the reference has no kernel {kernel['name']!r}")


def kernel_matvec(kernel: dict, z: np.ndarray, sv: np.ndarray,
                  coef: np.ndarray) -> np.ndarray:
    """K(z, sv) @ coef in float64, in row blocks over a thread pool."""
    z = np.asarray(z, np.float64)
    coef = np.asarray(coef, np.float64)
    if len(sv) == 0:
        return np.zeros(len(z))
    starts = range(0, len(z), BLOCK)

    def block(s):
        return gram64(kernel, z[s:s + BLOCK], sv) @ coef

    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(block, starts)))


def kkt_bounds(alpha, y, f, C: float) -> tuple[float, float]:
    """(b_up, b_low) of the C-SVC dual at ``alpha``: the least f over
    the rows that may still move up, the largest over those that may
    move down."""
    alpha = np.asarray(alpha, np.float64)
    eps = BOUND_EPS * C
    pos = y > 0
    not_upper = alpha < C - eps
    not_lower = alpha > eps
    up = (pos & not_upper) | (~pos & not_lower)
    low = (pos & not_lower) | (~pos & not_upper)
    b_up = f[up].min() if up.any() else np.inf
    b_low = f[low].max() if low.any() else -np.inf
    return float(b_up), float(b_low)


def kkt_violation(b_up: float, b_low: float) -> float:
    if not (np.isfinite(b_up) and np.isfinite(b_low)):
        return 0.0
    return max(0.0, (b_low - b_up) / 2.0)


def row_index(x: np.ndarray) -> dict:
    """Exact row -> index map of a training matrix (rows as bytes)."""
    x = np.ascontiguousarray(x, np.float32)
    return {x[i].tobytes(): i for i in range(len(x))}


def certify_task(kernel: dict, C: float, x: np.ndarray, y: np.ndarray,
                 sv_x: np.ndarray, coef: np.ndarray, b: float,
                 index: dict = None) -> dict:
    """Certify one binary task of a fitted model.

    ``x``/``y`` are the task's training rows and signs (+1/-1) as the
    reference builds them; ``sv_x``/``coef``/``b`` the model's bank.
    Every support vector has to be a training row whose sign is the
    sign of its coefficient, with |coef| <= C; otherwise the task is a
    ``sv_fault`` and reads ``kkt = bias_gap = inf``. Returns the KKT
    violation and the gap between the model's bias and the one its
    multipliers imply.
    """
    y = np.asarray(y, np.float64)
    index = row_index(x) if index is None else index
    alpha = np.zeros(len(x))
    coef = np.asarray(coef, np.float64)
    for k in range(len(sv_x)):
        i = index.get(np.ascontiguousarray(sv_x[k], np.float32).tobytes())
        if (i is None or np.sign(coef[k]) != y[i]
                or abs(coef[k]) > C * (1 + BOUND_EPS) or alpha[i] != 0):
            return {"kkt": float("inf"), "bias_gap": float("inf"),
                    "sv_fault": True}
        alpha[i] = abs(coef[k])
    f = kernel_matvec(kernel, x, sv_x, coef) - y
    b_up, b_low = kkt_bounds(alpha, y, f, C)
    b_ref = -(b_up + b_low) / 2.0 if np.isfinite(b_up + b_low) else b
    return {"kkt": kkt_violation(b_up, b_low),
            "bias_gap": abs(float(b) - b_ref), "sv_fault": False}


def routing(y: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The classes and the vote routing that the training labels imply:
    the sorted distinct labels; for two classes one task that credits
    the second on a positive decision, else the one-vs-one tasks
    ``(i, j)``, ``i < j``, in order, each crediting ``i``."""
    classes = np.unique(y)
    m = len(classes)
    if m == 2:
        return classes, np.array([[1, 0]], np.int64)
    return classes, np.array([(i, j) for i in range(m)
                              for j in range(i + 1, m)], np.int64)


def routing_faults(classes, pairs, model: dict) -> int:
    """Entries of the model's class table, vote routing and bank task
    ids that differ from the reference's: every task served from
    exactly one bank slot."""
    faults = 0
    for want, got in ((classes, model["classes"]), (pairs, model["pairs"])):
        got = np.asarray(got)
        faults += (int(np.sum(want != got)) if got.shape == want.shape
                   else want.size + 1)
    ids = np.concatenate([np.asarray(g[0]).ravel() for g in model["banks"]]
                         or [np.zeros(0, np.int64)])
    faults += int(np.sum(np.bincount(ids.astype(np.int64),
                                     minlength=len(pairs))[:len(pairs)] != 1))
    faults += int(np.sum((ids < 0) | (ids >= len(pairs))))
    return faults


def task_rows(classes, pairs, x, y):
    """Per task the reference's own rows and signs: +1 for the class a
    positive decision credits, -1 for the other."""
    for p, q in pairs:
        rows = (y == classes[p]) | (y == classes[q])
        yield x[rows], np.where(y[rows] == classes[p], 1.0, -1.0)


def certify_model(kernel: dict, C: float, x, y, classes, pairs,
                  banks) -> dict:
    """Every task of a fitted model against its training rows, by the
    reference's own routing: the worst KKT violation and bias gap, and
    the number of tasks whose bank fails ``certify_task``'s membership
    test."""
    tasks = list(task_rows(classes, pairs, x, y))
    kkt, gap, faults = 0.0, 0.0, 0
    for task_ids, sv_x, coef, b, counts in banks:
        for j, t in enumerate(task_ids):
            k = int(counts[j])
            if not 0 <= t < len(tasks):
                faults += 1
                continue
            xt, yt = tasks[t]
            r = certify_task(kernel, C, xt, yt, sv_x[j, :k], coef[j, :k],
                             b[j])
            kkt, gap = max(kkt, r["kkt"]), max(gap, r["bias_gap"])
            faults += int(r["sv_fault"])
    return {"kkt": kkt, "bias_gap": gap, "sv_faults": faults}


def decision_values(kernel: dict, banks, z: np.ndarray,
                    n_tasks: int) -> np.ndarray:
    """(n_tasks, len(z)) float64 decisions; ``banks`` yields
    (task_ids, sv_x (T, w, d), coef (T, w), b (T,), counts (T,))."""
    out = np.full((n_tasks, len(z)), np.nan)
    for task_ids, sv_x, coef, b, counts in banks:
        for j, t in enumerate(task_ids):
            if not 0 <= t < n_tasks:
                continue
            k = int(counts[j])
            out[t] = (gram64(kernel, z, sv_x[j, :k])
                      @ np.asarray(coef[j, :k], np.float64) + float(b[j]))
    return out


def tolerance(banks, n_tasks: int, rtol: float) -> np.ndarray:
    """(n_tasks, 1) bound on |served - reference| per task:
    ``rtol * (1 + ||coef_t||_1)``; f32 kernel values carry ~1e-7
    relative error, summed over the bank with |coef| weights."""
    mass = np.zeros((n_tasks, 1))
    for task_ids, _, coef, _, counts in banks:
        for j, t in enumerate(task_ids):
            if not 0 <= t < n_tasks:
                continue
            mass[t, 0] = np.abs(np.asarray(coef[j, :int(counts[j])],
                                           np.float64)).sum()
    return rtol * (1.0 + mass)


def vote(df: np.ndarray, pairs: np.ndarray, n_classes: int) -> np.ndarray:
    """One-vs-one majority vote: most votes first, then the largest sum
    of signed tanh margins among the leaders, then the lowest class
    index (the LIBSVM order). ``pairs[t] = (credited on df > 0,
    credited on df < 0)``."""
    df = np.asarray(df, np.float64)
    n = df.shape[1]
    votes = np.zeros((n, n_classes))
    margin = np.zeros((n, n_classes))
    for t, (p, q) in enumerate(pairs):
        pos = df[t] > 0
        votes[pos, p] += 1
        votes[~pos, q] += 1
        margin[:, p] += np.tanh(df[t])
        margin[:, q] -= np.tanh(df[t])
    lead = votes >= votes.max(1, keepdims=True) - 0.5
    score = np.where(lead, margin, -np.inf)
    return np.argmax(score, axis=1)
