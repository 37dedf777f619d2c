"""Open-loop arrivals against ``ServingService``.

Set-up draws the configuration's dataset from the seed, fits it, packs
the model, warms the default ``Predictor`` on the whole batch ladder
(decide and decode, 1 up to twice ``max_batch``: a merged window may
exceed the cap by one request), opens the service and starts the pacer
(``bench/pacer.py``), a process of its own that keeps the arrival
clock.

The schedule is a Poisson process at ``rate_rps`` conditioned on its
count: ``round(rate_rps * seconds)`` arrivals at sorted uniform times
over the window, so every seed offers the same number of requests.
Row counts are drawn from ``rows`` in the exact proportions of
``weights`` and shuffled; a share ``values_share`` of the requests asks
for decision values, the rest for labels; a request's rows are a
contiguous slice of the held-out pool at a seeded offset. The pacer
sends each request's index at its instant and never waits for answers;
a receiving thread in the serving process submits it, as a server's
network thread would. A request's latency runs from its scheduled
arrival to its answer, so a stall delays every later request too.

``gc_freeze`` in the mix freezes the set-up heap when the window opens
(``gc.freeze()``, as a server does after its warm-up): the collector
then never rescans it inside the window.

After the window every answer is held to the float64 reference, with
the configuration's kernel and the routing the training labels imply:
values within the tolerance, labels equal to the reference's vote. The
served banks are held to the training rows: every support vector a
training row of its task, with its sign and |coef| <= C, and each
task's bias near the one its multipliers imply.

End-to-end: ``serve_p99_ms``, the 99th percentile of the latency of
every request due in the window (a missing answer counts as infinite),
and ``serve_rows_per_s``, rows answered within the window over its
length; a cell's file names the ones it reports.
"""
from __future__ import annotations

import gc
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

from bench import reference, spans, system
from bench.pacer import TRAILER, read_exact
from bench.generators import Outcome, dataset, kernel, sub_seed

RESULT_WAIT_S = 60.0
PACER = Path(__file__).resolve().parent.parent / "pacer.py"


def schedule(params: dict, seconds: float, pool_rows: int, seed: int):
    """Arrival times, row counts, offsets into the pool and ops."""
    rng = np.random.default_rng(sub_seed(seed, 2))
    n = int(round(params["rate_rps"] * seconds))
    arrivals = np.sort(rng.uniform(0.0, seconds, n))
    w = np.asarray(params["weights"], np.float64)
    counts = np.floor(w / w.sum() * n).astype(int)
    short = n - counts.sum()
    counts[np.argsort(-(w / w.sum() * n - counts))[:short]] += 1
    rows = rng.permutation(np.repeat(params["rows"], counts))
    starts = rng.integers(0, pool_rows - rows + 1)
    n_values = int(round(params["values_share"] * n))
    ops = rng.permutation(np.array(["values"] * n_values
                                   + ["predict"] * (n - n_values)))
    return arrivals, rows, starts, ops


class Pacer:
    """The pacer process: started with the schedule in set-up, told the
    window's start instant by ``start``; ``indices()`` yields the index
    of each request as it falls due, then ``late`` holds the pacer's
    trailer (``bench/pacer.py``)."""

    def __init__(self, arrivals: np.ndarray):
        self.proc = subprocess.Popen([sys.executable, str(PACER)],
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, bufsize=0)
        arrivals = np.ascontiguousarray(arrivals, np.float64)
        self._send(np.int64(len(arrivals)).tobytes() + arrivals.tobytes())
        # ready once it holds the schedule: its start-up is set-up's
        ack = read_exact(self.proc.stdout.fileno(), 4)
        if int(np.frombuffer(ack, np.int32)[0]) != len(arrivals):
            raise RuntimeError("the pacer did not take the schedule")
        self.late = (np.nan,) * TRAILER

    def _send(self, data: bytes) -> None:
        view = memoryview(data)
        while view:
            view = view[self.proc.stdin.write(view):]

    def start(self, t0: float) -> None:
        self._send(np.float64(t0).tobytes())
        self.proc.stdin.close()

    def indices(self):
        fd, buf = self.proc.stdout.fileno(), b""
        while True:
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                raise EOFError("the pacer ended before its last request")
            buf += chunk
            whole = len(buf) // 4 * 4
            got = np.frombuffer(buf[:whole], np.int32)
            end = np.flatnonzero(got < 0)
            if end.size:
                yield from got[:end[0]].tolist()
                tail = buf[4 * (end[0] + 1):]
                tail += read_exact(fd, max(0, 8 * TRAILER - len(tail)))
                self.late = tuple(np.frombuffer(tail[:8 * TRAILER],
                                                np.float64))
                return
            yield from got.tolist()
            buf = buf[whole:]

    def close(self) -> None:
        if self.proc.stdin and not self.proc.stdin.closed:
            self.proc.stdin.close()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.proc.stdout.close()


def replay(submit, pacer: Pacer, arrivals, rows, starts, ops, pool,
           seconds, marks=()):
    """Submit each request as the pacer sends it, from a receiving
    thread; returns per-request futures, answer times and submission
    lateness, all from the window's start. ``marks`` are
    ``(offset, fn)``: the calling thread runs ``fn()`` at each offset."""
    n = len(arrivals)
    done = np.full(n, np.nan)
    late = np.full(n, np.nan)
    futs = [None] * n
    failure = []
    t0 = time.perf_counter()

    def receive():
        try:
            for i in pacer.indices():
                with spans.span("submit"):
                    late[i] = time.perf_counter() - t0 - arrivals[i]
                    fut = submit(pool[starts[i]:starts[i] + rows[i]],
                                 op=ops[i])

                def _done(_, i=i):
                    done[i] = time.perf_counter() - t0

                fut.add_done_callback(_done)
                futs[i] = fut
        except BaseException as e:                  # noqa: BLE001
            failure.append(e)

    thread = threading.Thread(target=receive, name="bench-receive")
    thread.start()
    pacer.start(t0)
    for at, fn in sorted(marks, key=lambda m: m[0]):
        wait = t0 + at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        fn()
    thread.join(seconds + RESULT_WAIT_S)
    if thread.is_alive() or failure:
        raise RuntimeError(f"the request stream broke off: {failure}")
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    return futs, done, late


def run(ctx) -> Outcome:
    cfg, params = ctx.config, ctx.params
    xtr, ytr, pool, _ = dataset(cfg, sub_seed(ctx.seed, 0))
    packed = system.pack(system.svc(cfg, params, control=ctx.control)
                         .fit(xtr, ytr))
    model = system.banks(packed)
    ladder = tuple(2 ** i for i in range(int(params["ladder_log2"]) + 1))
    pred = system.predictor(packed, control=ctx.control, ladder=ladder)
    del packed
    arrivals, rows, starts, ops = schedule(params, ctx.seconds, len(pool),
                                           ctx.seed)
    pool = np.ascontiguousarray(pool, np.float32)
    svc = system.service(pred, params["window_ms"])
    pacer = Pacer(arrivals)
    traced = {}

    def end_trace():
        # the traced part of the window: its first trace_seconds
        traced.update(svc.stats)
        ctx.stop_tracing()

    marks = ([(params.get("trace_seconds", ctx.seconds), end_trace)]
             if ctx.trace else [])
    freeze = bool(params.get("gc_freeze", False))
    try:
        if freeze:
            gc.collect()
        ctx.window_open()
        if freeze:
            gc.freeze()
        try:
            futs, done, late = replay(svc.submit, pacer, arrivals, rows,
                                      starts, ops, pool, ctx.seconds, marks)
        finally:
            if freeze:
                gc.unfreeze()
        stats = svc.stats
        ctx.window_close()
        answers = []
        for f in futs:
            try:
                answers.append(f.result(timeout=RESULT_WAIT_S))
            except Exception:                       # noqa: BLE001
                answers.append(None)
    finally:
        svc.close()
        pacer.close()

    with spans.span("certify"):
        classes, pairs = reference.routing(ytr)
        fit = reference.certify_model(kernel(cfg), cfg["svc"]["C"], xtr,
                                      ytr, classes, pairs, model["banks"])
        routing_faults = reference.routing_faults(classes, pairs, model)
        err, mismatched = check(kernel(cfg), classes, pairs, model, pool,
                                rows, starts, ops, answers, params["rtol"])
    missing = sum(a is None for a in answers)
    lat = done - arrivals
    lat[np.isnan(lat)] = np.inf
    in_window = np.isfinite(done) & (done <= ctx.seconds)
    ok = np.array([a is not None for a in answers])
    fifth = max(1, len(lat) // 5)
    return Outcome(
        attempted=len(arrivals), failed=missing,
        metrics={"serve_p99_ms": float(np.percentile(lat, 99)) * 1e3,
                 "serve_rows_per_s": float(rows[in_window & ok].sum())
                 / ctx.seconds},
        checks=[("value_err_over_tol", err, ctx.limits["value_err_over_tol"]),
                ("labels_mismatched", mismatched, 0),
                ("requests_missing", missing, 0),
                ("routing_faults", routing_faults, 0),
                ("sv_faults", fit["sv_faults"], 0),
                ("bias_gap", fit["bias_gap"], ctx.limits["bias_gap"])],
        layer={"kind": "serve", "stats": stats,
               "traced_stats": traced or stats,
               "n_sv": [int(c) for g in model["banks"] for c in g[4]],
               "d": model["n_features"]},
        notes={"p50_ms": float(np.percentile(lat, 50)) * 1e3,
               "p90_ms": float(np.percentile(lat, 90)) * 1e3,
               "p95_ms": float(np.percentile(lat, 95)) * 1e3,
               "p99_ms": float(np.percentile(lat, 99)) * 1e3,
               "lag_growth_ms": float(np.median(lat[-fifth:])
                                      - np.median(lat[:fifth])) * 1e3,
               "rows_per_batch": stats["rows_per_batch"],
               "submit_late_p99_ms": float(np.nanpercentile(late, 99)) * 1e3,
               "submit_late_max_ms": float(np.nanmax(late)) * 1e3,
               "pacer_late_p99_ms": float(pacer.late[0]) * 1e3,
               "pacer_late_max_ms": float(pacer.late[1]) * 1e3,
               "pacer_late_max_at_s": float(pacer.late[2]),
               "pacer_stalls": float(pacer.late[3]),
               "fit_kkt": fit["kkt"],
               "requests": len(arrivals), "rows": int(rows.sum()),
               "batches": stats["n_batches"]})


def check(kern, classes, pairs, model, pool, rows, starts, ops, answers,
          rtol):
    """Worst |served - reference| over its tolerance, and labels that
    differ from the reference's vote, over every answered request.
    Every request's rows are a slice of the pool, so the reference
    decides the pool once."""
    banks, n_tasks = model["banks"], len(pairs)
    tol = reference.tolerance(banks, n_tasks, rtol)
    want = reference.decision_values(kern, banks, pool, n_tasks)
    labels = classes[reference.vote(np.nan_to_num(want), pairs,
                                    len(classes))]
    err, mismatched = 0.0, 0
    for i, ans in enumerate(answers):
        if ans is None:
            continue
        sl = slice(starts[i], starts[i] + rows[i])
        if ops[i] == "values":
            got = np.asarray(ans, np.float64).reshape(-1, rows[i])
            if got.shape != want[:, sl].shape:
                err = np.inf
                continue
            gap = np.abs(got - want[:, sl]) / tol
            err = max(err, float(np.where(np.isnan(gap), np.inf, gap).max()))
        else:
            mismatched += int(np.sum(np.asarray(ans) != labels[sl]))
    return err, mismatched
