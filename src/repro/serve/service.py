"""Async serving service: request queue + dynamic-batching window.

``serve.Predictor`` made the decide *kernel* fast; this module makes it
fast **under open-loop traffic**, where requests arrive on their own
clock and mostly one row at a time. Dispatching each arrival alone
wastes the fused decide program — a 64-row bucket costs about the same
as 1 row — so the service batches the queue:

* ``submit`` enqueues a request (any row count) and returns a
  ``concurrent.futures.Future`` immediately — callers never block the
  batcher;
* a single worker thread collects arrivals for at most
  ``window_ms`` (measured from the FIRST request of the window) or
  until some model's collected rows reach its predictor's
  ``max_batch`` — whichever comes first — then flushes: per model, one
  fused ``decision_values`` over the concatenated rows, one vectorized
  decode, and the per-request slices scattered back through the
  futures;
* requests for different models share a window (the registry keeps
  their banks resident); an idle service burns no CPU (the worker
  blocks on the queue).

``window_ms=0`` disables the *wait* but not the batching: whatever is
already queued when the worker wakes is still fused into one decide —
the greedy-backlog batcher. The latency cost of a window is bounded by
``window_ms``; the throughput win at saturation is the batch width.

    svc = ServingService(serve.pack(clf), window_ms=2.0)
    fut = svc.submit(z_row, op="predict")     # non-blocking
    fut.result()                              # one label row
    svc.predict(Z)                            # blocking convenience
    svc.close()                               # flushes, then stops

Multi-model form: pass a ``ModelRegistry`` (or a ``{name: PackedModel}``
dict) and route with ``submit(x, model="name")``.

For operators, ``stats`` holds cumulative counters: requests, rows and
batches served (``rows_per_batch``), window against full flushes,
``n_failed_requests`` (futures that got a decide or decode error),
``queue_wait_s`` (summed from each ``submit`` to the batcher taking the
request), and the batcher's host seconds per stage: ``batch_s`` in all,
``collect_s``, ``merge_s``, ``decide_s``, ``decode_s``, ``scatter_s``,
and ``batcher_cpu_s``, its thread's CPU time inside batches; and what
the predictors' ``work`` counted for the batches: ``decide_evals``
(kernel values launched) and ``transfer_bytes`` (bytes uploaded and
fetched by decide and decode). Each stage
is also a ``jax.profiler.TraceAnnotation`` (``serve.trace``), so a
profiler trace shows, on the device's clock, ``serve.batch`` (metadata
``batch``, ``requests``, ``rows``, ``full``) and nested in it
``serve.collect``, ``serve.merge``, ``serve.decide`` (the predictor's
``serve.upload``, ``serve.launch``, ``serve.fetch`` inside),
``serve.decode`` and ``serve.scatter``. The batcher's blocking wait for
a first request lies outside every span.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import NamedTuple, Optional, Union

import numpy as np

from repro.serve.artifact import PackedModel
from repro.serve.predictor import Predictor, _pow2_floor
from repro.serve.registry import ModelRegistry
from repro.serve.trace import Stages

_OPS = ("predict", "decision_function", "values")
_SENTINEL = object()


class _Request(NamedTuple):
    model: str
    op: str
    x: np.ndarray          # (n, d) float32
    future: Future
    t_submit: float        # time.perf_counter() at submit


class ServingService:
    """Dynamic-batching front end over one or many packed models."""

    # shared mutable state and its lock (enforced by analysis rule R004):
    # the worker thread mutates _stats; _closed coordinates submit/close
    _GUARDED_BY = {"_stats": "_stats_lock", "_closed": "_stats_lock"}

    def __init__(self, models, *, window_ms: float = 2.0,
                 engine="auto", max_batch: int = 1024,
                 max_resident: int = 4, warmup_sizes: tuple = (1,)):
        if window_ms < 0:
            raise ValueError(f"window_ms must be >= 0, got {window_ms}")
        self.window_s = float(window_ms) * 1e-3
        self._direct: dict[str, Predictor] = {}
        self.registry: Optional[ModelRegistry] = None
        if isinstance(models, Predictor):
            # serve an existing predictor as the single "default" model
            self._direct["default"] = models
        elif isinstance(models, ModelRegistry):
            self.registry = models
        else:
            self.registry = ModelRegistry(
                max_resident=max_resident, engine=engine,
                max_batch=max_batch, warmup_sizes=warmup_sizes)
            named = (models if isinstance(models, dict)
                     else {"default": models})
            for name, m in named.items():
                self.registry.register(name, m)
        self._q: queue.Queue = queue.Queue()
        self._closed = False
        self._stats_lock = threading.Lock()
        self._stats = {"n_requests": 0, "n_rows": 0, "n_batches": 0,
                       "n_window_flushes": 0, "n_full_flushes": 0,
                       "max_batch_rows": 0, "n_failed_requests": 0,
                       "queue_wait_s": 0.0, "batch_s": 0.0,
                       "batcher_cpu_s": 0.0, "collect_s": 0.0,
                       "merge_s": 0.0, "decide_s": 0.0, "decode_s": 0.0,
                       "scatter_s": 0.0, "decide_evals": 0,
                       "transfer_bytes": 0}
        self._worker = threading.Thread(target=self._run,
                                        name="repro-serving-batcher",
                                        daemon=True)
        self._worker.start()

    # ------------------------------------------------------------- submit
    def _packed(self, name: str) -> PackedModel:
        if name in self._direct:
            return self._direct[name].model
        if self.registry is None or name not in self.registry:
            known = sorted(self._direct) + (
                sorted(self.registry.names) if self.registry else [])
            raise KeyError(f"unknown model {name!r} (known: {known})")
        return self.registry.model(name)

    def submit(self, x, *, model: str = "default",
               op: str = "predict") -> Future:
        """Enqueue a request; returns a Future resolving to the decoded
        output for exactly the submitted rows. A 1-D ``x`` is treated
        as a single row (and resolves to a length-1 result)."""
        if op not in _OPS:
            raise ValueError(f"unknown op {op!r}; expected one of {_OPS}")
        with self._stats_lock:
            if self._closed:
                raise RuntimeError("service is closed")
        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None]
        d = self._packed(model).n_features
        if x.ndim != 2 or x.shape[1] != d or x.shape[0] == 0:
            raise ValueError(f"expected a non-empty (n, {d}) request "
                             f"for model {model!r}, got shape {x.shape}")
        fut: Future = Future()
        self._q.put(_Request(model, op, x, fut, time.perf_counter()))
        return fut

    # ------------------------------------------------- blocking shortcuts
    def predict(self, x, *, model: str = "default"):
        return self.submit(x, model=model, op="predict").result()

    def decision_function(self, x, *, model: str = "default"):
        return self.submit(x, model=model,
                           op="decision_function").result()

    # -------------------------------------------------------------- stats
    @property
    def stats(self) -> dict:
        with self._stats_lock:
            s = dict(self._stats)
        s["rows_per_batch"] = (s["n_rows"] / s["n_batches"]
                               if s["n_batches"] else 0.0)
        return s

    # ------------------------------------------------------------ teardown
    def close(self, timeout: Optional[float] = None) -> None:
        """Stop accepting requests, flush everything queued, join the
        worker. Idempotent."""
        with self._stats_lock:
            first = not self._closed
            self._closed = True
        if first:
            # exactly one closer enqueues the sentinel — two racing
            # close() calls used to both pass the unlocked check
            self._q.put(_SENTINEL)
        self._worker.join(timeout)
        # a submit that raced close() may have queued behind the
        # sentinel; fail those futures rather than hanging their callers
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not _SENTINEL:
                req.future.set_exception(
                    RuntimeError("service closed before dispatch"))

    def __enter__(self) -> "ServingService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------- batcher
    def _predictor(self, name: str) -> Predictor:
        if name in self._direct:
            return self._direct[name]
        return self.registry.get(name)

    def _cap(self, name: str) -> int:
        """Rows at which a model's window is full (its predictor's
        max_batch — beyond that the predictor slices anyway)."""
        if name in self._direct:
            return self._direct[name].max_batch
        # host-side cap (don't force admission just to read it); the
        # predictor rounds its max_batch to the same pow2 ladder rung
        return _pow2_floor(self.registry.max_batch)

    def _run(self) -> None:
        n_batch = 0
        while True:
            req = self._q.get()        # idle: outside every span
            if req is _SENTINEL:
                return
            n_batch += 1
            stages = Stages()
            cpu0 = time.thread_time()
            with stages("serve.batch", "batch_s", batch=n_batch) as batch:
                stages.add("queue_wait_s", time.perf_counter() - req.t_submit)
                with stages("serve.collect", "collect_s"):
                    pending, full, closing = self._collect(req, stages)
                batch.set_metadata(requests=len(pending),
                                   rows=sum(r.x.shape[0] for r in pending),
                                   full=full)
                self._flush(pending, stages)
            stages.add("batcher_cpu_s", time.thread_time() - cpu0)
            with self._stats_lock:
                if not closing:
                    self._stats["n_full_flushes" if full
                                else "n_window_flushes"] += 1
                for k, v in stages.totals.items():
                    self._stats[k] += v
            if closing:
                return

    def _collect(self, req: _Request, stages: Stages):
        """The window opened by ``req``: drain the backlog greedily (all
        the batching ``window_ms=0`` gets), then wait out the window,
        until some model's rows reach its cap. Returns the requests,
        whether the window filled, and whether ``close`` ended it."""
        pending = [req]
        rows = {req.model: req.x.shape[0]}
        deadline = time.perf_counter() + self.window_s
        full = req.x.shape[0] >= self._cap(req.model)
        while not full:
            try:
                nxt = self._q.get_nowait()
            except queue.Empty:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
            if nxt is _SENTINEL:
                return pending, full, True
            stages.add("queue_wait_s", time.perf_counter() - nxt.t_submit)
            pending.append(nxt)
            rows[nxt.model] = rows.get(nxt.model, 0) + nxt.x.shape[0]
            full = rows[nxt.model] >= self._cap(nxt.model)
        return pending, full, False

    def _flush(self, pending: list, stages: Stages) -> None:
        """One fused decide + vectorized decode per model present, then
        scatter per-request slices back through the futures."""
        with stages("serve.merge", "merge_s"):
            by_model: dict[str, list] = {}
            for r in pending:
                by_model.setdefault(r.model, []).append(r)
        for name, reqs in by_model.items():
            try:
                pred = self._predictor(name)
                work = pred.work
                with stages("serve.merge", "merge_s"):
                    xcat = (reqs[0].x if len(reqs) == 1
                            else np.concatenate([r.x for r in reqs], axis=0))
                with stages("serve.decide", "decide_s"):
                    df = pred.decision_values(xcat)
                # decode ONCE per op over the merged batch (every op is
                # columnwise), then slice per request
                with stages("serve.decode", "decode_s"):
                    decoded = {op: pred.decode(df, op)
                               for op in {r.op for r in reqs}}
                for k, v in pred.work.items():
                    stages.add(k, v - work[k])
            except Exception as e:                 # noqa: BLE001
                stages.add("n_failed_requests", len(reqs))
                for r in reqs:
                    if not r.future.cancelled():
                        r.future.set_exception(e)
                continue
            with self._stats_lock:
                self._stats["n_requests"] += len(reqs)
                self._stats["n_rows"] += xcat.shape[0]
                self._stats["n_batches"] += 1
                self._stats["max_batch_rows"] = max(
                    self._stats["max_batch_rows"], xcat.shape[0])
            with stages("serve.scatter", "scatter_s"):
                start = 0
                for r in reqs:
                    stop = start + r.x.shape[0]
                    out = decoded[r.op]
                    sl = (out[..., start:stop] if out.ndim > 1
                          else out[start:stop])
                    start = stop
                    if not r.future.cancelled():
                        r.future.set_result(sl)
