"""Batched serving engine: resident SV banks + jit-cached decide programs.

The training-side ``decision_function`` rebuilds a ``KernelEngine`` and
re-uploads the support vectors on EVERY call, then loops serving buckets
in Python — fine for evaluating a fit, hopeless under request traffic.
``Predictor`` is the serving-side replacement:

* the packed SV bank (``artifact.PackedModel``) is moved to device once,
  at construction, and stays resident;
* decisions run through ONE jitted program per (bucket shape,
  batch bucket) static configuration — for the pallas backend the fused
  multi-task kernel (``kernels.ops.multitask_decision``, RBF and
  linear kernels; any other kernel is refused at construction), which
  evaluates every stacked task of a bucket against the test batch in a
  single grid; for chunked/dense configs a vmapped ``engine.decide``
  (the reference/fallback path, numerically identical to the legacy
  training-side serving);
* request batches are padding-bucketed: each micro-batch is zero-padded
  up to the next power of two (capped at ``max_batch``; longer requests
  stream in ``max_batch`` slices), so arbitrary request sizes reuse a
  small warm set of compiled programs instead of recompiling per shape;
* ``decide`` leaves a batch's decisions on the device
  (``Decisions``): per slice, every bank's decide program is launched
  and then ONE assembly program per slice width puts the bank outputs
  in task order (width-0 banks contribute their bias), runs the
  multiclass vote (OvO and OvR; the model's pairs and credit tables
  baked in as constants) and splits the batch's rows at the pow2
  boundaries 0, 1, 2, 4, ..., bucket, each piece a (rows, n_tasks)
  block that the TPU stores densely. Nothing is fetched until a
  caller asks: ``decode(dec, "predict")`` fetches the int32 class
  indices of each slice, and ``decision_values(dec.head(n))`` fetches
  the pow2 prefix of pieces that holds the first ``n`` rows. So a
  batch that asks for labels only moves its rows up and its labels
  down, and its decision matrix never crosses the link. Binary and
  SVR models vote nowhere: their labels and values are read on the
  host from the fetched decisions;
* a host ``df`` (``decode(df, "predict")``) decodes through one jitted
  vote program per pow2 width of ``df``: one upload, one launch, one
  fetch of the class indices;
* ``work`` counts what the calls launched and moved, from the shapes
  they ran at: ``decide_evals``, the kernel values of the decide
  programs (padded rows x bank width x tasks of every launch),
  ``transfer_bytes``, every byte that crossed the link (rows up,
  decision values and labels down, a host ``df`` up for its vote), and
  ``resident_votes``, the slices whose labels were fetched while their
  decision values never were.

Padded test rows are sliced off before results leave the predictor, and
padded SV rows carry ``coef == 0``, so padding never changes a served
value. Width-0 banks (the empty-SV degenerate model) serve the constant
bias, matching the training-side behavior.

Quantized packs (``artifact.pack(..., sv_dtype="fp16"|"bf16")``) keep
their SV banks device-resident AT the storage dtype — half the bank
HBM — and every decide program upcasts the bank tiles to f32 before the
cross-Gram contraction, so accumulation is always f32 regardless of how
the bank is stored. fp32 packs are bit-identical to pre-quantization
serving (the upcast is a no-op).

``decide``, ``decision_values`` and ``decode`` are thread-safe:
concurrent callers each own their ``Decisions`` and output buffers, jit
dispatch is safe under concurrency, and the served-
row counter / compiled-program ledger are guarded by a lock — the
dynamic-batching service (``serve.service``) and its submitters may
share one predictor freely.

Low-rank packs (``PackedModel.feature_map`` set) skip the SV-bank
machinery entirely: the feature-map arrays and the stacked linear
weights stay resident, and every batch is one jitted transform +
(rank, n_tasks) matmul — serving cost is independent of the
training-set size.

    pred = Predictor(serve.pack(clf), engine="pallas")
    pred.predict(Z)                   # class labels / SVR values
    pred.decision_function(Z)         # margins, sklearn orientation
    dec = pred.decide(Z)              # decisions left on the device
    pred.decision_values(dec.head(8)) # values of the first 8 rows only
    pred.decode(dec, "predict")       # labels, voted on the device
"""
from __future__ import annotations

import dataclasses
import threading

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import kernel_engine as KE
from repro.core import kernels as K
from repro.core import multiclass as MC
from repro.kernels import ops
from repro.serve.artifact import PackedModel

_OPS = ("predict", "decision_function", "values")


def serving_config(engine: str | KE.EngineConfig) -> KE.EngineConfig:
    """Resolve an engine choice into the serving-side config: serving
    never needs the (sv, sv) training Gram nor the LRU row cache, so
    dense/auto/sharded degrade to chunked; an explicit pallas choice is
    honored. Training-only fields that reference the TRAINING host's
    topology are stripped — in particular ``shard_axis``: a
    sharded-trained model must pack to a config that cannot name a mesh
    axis the serving host does not have."""
    cfg = (engine if isinstance(engine, KE.EngineConfig)
           else KE.EngineConfig(backend=engine))
    backend = "pallas" if cfg.backend == "pallas" else "chunked"
    return dataclasses.replace(cfg, backend=backend, cache_slots=0,
                               shard_axis=None)


def _pow2_floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


@dataclasses.dataclass
class _Slice:
    """One ``max_batch`` slice of a ``Decisions``: its first row, its
    row count, its class indices on the device (None where the model
    votes nowhere), its (rows, n_tasks) decisions on the device split
    at the pow2 row boundaries 0, 1, 2, 4, ..., bucket, and whether any
    of them was fetched."""
    start: int
    rows: int
    labels: object
    pieces: tuple
    fetched: bool = False


class Decisions:
    """The stacked decisions of one request batch, left on the device by
    ``Predictor.decide``. ``head(n)`` is a view of its first ``n`` rows
    (the slices are shared), which ``decision_values`` fetches as the
    pow2 prefix of each slice's pieces that holds them."""

    __slots__ = ("slices", "n_rows")

    def __init__(self, slices: tuple, n_rows: int):
        self.slices, self.n_rows = slices, n_rows

    def head(self, n: int) -> "Decisions":
        return Decisions(self.slices, min(n, self.n_rows))

    def _spans(self):
        """(slice, rows of it in this view) for every slice the view
        reaches."""
        for s in self.slices:
            rows = min(s.rows, self.n_rows - s.start)
            if rows <= 0:
                break
            yield s, rows


class Predictor:
    """Serve a ``PackedModel``; see module docstring."""

    # the served-row counter and program ledger are mutated by every
    # concurrent decision_values caller (enforced by analysis rule R004)
    _GUARDED_BY = {"n_requests": "_lock", "_program_sigs": "_lock",
                   "_decode_widths": "_lock", "_work": "_lock"}

    def __init__(self, model: PackedModel, *,
                 engine: str | KE.EngineConfig = "auto",
                 max_batch: int = 1024):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.model = model
        # max_batch is a rung on the pow2 padding ladder, not a free
        # integer: an off-ladder cap (say 1000) would pad 600-row
        # requests to a 1000-row program shape — one silently compiled
        # extra executable per such size class. Round DOWN to the
        # largest pow2 <= max_batch so the cap itself is on-ladder and
        # never exceeds what the caller asked for.
        self.max_batch = _pow2_floor(max_batch)
        self.engine_cfg = serving_config(engine)
        if (self.engine_cfg.backend == "pallas"
                and model.feature_map is None
                and model.kernel.name not in ("rbf", "linear")):
            raise ValueError(
                f"engine='pallas' serves the rbf and linear kernels; "
                f"this model's kernel is {model.kernel.name!r} — serve it "
                f"with engine='chunked'")
        # SV banks move to device once and stay resident; the task ids
        # become one row permutation the assembly program closes over
        self._banks = tuple(
            (jnp.asarray(g.sv_x), jnp.asarray(g.sv_coef), jnp.asarray(g.b))
            for g in model.buckets)
        # row t of the task-ordered matrix is row _order[t] of the bank
        # outputs stacked in bank order; None where that is the identity
        # (one bank in task order, or a low-rank pack)
        self._order = None
        if model.buckets:
            order = np.argsort(np.concatenate(
                [np.asarray(g.task_ids) for g in model.buckets]))
            if (order != np.arange(len(order))).any():
                self._order = order
        if model.feature_map is not None:
            # low-rank pack: resident map arrays + stacked linear
            # weights; one jitted transform+matmul program per batch
            # bucket, no SV bank at all
            fm = model.feature_map
            self._fm_arrays = (jnp.asarray(fm.a), jnp.asarray(fm.b))
            self._linear = (jnp.asarray(model.linear_w),
                            jnp.asarray(model.linear_b))
            kind, kp = fm.kind, model.kernel
            gram_dtype = self.engine_cfg.gram_dtype

            def lowrank_decide(a, b, w, lb, z):
                from repro.core import approx
                m = approx.map_from_arrays(kind, kp, a, b,
                                           gram_dtype=gram_dtype)
                return K.f32_dot(m.transform(z), w.T).T + lb[:, None]

            self._decide_lowrank = jax.jit(lowrank_decide)
        # one jitted callable; XLA caches one executable per distinct
        # (bucket shape, batch bucket) argument signature
        self._decide = jax.jit(self._decide_stack)
        # multiclass label decode: the vote/argmax over the stacked
        # decisions, the model's pairs and one-hot credit tables closed
        # over as constants. It runs inside the assembly program on the
        # device, and as a jitted program of its own for a host df; XLA
        # keeps one executable of each per width. Binary and SVR decode
        # on the host and need neither.
        self._vote = self._decode_labels = None
        if model.kind == "svc" and model.strategy != "binary":
            pairs, n_classes = np.asarray(model.pairs), model.n_classes
            strategy, decision = model.strategy, model.decision

            def decode_labels(df):
                return MC.decide_from_pairs(df, pairs, n_classes, strategy,
                                            decision)

            self._vote = decode_labels
            self._decode_labels = jax.jit(decode_labels)
        # one assembly (+ vote) executable per slice width
        self._assemble = jax.jit(self._assemble_slice, static_argnums=1)
        self.n_requests = 0  # rows served (warmup excluded)
        # predictor-owned ledger of distinct (bank signature, batch
        # bucket) program shapes — what n_programs reports; jax's
        # private jit cache introspection moved across versions
        self._program_sigs: set = set()
        # vote widths served so far — one compiled vote program each,
        # keyed ("device", slice width) or ("host", pow2 width of a df)
        self._decode_widths: set = set()
        self._work = {"decide_evals": 0, "transfer_bytes": 0,
                      "resident_votes": 0}
        self._lock = threading.Lock()

    # ---------------------------------------------------------- programs
    def _decide_stack(self, sv_x, sv_coef, b, z):
        """(T, w, d) stacked bank x (B, d) batch -> (T, B) decisions."""
        # quantized banks (fp16/bf16 packs) upcast to f32 here, inside
        # the program, so the contraction accumulates in f32 while the
        # resident bank stays at the storage dtype; a no-op for fp32
        sv_x = sv_x.astype(jnp.float32)
        sv_coef = sv_coef.astype(jnp.float32)
        kp = self.model.kernel
        if self.engine_cfg.backend == "pallas":
            return ops.multitask_decision(
                z, sv_x, sv_coef, b, gamma=kp.gamma, mode=kp.name,
                compute_dtype=self.engine_cfg.gram_dtype)

        def one(sv, cf, bb):
            return KE.make_engine(sv, kp, self.engine_cfg).decide(z, cf, bb)

        return jax.vmap(one)(sv_x, sv_coef, b)

    def _assemble_slice(self, parts, bucket):
        """One slice's bank outputs ((T_g, bucket) decisions, or (T_g,)
        biases of width-0 banks) -> its class indices (None where the
        model votes nowhere) and its decisions in task order, transposed
        to (bucket, n_tasks) — a few rows of it are then a dense block
        in the device's tiled layout, where a few columns of
        (n_tasks, bucket) would pad each to a full tile — and split at
        the pow2 row boundaries 0, 1, 2, 4, ..., bucket. A copy of the
        values: the decide programs' outputs are served bit for bit."""
        cols = [p if p.ndim == 2 else jnp.broadcast_to(
            p.astype(jnp.float32)[:, None], (p.shape[0], bucket))
            for p in parts]
        df = cols[0] if len(cols) == 1 else jnp.concatenate(cols)
        if self._order is not None:
            df = df[self._order]
        labels = None if self._vote is None else self._vote(df)
        edges = [0] + [1 << i for i in range(bucket.bit_length())]
        return labels, tuple(df.T[a:b] for a, b in zip(edges, edges[1:]))

    @property
    def n_programs(self) -> int:
        """Compiled decide-program count: distinct (bank shape/dtype,
        batch bucket) signatures served so far. Owned by the predictor
        — it used to read the private ``jit._cache_size()``, which
        moved across jax versions and returned -1 when absent."""
        with self._lock:
            return len(self._program_sigs)

    @property
    def n_decode_programs(self) -> int:
        """Compiled multiclass vote-program count: the slice widths
        ``decide`` assembled and voted on the device, plus the pow2
        widths of the host ``df`` that ``decode`` voted on (0 for binary
        and SVR models, which vote nowhere). Counted apart from
        ``n_programs``, which counts decide programs only."""
        with self._lock:
            return len(self._decode_widths)

    @property
    def work(self) -> dict:
        """Cumulative ``decide_evals``, ``transfer_bytes`` and
        ``resident_votes`` of every call so far, warmup included (see
        module docstring)."""
        with self._lock:
            return dict(self._work)

    def _batch_bucket(self, t: int) -> int:
        return min(self.max_batch, 1 << (max(t, 1) - 1).bit_length())

    def warmup(self, batch_sizes=(1,)) -> "Predictor":
        """Pre-compile the decide programs AND the assembly and vote
        program for the given request sizes. A pow2 prefix of decision
        values needs no program of its own: it is a run of the pieces
        the assembly program already returns.

        Warmup rows are synthetic and do NOT count toward
        ``n_requests`` (the served-row counter)."""
        d = self.model.n_features
        for t in batch_sizes:
            # predict() runs decide + decode, warming the decide
            # programs and the assembly program at this bucket
            self.predict(np.zeros((int(t), d), np.float32))
        # subtract exactly the synthetic rows rather than restoring a
        # pre-warmup snapshot: concurrent real requests served DURING
        # warmup keep their counts (the snapshot restore erased them)
        with self._lock:
            self.n_requests -= sum(int(t) for t in batch_sizes)
        return self

    # ------------------------------------------------------------ serving
    def needs_values(self, op: str) -> bool:
        """Whether ``op``'s answer is read from fetched decision values:
        every op but a multiclass "predict", which the device votes."""
        return op != "predict" or self._vote is None

    def decide(self, xt: np.ndarray) -> Decisions:
        """Upload the (n, d) request batch in padded slices and launch
        every bank's decide program and the slice's assembly program on
        each; nothing is fetched (see ``Decisions``)."""
        xt = np.asarray(xt, np.float32)
        if xt.ndim != 2 or xt.shape[1] != self.model.n_features:
            raise ValueError(
                f"expected (n, {self.model.n_features}) request batch, "
                f"got shape {xt.shape}")
        nt = xt.shape[0]
        slices, sigs, widths = [], [], []
        evals = moved = 0
        # host spans per slice: the pad and upload, then the launches
        span = jax.profiler.TraceAnnotation
        for start in range(0, nt, self.max_batch):
            stop = min(start + self.max_batch, nt)
            bucket = self._batch_bucket(stop - start)
            with span("serve.upload"):
                zp = np.zeros((bucket, xt.shape[1]), np.float32)
                zp[:stop - start] = xt[start:stop]
                zj = jnp.asarray(zp)
            moved += zp.nbytes
            with span("serve.launch"):
                if self.model.feature_map is not None:
                    a, fb = self._fm_arrays
                    w, lb = self._linear
                    parts = [self._decide_lowrank(a, fb, w, lb, zj)]
                    sigs.append(("lowrank", bucket))
                else:
                    parts = []
                    for sv_x, sv_coef, b in self._banks:
                        if sv_x.shape[1] == 0:  # empty-SV bank: its bias
                            parts.append(b)
                            continue
                        parts.append(self._decide(sv_x, sv_coef, b, zj))
                        evals += bucket * sv_x.shape[0] * sv_x.shape[1]
                        sigs.append((sv_x.shape, str(sv_x.dtype), bucket))
                labels, pieces = self._assemble(tuple(parts), bucket)
            if labels is not None:
                widths.append(("device", bucket))
            slices.append(_Slice(start, stop - start, labels, pieces))
        with self._lock:
            self._program_sigs.update(sigs)
            self._decode_widths.update(widths)
            self.n_requests += nt
            self._work["decide_evals"] += evals
            self._work["transfer_bytes"] += moved
        return Decisions(tuple(slices), nt)

    def decision_values(self, xt) -> np.ndarray:
        """(n_tasks, nt) stacked binary decision values of a request
        batch, or of the rows of a ``Decisions`` (``decide(x).head(k)``:
        only the pow2 prefix of each slice that holds them crosses the
        link)."""
        dec = xt if isinstance(xt, Decisions) else self.decide(xt)
        spans = list(dec._spans())
        with jax.profiler.TraceAnnotation("serve.fetch"):
            # pieces 0..k-1 hold rows [0, 2**(k-1)): the pow2 prefix
            got = jax.device_get([s.pieces[:(rows - 1).bit_length() + 1]
                                  for s, rows in spans])
        out = np.empty((self.model.n_tasks, dec.n_rows), np.float32)
        moved = 0
        for (s, rows), pieces in zip(spans, got):
            s.fetched = True
            out[:, s.start:s.start + rows] = np.concatenate(
                pieces)[:rows].T
            moved += sum(p.nbytes for p in pieces)
        with self._lock:
            self._work["transfer_bytes"] += moved
        return out

    def _labels(self, dec: Decisions) -> np.ndarray:
        """Class labels of a ``Decisions``, from the class indices the
        assembly program voted on the device: one fetch per slice."""
        spans = list(dec._spans())
        with jax.profiler.TraceAnnotation("serve.fetch"):
            idx = jax.device_get([s.labels for s, _ in spans])
        with self._lock:
            self._work["transfer_bytes"] += sum(i.nbytes for i in idx)
            self._work["resident_votes"] += sum(
                not s.fetched for s, _ in spans)
        return self.model.classes[np.concatenate(
            [i[:rows] for (_, rows), i in zip(spans, idx)]
            or [np.zeros(0, np.int32)])]

    def decode(self, df, op: str = "predict") -> np.ndarray:
        """Post-process stacked decision values into the requested
        output — the per-model decode step the dynamic-batching service
        shares across every request of a fused batch (decide once,
        decode column slices per request). ``df`` is a host
        ``(n_tasks, nt)`` array or a ``Decisions``.

        op: "values" (the stacked df, unchanged), "decision_function"
        (margins, sklearn orientation) or "predict" (labels / SVR
        values). Multiclass labels of a ``Decisions`` are the indices
        its assembly program voted on the device, and nothing else is
        fetched; those of a host df come from the compiled vote program
        at ``df``'s pow2 width. Binary labels and SVR values are read on
        the host."""
        m = self.model
        if op not in _OPS:
            raise ValueError(f"unknown decode op {op!r}; expected "
                             "'predict', 'decision_function' or 'values'")
        if isinstance(df, Decisions):
            if not self.needs_values(op):
                return self._labels(df)
            df = self.decision_values(df)
        if op == "values":
            return df
        if op == "decision_function":
            return df[0] if m.strategy in ("binary", "svr") else df
        if m.kind == "svr":
            return df[0]
        if m.strategy == "binary":
            return m.classes[(df[0] > 0).astype(np.int64)]
        # pad onto the pow2 ladder: the compiled decode program is keyed
        # on width, so decoding at the raw width would compile one
        # program per odd request size (a multi-hundred-ms stall apiece
        # under open-loop traffic). Padded columns (df == 0) are decoded
        # and discarded — the decision is columnwise.
        nt = df.shape[1]
        bucket = 1 << max(nt - 1, 0).bit_length()
        if bucket > nt:
            dfp = np.zeros((df.shape[0], bucket), np.float32)
            dfp[:, :nt] = df
            df = dfp
        # one upload, one launch, one fetch of the (bucket,) indices
        idx = np.asarray(self._decode_labels(df))
        with self._lock:
            self._decode_widths.add(("host", bucket))
            self._work["transfer_bytes"] += df.nbytes + idx.nbytes
        return m.classes[idx[:nt]]

    def decision_function(self, xt: np.ndarray) -> np.ndarray:
        """Margins in the training-side convention: (nt,) for binary
        SVC and SVR (positive margin => ``classes[1]``), (n_tasks, nt)
        stacked for multiclass."""
        return self.decode(self.decision_values(xt), "decision_function")

    def predict(self, xt: np.ndarray) -> np.ndarray:
        """Class labels (SVC) or regression values (SVR)."""
        return self.decode(self.decide(xt), "predict")
