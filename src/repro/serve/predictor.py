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
* multiclass labels (OvO and OvR) decode through ONE jitted vote/argmax
  program per pow2 decode width, the model's credit table baked in as
  constants: one upload of the stacked decisions, one launch, one
  fetch of the class indices;
* ``work`` counts what the calls launched and moved, from the shapes
  they ran at: ``decide_evals``, the kernel values of the decide
  programs (padded rows x bank width x tasks of every launch), and
  ``transfer_bytes``, every byte the decide and the decode uploaded or
  fetched.

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

``decision_values`` is thread-safe: concurrent callers each own their
output buffer, jit dispatch is safe under concurrency, and the served-
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
        # SV banks move to device once and stay resident; task_ids stay
        # host-side (they only scatter results back into request order)
        self._banks = tuple(
            (jnp.asarray(g.sv_x), jnp.asarray(g.sv_coef),
             jnp.asarray(g.b), np.asarray(g.task_ids))
            for g in model.buckets)
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
        # decisions as one jitted program, the model's pairs and one-hot
        # credit tables closed over as constants; XLA keeps one
        # executable per (pow2) decode width. Binary and SVR decode on
        # the host and need none.
        self._decode_labels = None
        if model.kind == "svc" and model.strategy != "binary":
            pairs, n_classes = np.asarray(model.pairs), model.n_classes
            strategy, decision = model.strategy, model.decision

            def decode_labels(df):
                return MC.decide_from_pairs(df, pairs, n_classes, strategy,
                                            decision)

            self._decode_labels = jax.jit(decode_labels)
        self.n_requests = 0  # rows served (warmup excluded)
        # predictor-owned ledger of distinct (bank signature, batch
        # bucket) program shapes — what n_programs reports; jax's
        # private jit cache introspection moved across versions
        self._program_sigs: set = set()
        # decode widths served so far — one compiled decode program each
        self._decode_widths: set = set()
        self._work = {"decide_evals": 0, "transfer_bytes": 0}
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
        """Compiled multiclass decode-program count: distinct pow2
        decode widths served so far (0 for binary and SVR models, which
        decode on the host). Counted apart from ``n_programs``, which
        counts decide programs only."""
        with self._lock:
            return len(self._decode_widths)

    @property
    def work(self) -> dict:
        """Cumulative ``decide_evals`` and ``transfer_bytes`` of every
        call so far, warmup included (see module docstring)."""
        with self._lock:
            return dict(self._work)

    def _batch_bucket(self, t: int) -> int:
        return min(self.max_batch, 1 << (max(t, 1) - 1).bit_length())

    def warmup(self, batch_sizes=(1,)) -> "Predictor":
        """Pre-compile the decide programs AND the decode (label) path
        for the given request sizes.

        Warmup rows are synthetic and do NOT count toward
        ``n_requests`` (the served-row counter)."""
        d = self.model.n_features
        for t in batch_sizes:
            # predict() runs decision_values + decode, warming both the
            # decide program and the decode program at this bucket
            self.predict(np.zeros((int(t), d), np.float32))
        # subtract exactly the synthetic rows rather than restoring a
        # pre-warmup snapshot: concurrent real requests served DURING
        # warmup keep their counts (the snapshot restore erased them)
        with self._lock:
            self.n_requests -= sum(int(t) for t in batch_sizes)
        return self

    # ------------------------------------------------------------ serving
    def decision_values(self, xt: np.ndarray) -> np.ndarray:
        """(n_tasks, nt) stacked binary decision values."""
        xt = np.asarray(xt, np.float32)
        if xt.ndim != 2 or xt.shape[1] != self.model.n_features:
            raise ValueError(
                f"expected (n, {self.model.n_features}) request batch, "
                f"got shape {xt.shape}")
        nt = xt.shape[0]
        out = np.empty((self.model.n_tasks, nt), np.float32)
        sigs = []
        evals = moved = 0
        # host spans per slice (and bank): the pad and upload, the jitted
        # call, and the fetch that waits for the device and copies back
        span = jax.profiler.TraceAnnotation
        for start in range(0, nt, self.max_batch):
            stop = min(start + self.max_batch, nt)
            bucket = self._batch_bucket(stop - start)
            with span("serve.upload"):
                zp = np.zeros((bucket, xt.shape[1]), np.float32)
                zp[:stop - start] = xt[start:stop]
                zj = jnp.asarray(zp)
            moved += zp.nbytes
            if self.model.feature_map is not None:
                a, fb = self._fm_arrays
                w, lb = self._linear
                with span("serve.launch"):
                    df = self._decide_lowrank(a, fb, w, lb, zj)
                with span("serve.fetch"):
                    df = np.asarray(df)
                    out[:, start:stop] = df[:, :stop - start]
                moved += df.nbytes
                sigs.append(("lowrank", bucket))
                continue
            for sv_x, sv_coef, b, task_ids in self._banks:
                if sv_x.shape[1] == 0:  # empty-SV bank: constant bias
                    bias = np.asarray(b)
                    out[task_ids, start:stop] = bias[:, None]
                    moved += bias.nbytes
                    continue
                with span("serve.launch"):
                    df = self._decide(sv_x, sv_coef, b, zj)
                with span("serve.fetch"):
                    df = np.asarray(df)
                    out[task_ids, start:stop] = df[:, :stop - start]
                evals += bucket * sv_x.shape[0] * sv_x.shape[1]
                moved += df.nbytes
                sigs.append((sv_x.shape, str(sv_x.dtype), bucket))
        with self._lock:
            self._program_sigs.update(sigs)
            self.n_requests += nt
            self._work["decide_evals"] += evals
            self._work["transfer_bytes"] += moved
        return out

    def decode(self, df: np.ndarray, op: str = "predict") -> np.ndarray:
        """Post-process stacked decision values ``df (n_tasks, nt)``
        into the requested output — the per-model decode step the
        dynamic-batching service shares across every request of a fused
        batch (compute ``decision_values`` once, decode column slices
        per request).

        op: "values" (the stacked df, unchanged), "decision_function"
        (margins, sklearn orientation) or "predict" (labels / SVR
        values). Multiclass labels come from the compiled decode program
        at ``df``'s pow2 width; binary labels and SVR values are read on
        the host."""
        m = self.model
        if op == "values":
            return df
        if op == "decision_function":
            return df[0] if m.strategy in ("binary", "svr") else df
        if op != "predict":
            raise ValueError(f"unknown decode op {op!r}; expected "
                             "'predict', 'decision_function' or 'values'")
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
            self._decode_widths.add(bucket)
            self._work["transfer_bytes"] += df.nbytes + idx.nbytes
        return m.classes[idx[:nt]]

    def decision_function(self, xt: np.ndarray) -> np.ndarray:
        """Margins in the training-side convention: (nt,) for binary
        SVC and SVR (positive margin => ``classes[1]``), (n_tasks, nt)
        stacked for multiclass."""
        return self.decode(self.decision_values(xt), "decision_function")

    def predict(self, xt: np.ndarray) -> np.ndarray:
        """Class labels (SVC) or regression values (SVR)."""
        return self.decode(self.decision_values(xt), "predict")
