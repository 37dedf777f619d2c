"""Serving subsystem: packed artifacts, the batched Predictor, and the
async dynamic-batching service layer.

    from repro import serve

    packed = serve.pack(clf, sv_dtype="fp16")   # quantized SV bank
    serve.save("model.npz", packed)             # versioned npz schema
    pred = serve.Predictor(serve.load("model.npz"), engine="pallas")
    pred.predict(Z)                             # jit-cached batched serving

    svc = serve.ServingService(packed, window_ms=2.0)   # open-loop traffic
    svc.submit(z).result()                      # dynamic-batched future
    reg = serve.ModelRegistry(max_resident=4)   # multi-model LRU residency

See ``serve.artifact`` for the artifact schema (v1/v2/v3 + SV-bank
quantization), ``serve.reference`` for the float64 decision oracle
served values are held to, ``serve.predictor`` for the bucket/jit-cache
behavior, ``serve.registry`` for LRU device residency and
``serve.service`` for the batching-window semantics.
"""
from repro.serve.artifact import (LowRankMap, PackedModel,  # noqa: F401
                                  TaskBucket, SCHEMA_NAME, SCHEMA_VERSION,
                                  SCHEMA_VERSION_CLASSIC,
                                  SCHEMA_VERSION_QUANT, SCHEMA_VERSIONS,
                                  SV_DTYPES, load, pack, quantize, save)
from repro.serve.predictor import Predictor, serving_config  # noqa: F401
from repro.serve.registry import ModelRegistry  # noqa: F401
from repro.serve import reference  # noqa: F401
from repro.serve.service import ServingService  # noqa: F401
