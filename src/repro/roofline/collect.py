"""Roofline term extraction from compiled dry-run artifacts.

* ``collective_bytes`` parses post-SPMD HLO text and sums the operand
  bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute (cost_analysis does not report collectives).
* ``roofline_terms`` converts (cost, memory, collectives) into the three
  per-device time terms against the published peaks of one device kind
  (``PEAKS``, keyed by ``jax.Device.device_kind``).
"""
from __future__ import annotations

import re
from typing import Optional

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" (system
# architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect (4 links, 50 GB/s each per direction).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
          "ici_bytes_per_s_per_link": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a kind missing from ``PEAKS``
    is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known "
            f"kinds: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
# matches e.g.:  %x = (f32[128]) all-reduce(...), or fused tuple shapes
_COLL_LINE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum RESULT-shape bytes per collective kind (per device, since the
    post-SPMD module is the per-device program)."""
    per_kind: dict[str, int] = {k: 0 for k in _COLL_KINDS}
    counts: dict[str, int] = {k: 0 for k in _COLL_KINDS}
    for m in _COLL_LINE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        if "-done(" in m.group(0):
            continue  # started op already counted
        per_kind[kind] += _shape_bytes(shape_str)
        counts[kind] += 1
    total = sum(per_kind.values())
    return {"per_kind_bytes": per_kind, "counts": counts,
            "total_bytes": total}


def summarize_cost(cost: dict) -> dict:
    out = {"flops": float(cost.get("flops", 0.0)),
           "transcendentals": float(cost.get("transcendentals", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    return out


def roofline_terms(*, flops: float, hbm_bytes: float,
                   collective_bytes_total: float, device_kind: str,
                   ici_links: int = 4) -> dict:
    """Per-device seconds for each roofline term on ``device_kind``.

    collective traffic is divided by the per-chip aggregate ICI bandwidth
    (links x per-link BW) — optimistic ring assumption, consistent across
    configs so RELATIVE comparisons hold.
    """
    pk = peaks(device_kind)
    t_compute = flops / pk["flops_bf16"]
    t_memory = hbm_bytes / pk["hbm_bytes_per_s"]
    t_coll = collective_bytes_total / (ici_links
                                       * pk["ici_bytes_per_s_per_link"])
    dom = max((t_compute, "compute"), (t_memory, "memory"),
              (t_coll, "collective"))
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dom[1],
            "t_total_est_s": max(t_compute, t_memory, t_coll)}


def model_flops(_param_count: int, active_param_count: int, tokens: int,
                *, kind: str) -> float:
    # _param_count: total (vs active) params — informational for MoE
    # callers; the 6ND/2ND rule charges only active params
    """6·N·D rule (training); 2·N·D for inference forward passes."""
    n = active_param_count
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
