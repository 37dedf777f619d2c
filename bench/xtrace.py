"""Reduction of a JAX profiler trace to device busy and idle time.

The profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, events with
a start and a duration in nanoseconds on one clock. A TPU's plane is
named ``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per
operation that ran. The harness's own spans (``bench/spans.py``) are
events of the same names on a host plane.

Per chip: busy is the union of the operations' intervals, idle gaps
are the holes between them inside the traced window, collective time
is the summed duration of all-reduce, all-gather and the other
cross-chip operations. Each idle gap is charged to the harness span
that was open at its middle (``other`` when none was).
"""
from __future__ import annotations

import glob
import os
import re

from bench.spans import NAMES as HOST_SPANS

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute", re.IGNORECASE)
TOP = 10


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _span_at(spans, t) -> str:
    best = None
    for name, s, e in spans:
        # the innermost (shortest) open span wins
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "other"


def reduce_planes(planes, n_devices: int) -> dict:
    """``planes``: iterable of (name, [(line name, [(event name, start_ns,
    duration_ns)])]). Returns busy and collective seconds per device,
    summed op seconds by name, the longest idle gaps, the window."""
    devices, spans = {}, []
    for pname, lines in planes:
        m = DEVICE.match(pname)
        for lname, events in lines:
            if m and lname == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(events)
            elif pname.startswith("/host:"):
                spans.extend((n, s, s + d) for n, s, d in events
                             if n in HOST_SPANS)
    ids = sorted(devices)[:n_devices]
    if not ids:
        raise ValueError("the trace holds no device operations")
    starts = [min(s for _, s, _ in devices[i]) for i in ids]
    ends = [max(s + d for _, s, d in devices[i]) for i in ids]
    lo = min(starts + [s for _, s, _ in spans])
    hi = max(ends + [e for _, _, e in spans])
    busy, coll, ops, gaps = [], [], {}, []
    for i in ids:
        merged = union((s, s + d) for _, s, d in devices[i])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        coll.append(sum(d for n, _, d in devices[i]
                        if COLLECTIVE.search(n)) * 1e-9)
        for n, _, d in devices[i]:
            ops[n] = ops.get(n, 0.0) + d * 1e-9 / len(ids)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, (s + e) / 2), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (hi - lo) * 1e-9, "busy_s_per_device": busy,
            "busy_s": sum(busy) / len(busy),
            "collective_s_per_device": coll,
            "collective_s": sum(coll) / len(coll),
            "op_seconds": ops,
            "breakdown": {
                "device_ops": [[n, s] for n, s in
                               sorted(ops.items(), key=lambda kv: -kv[1])
                               [:TOP]],
                "idle_gaps": [[n, s] for n, s in gaps[:TOP]]}}


def read_xplane(path: str):
    """The planes of an ``.xplane.pb`` file in ``reduce_planes``'s form,
    keeping only what it reads: the devices' operations and the
    harness's spans."""
    from jax.profiler import ProfileData
    out = []
    for pl in ProfileData.from_file(path).planes:
        device, host = DEVICE.match(pl.name), pl.name.startswith("/host:")
        lines = []
        for ln in pl.lines:
            if device and ln.name == OPS_LINE:
                lines.append((ln.name, [(e.name, e.start_ns, e.duration_ns)
                                        for e in ln.events]))
            elif host:
                lines.append((ln.name, [(e.name, e.start_ns, e.duration_ns)
                                        for e in ln.events
                                        if e.name in HOST_SPANS]))
        out.append((pl.name, lines))
    return out


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise ValueError(f"no xplane trace under {trace_dir}")
    return reduce_planes(read_xplane(max(paths, key=os.path.getmtime)),
                         n_devices)
