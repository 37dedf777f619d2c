"""Roofline report: dryrun JSONL -> the EXPERIMENTS.md tables.

    PYTHONPATH=src python -m repro.roofline.report results/dryrun_*.jsonl

Per (arch x shape x mesh): three roofline terms, dominant bottleneck,
MODEL_FLOPS = 6·N·D (train) / 2·N_active·D (inference) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs.
"""
from __future__ import annotations

import glob
import json
import sys

from repro.configs.base import INPUT_SHAPES, get_config
from repro.roofline.collect import V5E, model_flops, roofline_terms


def load(paths) -> list[dict]:
    rows = []
    for pat in paths:
        for p in glob.glob(pat):
            with open(p) as f:
                for line in f:
                    rows.append(json.loads(line))
    return rows


def analyze(row: dict, diff: dict | None = None) -> dict | None:
    """diff: optional {(arch, shape, multi_pod): corrected-costs dict}
    from the depth-differential probe (scan bodies are otherwise counted
    once by cost_analysis — see repro.roofline.differential)."""
    if row.get("status") != "ok":
        return None
    cfg = get_config(row["arch"])
    shape = INPUT_SHAPES[row["shape"]]
    n_dev = row["n_devices"]
    key = (row["arch"], row["shape"], row.get("multi_pod", False))
    if diff and key in diff:
        c = diff[key]
        flops = c["flops"]
        hbm = c["bytes_accessed"]
        coll = c["collective_total"]
        row = dict(row, corrected=True)
    else:
        flops = row["cost"]["flops"]                  # per device
        hbm = row["cost"]["bytes_accessed"]           # per device
        coll = row["collectives"]["total_bytes"]      # per device
    terms = roofline_terms(flops=flops, hbm_bytes=hbm,
                           collective_bytes_total=coll, device_kind=V5E)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(cfg.param_count(), cfg.active_param_count(),
                         tokens, kind="train")
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(cfg.param_count(), cfg.active_param_count(),
                         tokens, kind="serve")
    else:
        tokens = shape.global_batch                   # one token each
        mf = model_flops(cfg.param_count(), cfg.active_param_count(),
                         tokens, kind="serve")
    mf_per_dev = mf / n_dev
    ratio = mf_per_dev / flops if flops else 0.0
    return dict(row, terms=terms, model_flops_per_dev=mf_per_dev,
                useful_ratio=ratio)


def fmt_table(rows: list[dict], *, multi_pod: bool) -> str:
    out = ["| arch | shape | t_compute | t_memory | t_collective | "
           "dominant | 6ND/HLO | HBM GiB/dev |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r is None or r.get("multi_pod") != multi_pod:
            continue
        t = r["terms"]
        mem_gib = (r["memory"]["argument_bytes"]
                   + r["memory"]["temp_bytes"]) / 2**30
        out.append(
            f"| {r['arch']} | {r['shape']} "
            f"| {t['t_compute_s'] * 1e3:.2f} ms "
            f"| {t['t_memory_s'] * 1e3:.2f} ms "
            f"| {t['t_collective_s'] * 1e3:.2f} ms "
            f"| **{t['dominant']}** "
            f"| {r['useful_ratio']:.2f} "
            f"| {mem_gib:.2f} |")
    return "\n".join(out)


def main(argv=None):
    args = argv or sys.argv[1:]
    paths = [a for a in args if not a.startswith("--diff")]
    diff_paths = [a.split("=", 1)[1] for a in args
                  if a.startswith("--diff=")]
    diff = {}
    for r in load(diff_paths):
        if r.get("status") == "ok":
            # differential probes run single-pod; the per-layer costs
            # apply to the single-pod mesh rows
            diff[(r["arch"], r["shape"], r.get("multi_pod", False))] = \
                r["corrected"]
    rows = [analyze(r, diff) for r in load(paths)]
    rows = [r for r in rows if r]
    rows.sort(key=lambda r: (r["arch"], list(INPUT_SHAPES).index(
        r["shape"])))
    print("## Single-pod (16x16 = 256 chips)\n")
    print(fmt_table(rows, multi_pod=False))
    print("\n## Multi-pod (2x16x16 = 512 chips)\n")
    print(fmt_table(rows, multi_pod=True))
    # dominance summary
    from collections import Counter
    doms = Counter(r["terms"]["dominant"] for r in rows
                   if not r["multi_pod"])
    print(f"\nsingle-pod dominance: {dict(doms)}")
    worst = sorted((r for r in rows if not r["multi_pod"]),
                   key=lambda r: r["useful_ratio"])[:5]
    print("\nworst useful-compute ratios (hillclimb candidates):")
    for r in worst:
        print(f"  {r['arch']} x {r['shape']}: ratio="
              f"{r['useful_ratio']:.3f} dominant="
              f"{r['terms']['dominant']}")
    most_coll = sorted(
        (r for r in rows if not r["multi_pod"]),
        key=lambda r: -(r["terms"]["t_collective_s"]
                        / max(r["terms"]["t_total_est_s"], 1e-12)))[:5]
    print("\nmost collective-bound:")
    for r in most_coll:
        t = r["terms"]
        print(f"  {r['arch']} x {r['shape']}: "
              f"coll={t['t_collective_s'] * 1e3:.2f}ms "
              f"vs total={t['t_total_est_s'] * 1e3:.2f}ms")


if __name__ == "__main__":
    main()
