"""§Perf hillclimb driver: variant -> corrected roofline terms.

    PYTHONPATH=src python -m repro.roofline.hillclimb \
        --arch deepseek_67b --shape train_4k --variant bf16_scores \
        --out results/perf.jsonl

Each variant toggles runtime knobs (repro.models.runtime), then measures:
  * depth-differential corrected FLOPs / bytes / collective bytes
    (unrolled shallow compiles — true per-layer costs), and
  * full-depth compile temp/arg memory (peak per-device bytes — the
    "does it fit 16 GB HBM" check).

The 512-forced-host-device XLA environment is set up in ``main()``
(before any jax import), NOT at import time: other tooling (the SVM
kernel autotuner, ``inspect_hlo``) imports this module for its VARIANTS
table, and an import-time ``os.environ`` mutation would silently poison
every jax backend in the host process.
"""
import argparse
import json
import os
import sys


def setup_env(n_devices: int = 512) -> None:
    """Force the multi-host-device CPU platform for dry-run compiles.

    Must run before jax initializes its backends — i.e. first thing in
    a CLI entry point, never at module import.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_devices} "
            + flags).strip()

VARIANTS = {
    "baseline": {},
    "bf16_scores": {"scores_bf16": True},
    "remat_dots": {"remat_policy": "dots"},
    "remat_none": {"remat_policy": "none"},
    "chunk_attn_4k": {"chunked_threshold": 4096},
    "bf16+dots": {"scores_bf16": True, "remat_policy": "dots"},
    "bf16+chunk": {"scores_bf16": True, "chunked_threshold": 4096},
    "bf16+dots+chunk": {"scores_bf16": True, "remat_policy": "dots",
                        "chunked_threshold": 4096},
    "onehot_embed": {"embed_onehot": True},
    "moe_grouped": {"moe_grouped": True},
    "grouped+bf16": {"moe_grouped": True, "scores_bf16": True},
    "onehot+bf16": {"embed_onehot": True, "scores_bf16": True},
    "accum4": {"microbatches": 4},
    "fit4": {"scores_bf16": True, "chunked_threshold": 4096,
             "microbatches": 4},
    "fit8": {"scores_bf16": True, "chunked_threshold": 4096,
             "microbatches": 8},
    "grouped+accum4": {"moe_grouped": True, "microbatches": 4},
    "serve_tp": {"serve_pure_tp": True},
    "serve_tp+grouped": {"serve_pure_tp": True, "moe_grouped": True},
    "window_sp": {"window_cache_sp": True},
    "serve_tp+window_sp": {"serve_pure_tp": True, "window_cache_sp": True},
    "serve_tp+window_sp+onehot": {"serve_pure_tp": True,
                                  "window_cache_sp": True,
                                  "embed_onehot": True},
    "gather_w": {"gather_weights": True},
    "gather_w+accum4": {"gather_weights": True, "microbatches": 4},
    "gather_w+accum8": {"gather_weights": True, "microbatches": 8},
    "accum8": {"microbatches": 8},
    "accum16": {"microbatches": 16},
    "accum16+chunk": {"microbatches": 16, "chunked_threshold": 4096},
    "accum32": {"microbatches": 32},
    "xe_shard": {"moe_xe_shard": True},
    "xe_shard+cap1": {"moe_xe_shard": True},  # cap handled via cfg override
    "mla_pad": {"mla_pad_heads": True},
    "mla_pad+accum8": {"mla_pad_heads": True, "microbatches": 8},
}


def run(arch: str, shape: str, variant: str, *, multi_pod: bool = False,
        skip_full: bool = False) -> dict:
    from repro.models import runtime as RT
    RT.set_flags(**VARIANTS[variant])

    from repro.roofline.differential import probe
    from repro.roofline.collect import V5E, roofline_terms

    res = probe(arch, shape, multi_pod=multi_pod)
    if res["status"] != "ok":
        return res
    c = res["corrected"]
    # the gradient-accumulation scan body is counted once by
    # cost_analysis (like any scan); each microbatch is identical work,
    # so totals scale by MICROBATCHES
    m = RT.MICROBATCHES
    if m > 1:
        c = {k: v * m for k, v in c.items()}
        res["corrected"] = c
    terms = roofline_terms(flops=c["flops"], hbm_bytes=c["bytes_accessed"],
                           collective_bytes_total=c["collective_total"],
                           device_kind=V5E)

    full_mem = None
    if not skip_full:
        from repro.models import runtime as RT2
        RT2.set_unroll(False)      # full-depth compile uses scans
        from repro.launch.dryrun import lower_combo
        fr = lower_combo(arch, shape, multi_pod=multi_pod)
        full_mem = fr["memory"]

    return {
        "arch": arch, "shape": shape, "variant": variant,
        "status": "ok",
        "corrected": {k: v for k, v in c.items()
                      if not k.startswith("per_layer")},
        "terms": terms,
        "full_depth_memory": full_mem,
    }


def main(argv=None):
    setup_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--out", default="")
    ap.add_argument("--skip-full", action="store_true")
    args = ap.parse_args(argv)
    res = run(args.arch, args.shape, args.variant,
              skip_full=args.skip_full)
    if res["status"] == "ok":
        t = res["terms"]
        mem = res.get("full_depth_memory")
        mem_s = (f" temp={mem['temp_bytes'] / 2**30:.1f}GiB"
                 if mem else "")
        print(f"{args.arch} x {args.shape} [{args.variant}]: "
              f"compute={t['t_compute_s'] * 1e3:.1f}ms "
              f"memory={t['t_memory_s'] * 1e3:.1f}ms "
              f"coll={t['t_collective_s'] * 1e3:.1f}ms "
              f"dominant={t['dominant']}{mem_s}", flush=True)
    else:
        print(res["status"])
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(res) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
