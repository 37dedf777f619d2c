import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST be run as its own process (the XLA_FLAGS line above precedes every
other import — jax locks the device count at first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3_12b \
        --shape train_4k [--multi-pod] [--out results.json]

    PYTHONPATH=src python -m repro.launch.dryrun --all

For each combo it builds the production mesh, abstract params/batch
(ShapeDtypeStruct — zero allocation), jits the train/prefill/decode step
with explicit in/out shardings, lowers, compiles, and records:

  * memory_analysis()      (per-device bytes: args/temp/output)
  * cost_analysis()        (per-device HLO FLOPs + bytes accessed)
  * collective bytes       (parsed from post-SPMD compiled HLO)

Results are appended as JSON lines for the roofline report.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_NAMES, INPUT_SHAPES, get_config,
                                supports_shape)
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SP
from repro.models.model import Model, abstract_init
from repro.optim.adamw import AdamW
from repro.roofline.collect import collective_bytes, summarize_cost
from repro.sharding import rules
from repro.training.train import make_train_step


def _shardings(logical_tree, mesh, *, serve_pure_tp=False):
    return jax.tree.map(
        lambda lg: NamedSharding(
            mesh, rules.spec(lg, mesh, serve_pure_tp=serve_pure_tp)),
        logical_tree, is_leaf=lambda x: isinstance(x, tuple))


def _fit(shardings, shapes, mesh):
    """Null out sharded axes whose dim isn't divisible by the axis size
    (e.g. batch=1 on the dp axes for long_500k) — standard fallback."""
    import numpy as _np

    def one(sh, aval):
        spec = list(sh.spec) + [None] * (len(aval.shape) - len(sh.spec))
        new = []
        for dim, ax in zip(aval.shape, spec):
            if ax is None:
                new.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(_np.prod([mesh.shape[a] for a in axes]))
            new.append(ax if dim % size == 0 else None)
        return NamedSharding(mesh, P(*new))
    return jax.tree.map(one, shardings, shapes)


def _broadcast_cache(shardings, shapes):
    """Validate the cache sharding tree matches the cache shape tree."""
    jax.tree_util.tree_structure(shapes)  # noqa: touch both trees
    return shardings


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                remat: bool = True, extra_tag: str = "",
                n_layers: int = 0, cfg_overrides: dict | None = None,
                keep_hlo: bool = False):
    """Returns a result dict (or raises). No real allocation happens.

    ``n_layers`` overrides depth (the roofline differential probes use
    two shallow depths to recover per-layer costs — XLA cost_analysis
    counts scan bodies ONCE, not per trip)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if n_layers:
        kw = {"n_layers": n_layers}
        if cfg.arch_type == "audio":
            kw["encoder_layers"] = n_layers
        cfg = _dc.replace(cfg, **kw)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    if not supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped (full attention at 500k; DESIGN.md §6)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, remat=remat and shape.kind == "train")

    from repro.models import runtime as RT
    serve_tp = RT.SERVE_PURE_TP and shape.kind != "train"
    t0 = time.time()
    params_shapes, logical = abstract_init(model)
    p_shardings = _fit(_shardings(logical, mesh, serve_pure_tp=serve_tp),
                       params_shapes, mesh)

    if shape.kind == "train":
        opt = AdamW(lr=1e-4)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        # opt state shards exactly like params (mu/nu trees) + scalar step
        o_shardings = type(opt_shapes)(
            step=NamedSharding(mesh, P()),
            mu=p_shardings, nu=p_shardings)
        bspecs, bshard = SP.batch_specs(cfg, shape, mesh)
        b_shardings = _fit({k: NamedSharding(mesh, v)
                            for k, v in bshard.items()}, bspecs, mesh)
        step_fn = make_train_step(model, opt)
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shardings, o_shardings, b_shardings),
            out_shardings=(p_shardings, o_shardings,
                           NamedSharding(mesh, P())))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, opt_shapes, bspecs)
    elif shape.kind == "prefill":
        bspecs, bshard = SP.batch_specs(cfg, shape, mesh)
        b_shardings = _fit({k: NamedSharding(mesh, v)
                            for k, v in bshard.items()}, bspecs, mesh)
        cache_shapes = jax.eval_shape(
            lambda: model.cache_init(shape.global_batch, shape.seq_len))
        c_shardings = _fit(_broadcast_cache(_shardings(model.cache_specs(),
                                                       mesh), cache_shapes),
                           cache_shapes, mesh)
        jitted = jax.jit(
            model.prefill,
            in_shardings=(p_shardings, b_shardings, c_shardings),
            out_shardings=(NamedSharding(mesh, P()), c_shardings))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, bspecs, cache_shapes)
    else:  # decode
        tok_spec, tok_ps = SP.decode_token_specs(cfg, shape, mesh)
        cache_shapes = jax.eval_shape(
            lambda: model.cache_init(shape.global_batch, shape.seq_len))
        c_shardings = _fit(_broadcast_cache(_shardings(model.cache_specs(),
                                                       mesh), cache_shapes),
                           cache_shapes, mesh)
        batch_ax = tok_ps[0] if len(tok_ps) else None
        logits_sh = NamedSharding(mesh, P(batch_ax, "model"))
        jitted = jax.jit(
            model.decode_step,
            in_shardings=(p_shardings, NamedSharding(mesh, tok_ps),
                          c_shardings),
            out_shardings=(logits_sh, c_shardings))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, tok_spec, cache_shapes)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    res = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "tag": extra_tag,
        "status": "ok",
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": summarize_cost(cost),
        "collectives": coll,
    }
    if keep_hlo:
        res["_hlo"] = hlo_text
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every supported (arch x shape) on this mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    combos = []
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    if args.all:
        for a in ARCH_NAMES:
            for s in INPUT_SHAPES:
                for mp in meshes:
                    combos.append((a, s, mp))
    else:
        assert args.arch and args.shape
        for mp in meshes:
            combos.append((args.arch, args.shape, mp))

    out_f = open(args.out, "a") if args.out else None
    n_ok = n_skip = n_fail = 0
    for arch, shp, mp in combos:
        label = f"{arch} x {shp} x {'2x16x16' if mp else '16x16'}"
        try:
            res = lower_combo(arch, shp, multi_pod=mp,
                              remat=not args.no_remat, extra_tag=args.tag)
            if res["status"].startswith("skip"):
                n_skip += 1
                print(f"SKIP {label}: {res['status']}", flush=True)
            else:
                n_ok += 1
                print(f"OK   {label}: compile={res['compile_s']}s "
                      f"flops/dev={res['cost'].get('flops', 0):.3e} "
                      f"coll={res['collectives']['total_bytes']:.3e}B",
                      flush=True)
        except Exception as e:
            n_fail += 1
            res = {"arch": arch, "shape": shp, "multi_pod": mp,
                   "status": f"FAIL: {type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
        if out_f:
            out_f.write(json.dumps(res) + "\n")
            out_f.flush()
    if out_f:
        out_f.close()
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
