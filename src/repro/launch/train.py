"""End-to-end LM training driver (CPU-runnable at reduced scale).

    PYTHONPATH=src python -m repro.launch.train --arch mamba2_780m \
        --reduced --steps 200 --batch 8 --seq 128

Builds the model (optionally the reduced smoke variant), a synthetic
token pipeline, AdamW with cosine schedule, runs the jitted train step,
logs loss, and checkpoints at the end. With ``--mesh dxm`` it builds a
local device mesh (forced host devices) and shards params/batch with the
production rules — the same code path the real pod launcher uses.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_780m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2 -> force 4 host devices (data,model)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override d_model (e.g. ~100M quickstart)")
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args(argv)

    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={d * m} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import get_config, reduced as make_reduced
    from repro.data.lm import token_batches
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model, abstract_init
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.sharding import rules
    from repro.training.train import make_train_step
    from repro.checkpoint import ckpt as CK

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    import dataclasses
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)

    model = Model(cfg)
    params, logical = model.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,}")

    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        shardings = jax.tree.map(
            lambda lg: NamedSharding(mesh, rules.spec(lg, mesh)),
            logical, is_leaf=lambda x: isinstance(x, tuple))
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, s)
            if all(p.shape[i] % (np.prod([mesh.shape[a] for a in
                   (ax if isinstance(ax, tuple) else (ax,))])
                   if ax else 1) == 0
                   for i, ax in enumerate(list(s.spec) + [None] * (
                       p.ndim - len(s.spec)))) else p,
            params, shardings)

    opt = AdamW(lr=cosine_schedule(peak_lr=args.lr, warmup=20,
                                   total=args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    it = token_batches(vocab_size=cfg.vocab_size, batch=args.batch,
                       seq_len=args.seq, n_batches=args.steps, seed=1)
    ctx = jax.set_mesh(mesh) if mesh is not None else None
    if ctx:
        ctx.__enter__()
    try:
        for i, nb in enumerate(it):
            batch = {k: jnp.asarray(v) for k, v in nb.items()}
            if cfg.arch_type == "vlm":
                batch["vision_embeds"] = 0.02 * jnp.ones(
                    (args.batch, cfg.vision_tokens, cfg.d_model),
                    jnp.bfloat16)
            if cfg.arch_type == "audio":
                batch["frames"] = 0.02 * jnp.ones(
                    (args.batch, cfg.encoder_frames, cfg.d_model),
                    jnp.bfloat16)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if i % args.log_every == 0 or i == args.steps - 1:
                dt = time.time() - t0
                print(f"step {i:5d} loss {losses[-1]:.4f} "
                      f"({dt / (i + 1):.3f}s/step)", flush=True)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)

    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss first5={first:.4f} last5={last:.4f} "
          f"improved={last < first}")
    if args.ckpt:
        CK.save(args.ckpt, params, step=args.steps)
        print(f"checkpoint -> {args.ckpt}")
    return 0 if last < first else 1


if __name__ == "__main__":
    raise SystemExit(main())
