"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-v2-236b \
        --smoke --steps 50 --batch 8 --seq 128

``--smoke`` selects the reduced config (CPU-runnable); omit it on real
hardware to train the full config (the mesh is then the production mesh).
Fault tolerance: ``--ckpt-dir`` enables auto-resume; kill and relaunch to
continue from the last complete checkpoint.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp

from repro import configs, models
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.nn import module as nnm
from repro.nn import sharding as shd
from repro.optim import AdamWConfig, adamw_init, cosine
from repro.runtime import LoopConfig, TrainLoop, TrainStepConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="none",
                    help="none | single | multi | RxC (e.g. 2x2)")
    ap.add_argument("--impl", default="ref", help="ref | chunked | kernel")
    ap.add_argument("--scheme", default="seq")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.smoke(args.arch) if args.smoke else configs.full(args.arch)
    if args.mesh == "none":
        mesh = None
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    else:
        r, c = map(int, args.mesh.split("x"))
        mesh = make_mesh((r, c), ("data", "model"))

    dtype = jnp.float32 if mesh is None else jnp.bfloat16
    params = nnm.init_params(jax.random.PRNGKey(args.seed),
                             models.model_defs(cfg), dtype)
    opt_cfg = AdamWConfig(lr=cosine(args.lr, warmup=20, total=args.steps))
    opt = adamw_init(params, opt_cfg)
    if mesh is not None:
        rules = shd.make_rules(mesh, cfg=cfg)
        shardings = shd.param_shardings(models.model_defs(cfg), mesh, rules)
        params = jax.tree.map(jax.device_put, params, shardings)
        opt = {"step": opt["step"],
               "mu": jax.tree.map(jax.device_put, opt["mu"], shardings),
               "nu": jax.tree.map(jax.device_put, opt["nu"], shardings)}

    step_fn, _ = make_train_step(
        cfg, mesh, opt_cfg,
        TrainStepConfig(microbatches=args.microbatches, compute_dtype=dtype,
                        impl=args.impl, scheme=args.scheme))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))

    def make_batch(toks, labels):
        b = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        if cfg.family in ("vlm", "encdec"):
            P = cfg.n_patches if cfg.family == "vlm" else cfg.n_frames
            key = jax.random.PRNGKey(int(toks[0, 0]))
            b["embeds"] = jax.random.normal(
                key, (toks.shape[0], P, cfg.d_model), dtype) * 0.02
        return b

    ckpt_dir = args.ckpt_dir or os.path.join("/tmp/repro_train", cfg.name)
    loop = TrainLoop(
        LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                   ckpt_dir=ckpt_dir, fail_at_step=args.fail_at),
        step_fn, params, opt, data, make_batch=make_batch)
    metrics = loop.run()
    print(f"[train] done at step {loop.step}: "
          f"loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
