"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 50 --batch 8 --seq 256

On the CPU container this runs reduced configs end-to-end (real training);
on a TPU cluster the same entrypoint drives the full configs over the
production mesh with FSDP+TP shardings resolved from the same spec trees
the dry-run validates.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ShapeSpec, get_config
from repro.data.pipeline import pipeline_for
from repro.launch import specs as SP
from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                               parse_mesh_flag)
from repro.models import model as M
from repro.optim import adamw
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig
from repro.utils.compile_cache import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--reduced", action="store_true",
                    help="same-family miniature config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="explicit DATAxMODEL host mesh, e.g. 2x4 "
                         "(overrides --production-mesh)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = SHAPES[args.shape]
    if args.batch or args.seq:
        shape = dataclasses.replace(
            shape,
            global_batch=args.batch or shape.global_batch,
            seq_len=args.seq or shape.seq_len)

    if args.mesh:
        mesh = parse_mesh_flag(args.mesh)
    else:
        mesh = (make_production_mesh(multi_pod=args.multi_pod)
                if args.production_mesh else make_host_mesh())
    print(f"arch={cfg.name} params~{cfg.n_params()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} batch={shape.global_batch} "
          f"seq={shape.seq_len}")

    # --- state
    params, specs_tree = M.init_model(jax.random.PRNGKey(args.seed), cfg,
                                      max_pos=max(shape.seq_len, 1024))
    p_sh = SP.resolve(specs_tree, params, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
    opt = adamw.init(params)
    opt_sh = {"m": p_sh, "v": p_sh, "count": NamedSharding(mesh, P())}

    compute_dtype = jnp.float32 if args.reduced else jnp.bfloat16
    step_fn = make_train_step(
        cfg, mesh, compute_dtype=compute_dtype, remat=not args.reduced,
        lr_schedule=adamw.cosine_schedule(args.lr, 10, args.steps))
    batch_sds, batch_sh = SP.train_batch_specs(cfg, shape, mesh)
    jitted = jax.jit(step_fn,
                     in_shardings=(p_sh, opt_sh, batch_sh, None),
                     out_shardings=(p_sh, opt_sh, None),
                     donate_argnums=(0, 1))

    pipe = pipeline_for(cfg, shape, seed=args.seed)

    def put(batch):
        return {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()}

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps,
                      checkpoint_every=args.ckpt_every,
                      checkpoint_dir=args.ckpt_dir),
        jitted, pipe, put)

    t0 = time.time()
    losses = []

    def log(step, metrics):
        losses.append(metrics["loss"])
        print(f"step {step:5d} loss {metrics['loss']:.4f} "
              f"gnorm {metrics['grad_norm']:.3f} "
              f"({(time.time()-t0)/max(step,1):.2f}s/step)")

    state, final = trainer.run(params, opt, metrics_cb=log)
    print(f"done at step {final}; stragglers={len(trainer.straggler_steps)} "
          f"retries={trainer.retries}")
    if len(losses) >= 2:
        print(f"loss first->last: {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
