"""Training entry point.

CPU-scale run (reduced config, real execution):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 30 --batch 8 --seq 64 --ckpt-dir /tmp/repro_ckpt --resume auto

Production (TPU pod): the same driver with --mesh 16x16 / 2x16x16 — the step
function, shardings and checkpoint layout are identical; only the mesh and
the per-host data shards change.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.data.pipeline import TokenPipeline
from repro.models.model import init_params
from repro.optim import make_optimizer, warmup_cosine
from repro.runtime import Supervisor
from .mesh import make_mesh
from .steps import TrainState, make_train_step
from . import shardings as shd


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--resume", default="fresh", choices=["fresh", "auto"])
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 16x16 on a pod")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model"))
    if int(np.prod(dshape)) > 1:
        cfg = dataclasses.replace(cfg, batch_axes=("data",))

    optimizer = make_optimizer(
        args.optimizer, warmup_cosine(args.lr, max(args.steps // 10, 1),
                                      args.steps))
    pipe = TokenPipeline(vocab=cfg.vocab, batch=args.batch, seq=args.seq,
                         seed=args.seed)

    with jax.set_mesh(mesh):
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        params = jax.device_put(params,
                                shd.param_shardings(params, mesh))
        state = TrainState(params, optimizer.init(params))
        step_fn = jax.jit(make_train_step(cfg, optimizer),
                          donate_argnums=(0,))

        sup = Supervisor(args.ckpt_dir, save_every=args.save_every,
                         heartbeat_path=args.ckpt_dir + "/heartbeat.json")
        start = 0
        if args.resume == "auto":
            restored, start = sup.restore(state)
            if restored is not None:
                state = restored
                print(f"[train] resumed from step {start}")

        t_last = time.perf_counter()
        for step in range(start, args.steps):
            batch = {"tokens": jnp.asarray(pipe.batch_at(step)["tokens"])}
            if cfg.frontend == "vision":
                batch["vision_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(step), (args.batch,
                                               cfg.vision_tokens,
                                               cfg.vision_dim),
                    jnp.bfloat16)
            state, metrics = step_fn(state, batch)
            sup.monitor.observe(step, time.perf_counter() - t_last)
            t_last = time.perf_counter()
            sup.heartbeat(step, {k: float(v) for k, v in metrics.items()})
            if step % args.log_every == 0:
                print(f"[train] step {step} loss={float(metrics['loss']):.4f}"
                      f" ce={float(metrics['ce']):.4f}"
                      f" gnorm={float(metrics['grad_norm']):.3f}")
            sup.maybe_save(step + 1, state)
        sup.finalize(args.steps, state)
        print(f"[train] done; final loss {float(metrics['loss']):.4f}; "
              f"checkpoints in {args.ckpt_dir}")
        return float(metrics["loss"])


if __name__ == "__main__":
    main()
