"""Training launcher.

Single-host CPU runs use real (reduced) configs; on a TPU pod slice the
same entrypoint initializes jax.distributed and uses the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
      --steps 50 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="'DxM' data x model mesh (default: single device)")
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed (TPU pod slice)")
    args = ap.parse_args()

    import jax
    if args.distributed:
        jax.distributed.initialize()

    from repro.configs import get_config, reduced
    from repro.distributed.sharding import ShardingRules
    from repro.models import init_params
    from repro.training import optimizer as opt_mod
    from repro.training.checkpoint import latest_step, restore_checkpoint
    from repro.training.data import synthetic_batches
    from repro.training.train_loop import TrainConfig, train_loop

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = opt_mod.select_optimizer(cfg)
    state = opt_mod.opt_init(opt, params)
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir):
        shardings = None
        if mesh is not None:
            rules = ShardingRules(cfg, mesh)
            shardings = {"params": rules.params(jax.eval_shape(lambda: params)),
                         "opt_state": rules.opt_state(
                             jax.eval_shape(lambda: state))}
        tree, start = restore_checkpoint(args.ckpt_dir,
                                         shardings=shardings)
        params, state = tree["params"], tree["opt_state"]
        print(f"resumed from step {start}")

    data = synthetic_batches(cfg.vocab_size, args.batch, args.seq)
    params, state, hist = train_loop(
        cfg, params, state, data, steps=args.steps, opt=opt,
        tc=TrainConfig(microbatches=args.microbatches, remat=False),
        mesh=mesh, checkpoint_every=20 if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir)
    for step, loss in hist[-5:]:
        print(f"step {step:5d} loss {loss:.4f}")


if __name__ == "__main__":
    main()
