"""Distributed training step builder + loop.

``build_train_step`` produces the pjit-able function the dry-run lowers:
loss -> grads (grad-accum microbatching, remat) -> optimizer update.
The same builder powers the runnable example (tiny config, 1 CPU device)
and the 512-chip dry-run — only the mesh and shardings differ.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import auto_axes
from repro.models import model as M
from repro.training import optimizer as opt_mod


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1            # gradient accumulation steps
    remat: bool = True
    seq_shard_activations: bool = False  # Megatron-SP residual constraint
    bf16_grad_reduce: bool = False   # barrier grads in bf16 so XLA cannot
    # hoist the optimizer's f32 cast ahead of the DP all-reduce (halves
    # gradient-reduction wire bytes; error bounded by bf16 rounding of an
    # already-bf16-computed gradient)


def _microbatch_stack(batch, n, mesh):
    """[B, ...] -> [n, B/n, ...] so the grad-accum scan slices STATICALLY,
    with the batch shard kept on the SECOND dim (without the constraint
    SPMD moves the 'data' shard onto the microbatch dim and every device
    recomputes the full microbatch — 16x replicated compute)."""
    def f(x):
        y = x.reshape((n, x.shape[0] // n) + x.shape[1:])
        if mesh is not None:
            da = tuple(a for a in mesh.axis_names if a != "model")
            spec = P(None, da, *([None] * (y.ndim - 2)))
            y = jax.lax.with_sharding_constraint(
                y, jax.NamedSharding(mesh, spec))
        return y
    return jax.tree.map(f, batch)


def build_train_step(cfg, opt: opt_mod.OptConfig, tc: TrainConfig,
                     mesh=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)."""
    if mesh is not None:
        mesh = auto_axes(mesh)
    seq_spec = None
    if tc.seq_shard_activations and mesh is not None:
        da = tuple(a for a in mesh.axis_names if a != "model")
        seq_spec = jax.NamedSharding(mesh, P(da, "model", None))

    def loss_of(params, mb):
        loss, metrics = M.loss_fn(cfg, params, mb, mesh=mesh,
                                  remat=tc.remat, seq_spec=seq_spec)
        return loss, metrics

    def train_step(params, opt_state, batch):
        if tc.microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        else:
            def accum(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + l), None
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = M._scan(
                accum, (zero, jnp.zeros(())),
                _microbatch_stack(batch, tc.microbatches, mesh))
            grads = jax.tree.map(lambda g: g / tc.microbatches, gsum)
            loss = lsum / tc.microbatches
            metrics = {}
        if tc.bf16_grad_reduce:
            grads = jax.lax.optimization_barrier(
                jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads))
        new_params, new_opt, om = opt_mod.opt_update(
            opt, grads, opt_state, params)
        out = {"loss": loss, **om}
        return new_params, new_opt, out

    return train_step


def train_loop(cfg, params, opt_state, data_iter, *, steps: int,
               opt: opt_mod.OptConfig, tc: Optional[TrainConfig] = None,
               mesh=None, checkpoint_every: int = 0, ckpt_dir=None,
               log_every: int = 10):
    """Simple driver used by examples; checkpointing is async-friendly."""
    from repro.training.checkpoint import save_checkpoint
    tc = tc or TrainConfig(remat=False)
    step_fn = jax.jit(build_train_step(cfg, opt, tc, mesh=mesh))
    history = []
    for step in range(steps):
        batch = next(data_iter)
        params, opt_state, m = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == steps - 1:
            history.append((step, float(m["loss"])))
        if checkpoint_every and ckpt_dir and \
                (step + 1) % checkpoint_every == 0:
            save_checkpoint(ckpt_dir, step + 1, params, opt_state)
    return params, opt_state, history
