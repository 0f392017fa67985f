"""Held experts' SiLU-gated FFN — one Pallas TPU kernel, ``moe_experts``.

The dropless expert layer (DESIGN.md §17) routes every valid token over
all of the router's experts and computes the part of the result that
the experts held on this chip give. ``plan`` lays the (token, expert)
pairs that land on held experts out sorted by expert, each expert's
rows padded to whole tiles of ``TM`` rows; ``moe_experts`` then runs
one grid step per tile:

- gather: the tile's rows of ``x`` by a one-hot matmul against the
  whole token block, resident in VMEM (exact: one 1.0 per row);
- gate and up projections, SiLU, down projection, with the tile's
  expert's weights, in f32 accumulation;
- combine: each row scaled by its routing weight, rounded to the served
  dtype, and added into its token's f32 output row by the transposed
  one-hot matmul (exact: one term per token per tile).

Live tiles come first and an expert's tiles are consecutive, so each
touched expert's weights are copied into VMEM once per call, and the
copy of the next expert's runs while a tile computes. Tiles past the
last live one repeat its block indices — no copy is issued — and skip
the compute, as ``paged_attention`` skips blocks past a row's length.
Pairs routed to experts held elsewhere and padded rows get no row.

A token's result depends only on its own routing: it appears at most
once per expert, its rows are computed row by row, and its output row
sums its experts' terms in expert order whatever else the launch holds.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 128                      # pair rows per tile
VMEM_LIMIT_MAX = 100 << 20    # of a v5e's 128 MiB


class Plan(NamedTuple):
    """Where each (token, held expert) pair computes. Tiles of ``TM``
    rows; ``n_tiles`` is static (every pair held, each expert's last
    tile partly filled)."""
    tok: jax.Array          # [n_tiles, TM] i32 token of each row (T: none)
    weight: jax.Array       # [n_tiles, TM] f32 routing weight (0: none)
    expert: jax.Array       # [n_tiles] i32 held expert whose weights load
    src: jax.Array          # [n_tiles] i32 tile whose rows load
    live: jax.Array         # [n_tiles] i32 1 where the tile has rows
    counts: jax.Array       # [3] i32 routed pairs, held pairs, experts hit


def plan(sel, weight, valid, *, offset: int, held: int,
         tm: int = TM) -> Plan:
    """Sort the valid tokens' top-k choices ``sel`` [T, k] (global
    expert ids, weights ``weight`` [T, k]) onto the ``held`` experts
    from ``offset``. ``valid`` [T] bool marks the tokens that route."""
    T, k = sel.shape
    local = sel.astype(jnp.int32) - offset
    on = valid[:, None] & (local >= 0) & (local < held)
    key = jnp.where(on, local, held).reshape(-1)             # [T*k]
    first = jnp.cumsum((key[:, None] == jnp.arange(held + 1)
                        ).astype(jnp.int32), axis=0)
    rank = jnp.take_along_axis(first, key[:, None], axis=1)[:, 0] - 1
    sizes = first[-1, :held]
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n = -(-T * min(k, held) // tm) + held
    dest = jnp.where(
        key < held,
        (tile_end - tiles)[jnp.minimum(key, held - 1)] * tm + rank, n * tm)
    tok = jnp.full((n * tm,), T, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    w = jnp.zeros((n * tm,), jnp.float32).at[dest].set(
        weight.reshape(-1).astype(jnp.float32), mode="drop")
    n_live = tile_end[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    src = jnp.minimum(idx, jnp.maximum(n_live - 1, 0))
    expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= src[:, None], axis=1), held - 1)
    counts = jnp.stack([jnp.sum(valid) * k, jnp.sum(on),
                        jnp.sum(sizes > 0)]).astype(jnp.int32)
    return Plan(tok.reshape(n, tm), w.reshape(n, tm),
                expert.astype(jnp.int32), src,
                (idx < n_live).astype(jnp.int32), counts)


def _kernel(expert_ref, src_ref, live_ref, tok_col, tok_row, w_col, x_ref,
            wg_ref, wu_ref, wd_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[i] > 0)
    def _tile():
        T = x_ref.shape[0]
        tm = tok_col.shape[0]
        dt = x_ref.dtype
        gather = (jax.lax.broadcasted_iota(jnp.int32, (tm, T), 1)
                  == tok_col[...]).astype(dt)
        xt = jnp.dot(gather, x_ref[...],
                     preferred_element_type=jnp.float32).astype(dt)
        g = jnp.dot(xt, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(xt, wu_ref[...], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(wd_ref.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)
        yw = (y * w_col[...]).astype(dt)
        scatter = (jax.lax.broadcasted_iota(jnp.int32, (T, tm), 0)
                   == tok_row[...]).astype(dt)
        out_ref[...] += jnp.dot(scatter, yw,
                                preferred_element_type=jnp.float32)


def _vmem_bytes(T, d, f, tm, itemsize, w_itemsize) -> int:
    """Both buffers of every block, and the tile's temporaries."""
    blocks = 2 * (T * d * itemsize + T * d * 4 + 3 * d * f * w_itemsize
                  + 3 * tm * 128 * 4)
    temps = 2 * tm * T * itemsize + tm * d * (itemsize + 4) \
        + 3 * tm * f * 4 + 2 * T * d * 4
    return blocks + temps


def moe_experts(x, p: Plan, w_gate, w_up, w_down, *,
                interpret: bool = False):
    """x [T, d] -> [T, d] f32: for each token, the sum over its pairs
    in ``p`` of weight x W_down(silu(W_gate x) * W_up x), with the held
    experts' stacks ``w_gate``/``w_up`` [E, d, f] and ``w_down``
    [E, f, d]."""
    T, d = x.shape
    n, tm = p.tok.shape
    f = w_gate.shape[-1]

    def tile(i, expert, src, live):
        return (src[i], 0, 0)

    def weights(i, expert, src, live):
        return (expert[i], 0, 0)

    def whole(i, expert, src, live):
        return (0, 0)

    limit = min(VMEM_LIMIT_MAX,
                max(32 << 20, 2 * _vmem_bytes(T, d, f, tm, x.dtype.itemsize,
                                              w_gate.dtype.itemsize)))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((None, tm, 1), tile),
                pl.BlockSpec((None, 1, tm), tile),
                pl.BlockSpec((None, tm, 1), tile),
                pl.BlockSpec((T, d), whole),
                pl.BlockSpec((None, d, f), weights),
                pl.BlockSpec((None, d, f), weights),
                pl.BlockSpec((None, f, d), weights),
            ],
            out_specs=pl.BlockSpec((T, d), whole),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=limit),
        interpret=interpret,
        name="moe_experts",
    )(p.expert, p.src, p.live, p.tok[:, :, None], p.tok[:, None, :],
      p.weight[:, :, None], x, w_gate, w_up, w_down)


def moe_experts_reference(x, p: Plan, w_gate, w_up, w_down):
    """The same result as a plain grouped matmul: every tile's rows
    gathered, run through its expert's FFN, weighted and summed into
    their tokens (for tests)."""
    T, d = x.shape
    dt = x.dtype
    xp = jnp.concatenate([x, jnp.zeros((1, d), dt)])
    live = p.live.astype(bool)
    xt = xp[p.tok]                                      # [n, tm, d]
    g = jnp.einsum("nmd,ndf->nmf", xt, w_gate[p.expert],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nmd,ndf->nmf", xt, w_up[p.expert],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(w_down.dtype)
    y = jnp.einsum("nmf,nfd->nmd", h, w_down[p.expert],
                   preferred_element_type=jnp.float32)
    yw = (y * p.weight[..., None]).astype(dt).astype(jnp.float32)
    yw = jnp.where(live[:, None, None], yw, 0.0)
    out = jnp.zeros((T + 1, d), jnp.float32).at[p.tok.reshape(-1)].add(
        yw.reshape(-1, d))
    return out[:T]
