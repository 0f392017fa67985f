"""Mixture-of-Experts layer.

Training runs one of two capacity paths with identical numerics:

- ``moe_local``: single-device / pjit-friendly. Dispatch is gather-based
  (argsort routing -> [E, C] token-index table), so HLO FLOPs are
  proportional to top_k (no one-hot einsum blow-up).
- ``moe_ep``: explicit expert parallelism under ``shard_map``. Tokens are
  routed locally per (data, model) shard, exchanged with two
  ``all_to_all`` collectives over the expert ('model') axis (DeepSeek-style
  EP), and the output restored with one ``all_gather``.

Capacity-factor dropping matches the published dropping implementations
(tokens beyond an expert's capacity fall back to the residual path).

Serving runs ``moe_held``, the dropless held-expert layer (DESIGN.md
§17): no capacity, every valid token routed over all of the router's
experts, and only the experts this chip holds (``MoEConfig.held_*``)
computed, in the ``moe_experts`` kernel.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import moe_experts as kernel
from repro.models.layers import dense_init, mlp_apply, mlp_init

# Expert tensor-parallelism over the 'data' axis (decode_2d layouts):
# valid only when activations are replicated across 'data' (big-model
# decode), where the d_ff-sharded expert GEMM + psum replaces the
# prohibitive per-step gather of d-sharded expert weights.
_EXPERT_TP = False


def set_expert_tp(v: bool) -> None:
    global _EXPERT_TP
    _EXPERT_TP = v


# ----------------------------------------------------------------- init
def moe_init(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    ks = jax.random.split(key, 5)
    ek = jax.random.split(ks[0], m.held)
    p = {
        # training keeps the router in f32; a chip's served share holds
        # it in the served dtype (``moe_held`` accumulates in f32)
        "router": dense_init(ks[1], (d, m.num_experts), d,
                             dtype if m.held_count else jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, (d, f), d, dtype))(ek),
        "w_up": jax.vmap(
            lambda k: dense_init(k, (d, f), d, dtype))(
                jax.random.split(ks[2], m.held)),
        "w_down": jax.vmap(
            lambda k: dense_init(k, (f, d), f, dtype))(
                jax.random.split(ks[3], m.held)),
    }
    if m.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * m.num_shared_experts,
                               "swiglu", dtype)
    return p


# ----------------------------------------------------------------- routing
def route(router_w, x2d, top_k: int, *, normalize: bool = True):
    """x2d [T, d] -> (weights [T,k] f32, sel [T,k] i32, aux_loss scalar)."""
    logits = x2d.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(probs, top_k)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    E = router_w.shape[-1]
    f_e = jnp.mean(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=(0, 1))
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e)
    return weights, sel, aux


def _capacity(tokens: int, top_k: int, num_experts: int, cf: float) -> int:
    c = int(math.ceil(tokens * top_k / num_experts * cf))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def build_dispatch(sel, weights, num_experts: int, capacity: int):
    """argsort-based dispatch tables.

    Returns (tok_idx [E, C] int32 — index into the padded token array where
    row ``T`` is the zero pad; w [E, C] f32 combine weights, 0 on empties).
    """
    T, k = sel.shape
    flat_e = sel.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)            # slots sorted by expert
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = rank < capacity
    dest = jnp.where(keep, sorted_e * capacity + rank, num_experts * capacity)
    tok_of_slot = (order // k).astype(jnp.int32)
    w_of_slot = weights.reshape(-1)[order]
    tok_idx = jnp.full((num_experts * capacity + 1,), T, jnp.int32)
    tok_idx = tok_idx.at[dest].set(jnp.where(keep, tok_of_slot, T))
    w_tab = jnp.zeros((num_experts * capacity + 1,), jnp.float32)
    w_tab = w_tab.at[dest].set(jnp.where(keep, w_of_slot, 0.0))
    return (tok_idx[:-1].reshape(num_experts, capacity),
            w_tab[:-1].reshape(num_experts, capacity))


def expert_ffn(params, xe):
    """xe [E, C, d] with per-expert stacked weights."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


# ----------------------------------------------------------------- local path
def moe_local(params, x, cfg):
    """x [B, S, d] -> (y, aux_loss). Single-shard reference path."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    weights, sel, aux = route(params["router"], x2, m.top_k)
    C = _capacity(T, m.top_k, m.num_experts, m.capacity_factor)
    tok_idx, w_tab = build_dispatch(sel, weights, m.num_experts, C)
    x_pad = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)], axis=0)
    xe = x_pad[tok_idx]                                  # [E, C, d] gather
    ye = expert_ffn(params, xe)
    y = jnp.zeros((T + 1, d), x2.dtype)
    y = y.at[tok_idx.reshape(-1)].add(
        (ye * w_tab[..., None].astype(ye.dtype)).reshape(-1, d))
    y = y[:T].reshape(B, S, d)
    if m.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, "swiglu")
    return y, aux


# ----------------------------------------------------------------- EP path
def moe_ep(params, x, cfg, mesh, *, data_axes=("data",), model_axis="model"):
    """Explicit expert-parallel MoE under shard_map.

    x: [B, S, d] sharded batch->data_axes, d replicated over model_axis.
    Expert weights sharded over model_axis on the expert dim.
    """
    from repro.launch.mesh import auto_axes
    mesh = auto_axes(mesh)
    m = cfg.moe
    B, S, d = x.shape
    M = mesh.shape[model_axis]
    DPS = tuple(data_axes)

    shared = params.get("shared")
    core = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}

    expert_tp = _EXPERT_TP
    if expert_tp:
        # weights: experts over model, d_ff over data; tokens replicated
        in_specs = (
            {"router": P(), "w_gate": P(model_axis, None, "data"),
             "w_up": P(model_axis, None, "data"),
             "w_down": P(model_axis, "data", None)},
            P(None, None, None),
        )
    else:
        in_specs = (
            {"router": P(), "w_gate": P(model_axis), "w_up": P(model_axis),
             "w_down": P(model_axis)},
            P(DPS, None, None),
        )

    def body(pl, x_loc):
        b_loc, s, _ = x_loc.shape
        t = b_loc * s
        tm = -(-t // M)                                  # ceil
        x2 = x_loc.reshape(t, d)
        if tm * M > t:
            x2 = jnp.concatenate(
                [x2, jnp.zeros((tm * M - t, d), x2.dtype)], axis=0)
        m_idx = jax.lax.axis_index(model_axis)
        x_slice = jax.lax.dynamic_slice_in_dim(x2, m_idx * tm, tm)
        weights, sel, aux = route(pl["router"], x_slice, m.top_k)
        C = _capacity(tm, m.top_k, m.num_experts, m.capacity_factor)
        tok_idx, w_tab = build_dispatch(sel, weights, m.num_experts, C)
        x_pad = jnp.concatenate([x_slice, jnp.zeros((1, d), x2.dtype)], 0)
        xe = x_pad[tok_idx]                              # [E, C, d]
        # dispatch: expert dim scattered across the model axis
        xe = jax.lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=1,
                                tiled=True)              # [E/M, C*M, d]
        ye = expert_ffn(pl, xe)
        if expert_tp:
            # d_ff was sharded over 'data': finish the contraction
            ye = jax.lax.psum(ye, "data")
        ye = jax.lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0,
                                tiled=True)              # [E, C, d]
        y = jnp.zeros((tm + 1, d), x2.dtype)
        y = y.at[tok_idx.reshape(-1)].add(
            (ye * w_tab[..., None].astype(ye.dtype)).reshape(-1, d))
        y = jax.lax.all_gather(y[:tm], model_axis, axis=0, tiled=True)
        aux = jax.lax.pmean(aux, model_axis)
        for ax in DPS:
            aux = jax.lax.pmean(aux, ax)
        return y[:t].reshape(b_loc, s, d), aux

    out_spec = P(None, None, None) if expert_tp else P(DPS, None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(out_spec, P()), check_vma=False)(core, x)
    if m.num_shared_experts:
        y = y + mlp_apply(params["shared"] if shared is None else shared,
                          x, "swiglu")
    return y, aux


def moe_apply(params, x, cfg, mesh=None, *, data_axes=("data",),
              model_axis="model"):
    if cfg.moe.held != cfg.moe.num_experts:
        raise ValueError(
            f"{cfg.name} holds {cfg.moe.held} of {cfg.moe.num_experts} "
            "experts: a chip's share is served by moe_held, not trained")
    if mesh is None:
        return moe_local(params, x, cfg)
    return moe_ep(params, x, cfg, mesh, data_axes=data_axes,
                  model_axis=model_axis)


# ----------------------------------------------------------------- served
def moe_held(params, x, cfg, valid, *, interpret: bool = False):
    """The dropless held-expert layer (HF ``Qwen3MoeSparseMoeBlock`` on
    this chip's experts): x [B, S, d] (normed), valid [B, S] bool ->
    (y [B, S, d], counts [3] i32).

    Every valid token is routed over all ``num_experts`` (softmax in
    f32, top-k, renormalised to sum to 1); y is the sum of
    its routing weight times expert FFN over the chosen experts that
    this chip holds. Invalid tokens route nothing and get y = 0. No
    capacity: nothing is dropped, and a token's y depends only on
    itself. ``counts`` are the routed pairs, those on held experts, and
    the held experts touched."""
    m = cfg.moe
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    logits = jnp.dot(x2, params["router"], preferred_element_type=jnp.float32)
    weights, sel = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    p = kernel.plan(sel, weights, valid.reshape(-1), offset=m.held_offset,
                    held=m.held)
    y = kernel.moe_experts(x2, p, params["w_gate"], params["w_up"],
                           params["w_down"], interpret=interpret)
    y = y.reshape(B, S, d).astype(x.dtype)
    if m.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, "swiglu")
    return y, p.counts
