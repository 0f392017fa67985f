"""Pure-jnp oracles for every Pallas kernel.

Deliberately naive implementations (materialized logits, sequential
recurrences) — slow but obviously correct; the kernels are asserted
allclose against these across shape/dtype sweeps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_prefill_ref(q, k, v, *, causal: bool = True, window=None,
                      q_offset: int = 0):
    """q [B, Hq, Sq, D], k/v [B, Hkv, Skv, D] -> [B, Hq, Sq, D].

    q_offset: absolute position of q[0] (chunked prefill against a longer
    KV prefix).
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens):
    """Decode attention over paged KV.

    q [B, Hq, D]; k_pages/v_pages [P, page, Hkv*D] (or the
    [P, page, Hkv, D] view); block_tables [B, pages_per_seq] int32;
    seq_lens [B] int32.
    """
    B, Hq, D = q.shape
    page = k_pages.shape[1]
    Hkv = k_pages[0, 0].size // D
    G = Hq // Hkv
    pps = block_tables.shape[1]
    # gather each sequence's pages into a dense [B, S_max, Hkv, D]
    k = k_pages[block_tables].reshape(B, pps * page, Hkv, D)
    v = v_pages[block_tables].reshape(B, pps * page, Hkv, D)
    pos = jnp.arange(pps * page)
    valid = pos[None, :] < seq_lens[:, None]
    qg = q.reshape(B, Hkv, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_tables,
                                q_start, q_lens):
    """Fused multi-token-query attention over paged KV (DESIGN.md §11).

    q [B, Q, Hq, D]; k_pages/v_pages [P, page, Hkv*D] (or the
    [P, page, Hkv, D] view); block_tables [B, pages_per_seq] int32;
    q_start/q_lens [B] int32.
    Query token t of row b attends causally over global positions
    <= q_start[b] + t; tokens t >= q_lens[b] are padding (output
    unspecified — callers discard them; here they are zeroed so the
    oracle is deterministic).
    """
    B, Q, Hq, D = q.shape
    page = k_pages.shape[1]
    Hkv = k_pages[0, 0].size // D
    G = Hq // Hkv
    pps = block_tables.shape[1]
    k = k_pages[block_tables].reshape(B, pps * page, Hkv, D)
    v = v_pages[block_tables].reshape(B, pps * page, Hkv, D)
    pos = jnp.arange(pps * page)
    t = jnp.arange(Q)
    limit = q_start[:, None] + t[None, :]              # [B, Q]
    valid = pos[None, None, :] <= limit[:, :, None]    # [B, Q, S]
    valid &= (t[None, :] < q_lens[:, None])[:, :, None]
    qg = q.reshape(B, Q, Hkv, G, D)
    logits = jnp.einsum("bqhgd,bshd->bhgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p, v.astype(jnp.float32))
    out = jnp.where(valid.any(-1)[..., None, None, None], out, 0.0)
    return out.reshape(B, Q, Hq, D).astype(q.dtype)


def ssd_scan_ref(X, dA, B_mat, C_mat, initial_state=None):
    """Sequential (token-by-token) SSD recurrence — the ground truth.

    X [B, L, H, P] (dt-scaled inputs), dA [B, L, H] log-decay,
    B_mat/C_mat [B, L, H, N]. Returns (Y [B, L, H, P], state [B, H, P, N]).
    """
    b, l, h, p = X.shape
    n = B_mat.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(state, inp):
        x_t, da_t, b_t, c_t = inp
        state = state * jnp.exp(da_t)[..., None, None] \
            + x_t[..., :, None] * b_t[..., None, :]
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t)
        return state, y_t

    xs = (X.transpose(1, 0, 2, 3).astype(jnp.float32),
          dA.transpose(1, 0, 2).astype(jnp.float32),
          B_mat.transpose(1, 0, 2, 3).astype(jnp.float32),
          C_mat.transpose(1, 0, 2, 3).astype(jnp.float32))
    state, ys = jax.lax.scan(step, initial_state, xs)
    return ys.transpose(1, 0, 2, 3).astype(X.dtype), state
