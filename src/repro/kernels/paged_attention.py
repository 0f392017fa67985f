"""Paged attention — one Pallas TPU kernel for decode and fused rounds.

Queries attend over a paged KV cache addressed through per-sequence
block tables. This is the TPU-native re-think of vLLM-style CUDA paged
attention (DESIGN.md §3): instead of warp-level gather, each grid step
DMAs one KV tile HBM→VMEM, selected by a scalar-prefetched block table
(``PrefetchScalarGridSpec``), and folds it into an online-softmax
accumulator.

Page layout: the store is ``[P, page, Hkv*D]`` — each page is a
``[page, Hkv*D]`` row block with KV head ``h`` in lanes
``[h*D, (h+1)*D)``. A grid step reads the ``(kv_block, D)`` tile of one
head, which Mosaic accepts when ``D % 128 == 0`` (or ``Hkv == 1``): the
tile's minor dims are then lane-aligned. (A ``[P, page, Hkv, D]`` store
tiled ``(kv_block, 1, D)`` is refused — a one-head slice of the
second-minor dim is neither 8-divisible nor the whole dim.) Callers may
pass the 4-D ``[P, page, Hkv, D]`` view; it is reshaped to the merged
store, which is free for a row-major array but a relayout copy on the
chip, so the engine keeps its store merged.

``paged_prefill_attention`` is the one kernel (DESIGN.md §11): each
batch row carries a *chunk* of Q consecutive query tokens (a prefill
chunk, a speculative draft window, or Q=1 for a decode slot) whose KV
was scattered into the pages before the call, with per-row
``q_start``/``q_lens`` scalars. Query token t of a row attends to global
positions ``<= q_start + t`` and each of the Q*G query rows keeps its
own online-softmax accumulator, so one launch serves a whole mixed
prefill+decode token budget (and the spec-decode verify step, DESIGN.md
§16). ``paged_attention`` is its Q=1 case.

Grid: (B, Hkv, pages_per_seq * page/kv_block) — KV tiles innermost,
accumulators in VMEM.

Both entry points also run as one *shard* of a tensor-sharded page
store (DESIGN.md §9): when the 'model' mesh axis splits each page's
token slots, a shard holds ``page_local = page / M`` slots of every
physical page, and ``pos_stride``/``pos_offset`` map local slot ``j`` of
grid page ``p`` back to its global position ``p * pos_stride +
pos_offset + j`` so the causal/length mask stays exact. ``return_stats``
additionally emits the online-softmax running max ``m`` and denominator
``l`` per query row so the caller can combine partial softmaxes across
shards (the standard flash-merge: weight each shard's normalized output
by ``l_s * exp(m_s - max_s m_s)``).

Tiling knobs (DESIGN.md §16): ``kv_block`` splits each page into
``page / kv_block`` grid steps (arithmetic-identical at any legal value,
because the online softmax folds tiles in the same position order);
``head_block`` caps the KV heads per launch, splitting the head axis
across multiple ``pallas_call``s whose outputs concatenate (exact, by
per-head softmax independence). Both default to a static heuristic and
are overridden per (shape, backend) by the autotune cache when
``repro.kernels.autotune.enable()`` has loaded one — callers that pass
explicit values bypass the cache entirely.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _default_kv_block(page: int) -> int:
    """Static tile heuristic: whole-page tiles up to 16 slots (a whole
    page is always a legal block); larger 16-divisible pages default to
    16-slot sub-tiles — the bf16 sublane tile — and the autotune sweep
    overrides per shape."""
    return page if (page <= 16 or page % 16 != 0) else 16


def _resolve(kind: str, kv_block, head_block, *, page: int, Hkv: int,
             dims: dict) -> tuple:
    """Fill unset tiling knobs from the autotune cache (a no-op unless
    ``autotune.enable()`` loaded one), else the static defaults."""
    if kv_block is None or head_block is None:
        from repro.kernels import autotune
        tuned = autotune.lookup(kind, autotune.shape_key(**dims))
        if tuned is not None:
            if kv_block is None:
                kv_block = tuned.get("kv_block")
            if head_block is None:
                head_block = tuned.get("head_block")
    if kv_block is None:
        kv_block = _default_kv_block(page)
    if head_block is None:
        head_block = Hkv
    assert page % kv_block == 0, (page, kv_block)
    assert Hkv % head_block == 0, (Hkv, head_block)
    return kv_block, head_block


def _check_tiles(kv_pages, kv_block: int, Hkv: int, D: int) -> None:
    """Refuse, with the reason, a tiling Mosaic would refuse: the
    ``(kv_block, D)`` tile needs lane-aligned heads and whole packed
    sublane tiles (8 rows of 32-bit, 16 of 16-bit) unless it spans the
    whole page."""
    page = kv_pages.shape[1]
    rows = 8 * 4 // kv_pages.dtype.itemsize
    if Hkv > 1 and D % 128:
        raise ValueError(
            f"head_dim {D} is not a multiple of 128: a one-head tile of "
            f"the [P, page, Hkv*D] store is not lane-aligned on the chip")
    if kv_block != page and kv_block % rows:
        raise ValueError(
            f"kv_block {kv_block} is not a multiple of the {rows}-row "
            f"{kv_pages.dtype} tile (page {page})")


def _merged(kv_pages, D: int):
    """[P, page, Hkv, D] view -> the [P, page, Hkv*D] store."""
    if kv_pages.ndim == 4:
        return kv_pages.reshape(*kv_pages.shape[:2], -1)
    return kv_pages


def _kernel(block_tables, q_start, q_lens, q_ref, k_ref, v_ref, *refs,
            kv_block: int, bpp: int, total_steps: int, scale: float,
            pos_stride: int, pos_offset: int, stats: bool, Q: int):
    if stats:
        o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = q_start[b]
    nq = q_lens[b]
    seq_len = start + nq                 # post-write attention length
    base = (p // bpp) * pos_stride + (p % bpp) * kv_block + pos_offset

    @pl.when(base < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [G*Q, D]
        k = k_ref[0].astype(jnp.float32)                 # [kv_block, D]
        v = v_ref[0].astype(jnp.float32)                 # [kv_block, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        kv_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # query rows are (g, t) pairs, t minor: row r is token r % Q
        t_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % Q
        mask = (kv_pos <= start + t_idx) & (t_idx < nq)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # [G*Q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + pexp @ v
        m_ref[...] = m_new

    @pl.when(p == total_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if stats:
            m_out_ref[0, 0] = m_ref[...]
            l_out_ref[0, 0] = l_ref[...]


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    pos_stride: int | None = None, pos_offset: int = 0,
                    return_stats: bool = False, interpret: bool = False,
                    kv_block: int | None = None,
                    head_block: int | None = None):
    """Decode: q [B, Hq, D]; k_pages/v_pages [P, page, Hkv*D] (or the
    [P, page, Hkv, D] view); block_tables [B, pages_per_seq] i32;
    seq_lens [B] i32 (post-write lengths) -> [B, Hq, D].

    The Q=1 case of ``paged_prefill_attention``: the query sits at
    position ``seq_lens - 1`` and attends over ``[0, seq_lens)``. With
    ``return_stats`` the result is ``(out, m, l)`` where ``m``/``l``
    [B, Hq] f32 are the per-row online-softmax running max and
    denominator (``m = NEG_INF``, ``l = 0`` for rows/shards with no
    valid position). Tiling knobs as in ``paged_prefill_attention``.
    """
    B, Hq, D = q.shape
    k_pages, v_pages = _merged(k_pages, D), _merged(v_pages, D)
    Hkv = k_pages.shape[2] // D
    kv_block, head_block = _resolve(
        "paged_attention", kv_block, head_block, page=k_pages.shape[1],
        Hkv=Hkv, dims=dict(B=B, Hq=Hq, Hkv=Hkv, D=D, page=k_pages.shape[1],
                           pps=block_tables.shape[1]))
    out = paged_prefill_attention(
        q[:, None], k_pages, v_pages, block_tables, seq_lens - 1,
        jnp.ones_like(seq_lens), pos_stride=pos_stride,
        pos_offset=pos_offset, return_stats=return_stats,
        interpret=interpret, kv_block=kv_block, head_block=head_block)
    if return_stats:
        return tuple(x[:, 0] for x in out)
    return out[:, 0]


def paged_prefill_attention(q, k_pages, v_pages, block_tables, q_start,
                            q_lens, *, pos_stride: int | None = None,
                            pos_offset: int = 0,
                            return_stats: bool = False,
                            interpret: bool = False,
                            kv_block: int | None = None,
                            head_block: int | None = None):
    """q [B, Q, Hq, D]; k_pages/v_pages [P, page, Hkv*D] (or the
    [P, page, Hkv, D] view); block_tables [B, pages_per_seq] i32;
    q_start/q_lens [B] i32 -> [B, Q, Hq, D].

    Row b's query token t sits at global position ``q_start[b] + t`` and
    attends causally over positions ``<= q_start[b] + t`` — the
    committed history plus the chunk prefix, whose KV the caller already
    scattered into the pages. Tokens ``t >= q_lens[b]`` are padding:
    fully masked, finite-garbage output, to be discarded (a row with
    ``q_lens == 0`` computes nothing and returns zeros). ``pos_stride``/
    ``pos_offset`` remap local page slots to global positions (module
    docstring); a slot-sharded caller shifts the *traced* ``q_start`` by
    its slot offset instead of passing a traced ``pos_offset``. With
    ``return_stats`` the result is ``(out, m, l)`` with m/l [B, Q, Hq]
    f32 per query row, enabling the exact cross-shard softmax merge
    (fully-masked rows report ``m = NEG_INF`` — a finite, hugely
    negative sentinel — so merge weights vanish without NaNs).

    ``kv_block`` (divides ``page``) sizes the per-grid-step KV tile;
    ``head_block`` (divides ``Hkv``) splits the launch over the KV-head
    axis. Unset knobs come from the autotune cache when enabled, else
    static defaults. Any legal values are output-identical. Compiled
    for the chip (``interpret=False``) the tiling must be one Mosaic
    accepts (``_check_tiles``).
    """
    B, Q, Hq, D = q.shape
    k_pages, v_pages = _merged(k_pages, D), _merged(v_pages, D)
    num_pages, page, HD = k_pages.shape
    Hkv = HD // D
    G = Hq // Hkv
    pages_per_seq = block_tables.shape[1]
    if pos_stride is None:
        pos_stride = page
    kv_block, head_block = _resolve(
        "paged_prefill_attention", kv_block, head_block, page=page,
        Hkv=Hkv, dims=dict(B=B, Q=Q, Hq=Hq, Hkv=Hkv, D=D, page=page,
                           pps=pages_per_seq))
    if head_block < Hkv:
        # split the KV-head axis into independent launches; exact
        # because each head's softmax never mixes with another's
        parts = [paged_prefill_attention(
            q[:, :, h0 * G:(h0 + head_block) * G],
            k_pages[..., h0 * D:(h0 + head_block) * D],
            v_pages[..., h0 * D:(h0 + head_block) * D],
            block_tables, q_start, q_lens, pos_stride=pos_stride,
            pos_offset=pos_offset, return_stats=return_stats,
            interpret=interpret, kv_block=kv_block,
            head_block=head_block)
            for h0 in range(0, Hkv, head_block)]
        if return_stats:
            return tuple(jnp.concatenate([p[i] for p in parts], axis=2)
                         for i in range(3))
        return jnp.concatenate(parts, axis=2)
    if not interpret:
        _check_tiles(k_pages, kv_block, Hkv, D)
    bpp = page // kv_block
    total_steps = pages_per_seq * bpp
    grid = (B, Hkv, total_steps)
    kernel = functools.partial(
        _kernel, kv_block=kv_block, bpp=bpp, total_steps=total_steps,
        scale=1.0 / math.sqrt(D), pos_stride=pos_stride,
        pos_offset=pos_offset, stats=return_stats, Q=Q)
    # [B, Q, (Hkv, G), D] -> [B, Hkv, G*Q, D]: rows are (g, t), t minor,
    # so the kernel recovers the token index as row % Q
    N = G * Q
    qg = jnp.moveaxis(q.reshape(B, Q, Hkv, G, D), 1, 3) \
        .reshape(B, Hkv, N, D)
    row_map = lambda b, h, p, bt, qs, ql: (b, h, 0, 0)   # noqa: E731
    out_specs = pl.BlockSpec((1, 1, N, D), row_map)
    out_shape = jax.ShapeDtypeStruct((B, Hkv, N, D), q.dtype)
    if return_stats:
        # stats keep a unit lane dim: a (N, 1) block is the whole of the
        # array's minor two dims, which Mosaic accepts
        stat_spec = pl.BlockSpec((1, 1, N, 1), row_map)
        stat_shape = jax.ShapeDtypeStruct((B, Hkv, N, 1), jnp.float32)
        out_specs = [out_specs, stat_spec, stat_spec]
        out_shape = [out_shape, stat_shape, stat_shape]
    kv_spec = pl.BlockSpec((1, kv_block, D),
                           lambda b, h, p, bt, qs, ql:
                           (bt[b, p // bpp], p % bpp, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, N, D), row_map), kv_spec, kv_spec],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((N, D), jnp.float32),
            pltpu.VMEM((N, 1), jnp.float32),
            pltpu.VMEM((N, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="paged_attention",
    )(block_tables, q_start, q_lens, qg, k_pages, v_pages)

    def unpack(x):                        # [B, Hkv, G*Q, ...] -> token-major
        tail = x.shape[3:]
        return jnp.moveaxis(x.reshape(B, Hkv, G, Q, *tail), 3, 1) \
            .reshape(B, Q, Hq, *tail)

    if return_stats:
        o, m, l = out
        return unpack(o), unpack(m[..., 0]), unpack(l[..., 0])
    return unpack(out)
