"""Paged attention — one Pallas TPU kernel for decode and fused rounds.

Queries attend over a paged KV cache addressed through per-sequence
block tables. This is the TPU-native re-think of vLLM-style CUDA paged
attention (DESIGN.md §3): instead of warp-level gather, the kernel
copies whole pages HBM→VMEM with its own DMAs, chosen by the
scalar-prefetched block table, and folds each block of pages into an
online-softmax accumulator.

Page layout: the store is ``[P, page, Hkv*D]`` — each page is a
``[page, Hkv*D]`` row block with KV head ``h`` in lanes
``[h*D, (h+1)*D)``. One DMA moves one page for every head; the kernel
then reads head ``h`` as a lane slice, which Mosaic accepts when
``D % 128 == 0`` (or ``Hkv == 1``). Callers may pass the 4-D
``[P, page, Hkv, D]`` view; it is reshaped to the merged store, which
is free for a row-major array but a relayout copy on the chip, so the
engine keeps its store merged.

``paged_prefill_attention`` is the one kernel (DESIGN.md §11): each
batch row carries a *chunk* of Q consecutive query tokens (a prefill
chunk, a speculative draft window, or Q=1 for a decode slot) whose KV
was scattered into the pages before the call, with per-row
``q_start``/``q_lens`` scalars. Query token t of a row attends to global
positions ``<= q_start + t`` and each of the Q*G query rows keeps its
own online-softmax accumulator, so one launch serves a whole mixed
prefill+decode token budget (and the spec-decode verify step, DESIGN.md
§16). ``paged_attention`` is its Q=1 case.

Grid: ``(B,)``, one step per batch row. A *block* is ``kv_block / page``
consecutive block-table pages, all KV heads. Step ``b`` loops over the
row's live blocks only — those that start before ``q_start + q_lens``;
a row with ``q_lens == 0`` has none — so the work follows the live KV,
not the context cap. K and V blocks are double-buffered in VMEM: while
block ``i`` is computed the copies of block ``i+1`` run, and the last
block of a row starts the copies of the next live row's first block.
Per block, a static loop over the KV heads updates one accumulator
``[G*Q, D]`` (plus ``m``, ``l``) per head in VMEM. Scores, softmax and
accumulators are f32; K and V are the stored values, upcast in VMEM.

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

Tiling knobs (DESIGN.md §16): ``kv_block`` is the tokens of one block,
a whole number of pages (arithmetic-identical up to rounding at any
legal value, because the online softmax folds blocks in position
order); ``head_block`` caps the KV heads per launch, splitting the head
axis across multiple ``pallas_call``s whose outputs concatenate (exact,
by per-head softmax independence). ``kv_block`` defaults to the block
rule ``kv_block_tokens`` and ``head_block`` to all heads; the autotune
cache overrides both per (shape, backend) when
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

BLOCK_TOKENS = 128            # KV tokens a block aims at
BLOCK_VMEM_BYTES = 4 << 20    # K and V blocks, both buffers


def kv_block_tokens(page: int, HD: int, itemsize: int, pps: int) -> int:
    """The block rule: whole pages, about ``BLOCK_TOKENS`` tokens, with
    the double-buffered K and V blocks within ``BLOCK_VMEM_BYTES`` and
    no more pages than a block table has. Pages are stacked only when
    they are whole 8-row f32 tiles, so the stacked block is one
    ``[tokens, D]`` operand; an unaligned page is a block alone."""
    if page % 8:
        return page
    ppb = min(max(1, BLOCK_TOKENS // page),
              max(1, BLOCK_VMEM_BYTES // (4 * page * HD * itemsize)),
              pps)
    return ppb * page


def _resolve(kind: str, kv_block, head_block, *, itemsize: int,
             dims: dict) -> tuple:
    """Fill unset tiling knobs from the autotune cache (a no-op unless
    ``autotune.enable()`` loaded one), else the block rule and all
    heads. ``dims`` holds at least ``page``, ``Hkv``, ``D`` and
    ``pps``."""
    page, Hkv = dims["page"], dims["Hkv"]
    if kv_block is None or head_block is None:
        from repro.kernels import autotune
        tuned = autotune.lookup(kind, autotune.shape_key(**dims))
        if tuned is not None:
            if kv_block is None:
                kv_block = tuned.get("kv_block")
            if head_block is None:
                head_block = tuned.get("head_block")
    if kv_block is None:
        kv_block = kv_block_tokens(page, Hkv * dims["D"], itemsize,
                                   dims["pps"])
    if head_block is None:
        head_block = Hkv
    assert kv_block % page == 0, (page, kv_block)
    assert Hkv % head_block == 0, (Hkv, head_block)
    return kv_block, head_block


def _check_tiles(kv_pages, kv_block: int, Hkv: int, D: int) -> None:
    """Refuse, with the reason, a tiling Mosaic would refuse: a head's
    lane slice of the store must be lane-aligned, and stacked pages
    must each be whole 8-row f32 tiles."""
    page = kv_pages.shape[1]
    if Hkv > 1 and D % 128:
        raise ValueError(
            f"head_dim {D} is not a multiple of 128: a one-head slice of "
            f"the [P, page, Hkv*D] store is not lane-aligned on the chip")
    if kv_block != page and page % 8:
        raise ValueError(
            f"a kv_block of {kv_block // page} pages stacks pages of "
            f"{page} rows, which are not whole 8-row tiles")


def _merged(kv_pages, D: int):
    """[P, page, Hkv, D] view -> the [P, page, Hkv*D] store."""
    if kv_pages.ndim == 4:
        return kv_pages.reshape(*kv_pages.shape[:2], -1)
    return kv_pages


def _kernel(block_tables, nblocks, next_row, q_start, q_lens, q_ref,
            k_hbm, v_hbm, *refs, ppb: int, scale: float, pos_stride: int,
            pos_offset: int, stats: bool, Q: int):
    if stats:
        o_ref, m_out_ref, l_out_ref, *scratch = refs
    else:
        o_ref, *scratch = refs
    k_buf, v_buf, k_sem, v_sem, state, acc_ref, m_ref, l_ref = scratch
    B, pps = block_tables.shape
    _, _, page, HD = k_buf.shape
    Hkv, N, D = acc_ref.shape
    T = ppb * page
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _first_row():
        state[0] = 0                  # buffer of the row's first block
        state[1] = 0                  # its copies already started

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(row, blk, slot, wait_only=False):
        """Start (or wait for) the K and V copies of block ``blk`` of
        ``row`` into buffer ``slot``. A wait needs only the shapes; a
        block running past the table re-reads its last page, whose
        positions the mask then drops."""
        def page_copies(j, carry):
            pid = 0 if wait_only else block_tables[
                row, jnp.minimum(blk * ppb + j, pps - 1)]
            for hbm, buf, sem in ((k_hbm, k_buf, k_sem),
                                  (v_hbm, v_buf, v_sem)):
                cp = pltpu.make_async_copy(hbm.at[pid], buf.at[slot, j],
                                           sem.at[slot])
                if wait_only:
                    cp.wait()
                else:
                    cp.start()
            return carry

        jax.lax.fori_loop(0, ppb, page_copies, 0)

    n = nblocks[b]
    start = q_start[b]
    nq = q_lens[b]

    @pl.when(n > 0)
    def _row():
        @pl.when(state[1] == 0)
        def _fetch_first():
            copies(b, 0, state[0])

        # query rows are (g, t) pairs, t minor: row r is token r % Q
        t_idx = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (N, T), 0), Q)
        # a padding token's limit lies before every position
        limit = jnp.where(t_idx < nq, start + t_idx, pos_offset - 1)
        r = jax.lax.broadcasted_iota(jnp.int32, (N, T), 1)
        if pos_stride != page:        # a shard's slots of each page
            r = jax.lax.div(r, page) * pos_stride + jax.lax.rem(r, page)

        def block(i, slot):
            other = 1 - slot

            @pl.when(i + 1 < n)
            def _prefetch():
                copies(b, i + 1, other)

            @pl.when(i + 1 == n)
            def _prefetch_next_row():
                nb = next_row[b]

                @pl.when(nb < B)
                def _():
                    copies(nb, 0, other)
                state[0] = other
                state[1] = (nb < B).astype(jnp.int32)

            copies(b, i, slot, wait_only=True)
            mask = (i * ppb * pos_stride + pos_offset + r) <= limit
            for h in range(Hkv):
                lanes = pl.ds(h * D, D)
                q = q_ref[0, h].astype(jnp.float32)           # [N, D]
                k = k_buf[slot, :, :, lanes].astype(jnp.float32) \
                    .reshape(T, D)
                v = v_buf[slot, :, :, lanes].astype(jnp.float32) \
                    .reshape(T, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ()))) * scale   # [N, T]
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h]                             # [N, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + p @ v
                m_ref[h] = m_new
            return other

        jax.lax.fori_loop(0, n, block, state[0])

    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
    if stats:
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


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
    _, page, HD = k_pages.shape
    Hkv = HD // D
    kv_block, head_block = _resolve(
        "paged_attention", kv_block, head_block,
        itemsize=k_pages.dtype.itemsize,
        dims=dict(B=B, Hq=Hq, Hkv=Hkv, D=D, page=page,
                  pps=block_tables.shape[1]))
    out = paged_prefill_attention(
        q[:, None], k_pages, v_pages, block_tables, seq_lens - 1,
        jnp.ones_like(seq_lens), pos_stride=pos_stride,
        pos_offset=pos_offset, return_stats=return_stats,
        interpret=interpret, kv_block=kv_block, head_block=head_block)
    if return_stats:
        return tuple(x[:, 0] for x in out)
    return out[:, 0]


def live_blocks(q_start, q_lens, *, block_span: int, nblk: int,
                pos_offset: int = 0):
    """Per row, the blocks that start before ``q_start + q_lens``
    (none for a ``q_lens == 0`` row), at most ``nblk``. A block of
    ``block_span`` positions starts at ``i * block_span + pos_offset``.
    Works on numpy and jax arrays alike."""
    past = (q_start + q_lens - pos_offset).clip(0, None)
    n = ((past + block_span - 1) // block_span).clip(None, nblk)
    return n * (q_lens > 0)


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

    ``kv_block`` (a multiple of ``page``) is the tokens of one block;
    ``head_block`` (divides ``Hkv``) splits the launch over the KV-head
    axis. Unset knobs come from the autotune cache when enabled, else
    the block rule and all heads. Any legal values agree up to
    rounding. Compiled for the chip (``interpret=False``) the tiling
    must be one Mosaic accepts (``_check_tiles``).
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
        "paged_prefill_attention", kv_block, head_block,
        itemsize=k_pages.dtype.itemsize,
        dims=dict(B=B, Q=Q, Hq=Hq, Hkv=Hkv, D=D, page=page,
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
    ppb = kv_block // page
    nblocks = live_blocks(q_start, q_lens, block_span=ppb * pos_stride,
                          nblk=-(-pages_per_seq // ppb),
                          pos_offset=pos_offset).astype(jnp.int32)
    # the first live row after each row (B past the last)
    live_row = jnp.where(nblocks > 0, jnp.arange(B, dtype=jnp.int32), B)
    next_row = jax.lax.cummin(
        jnp.append(live_row[1:], jnp.int32(B)), reverse=True)
    kernel = functools.partial(
        _kernel, ppb=ppb, scale=1.0 / math.sqrt(D), pos_stride=pos_stride,
        pos_offset=pos_offset, stats=return_stats, Q=Q)
    # [B, Q, (Hkv, G), D] -> [B, Hkv, G*Q, D]: rows are (g, t), t minor,
    # so the kernel recovers the token index as row % Q
    N = G * Q
    qg = jnp.moveaxis(q.reshape(B, Q, Hkv, G, D), 1, 3) \
        .reshape(B, Hkv, N, D)
    row_map = lambda b, *_: (b, 0, 0, 0)                 # noqa: E731
    out_specs = pl.BlockSpec((1, Hkv, N, D), row_map)
    out_shape = jax.ShapeDtypeStruct((B, Hkv, N, D), q.dtype)
    if return_stats:
        # stats keep a unit lane dim: a (N, 1) block is the whole of the
        # array's minor two dims, which Mosaic accepts
        stat_spec = pl.BlockSpec((1, Hkv, N, 1), row_map)
        stat_shape = jax.ShapeDtypeStruct((B, Hkv, N, 1), jnp.float32)
        out_specs = [out_specs, stat_spec, stat_spec]
        out_shape = [out_shape, stat_shape, stat_shape]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hkv, N, D), row_map), any_spec,
                  any_spec],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, HD), k_pages.dtype),
            pltpu.VMEM((2, ppb, page, HD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hkv, N, D), jnp.float32),
            pltpu.VMEM((Hkv, N, 1), jnp.float32),
            pltpu.VMEM((Hkv, N, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # rows run in order: a row's last block prefetches the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, nblocks, next_row, q_start, q_lens, qg, k_pages,
      v_pages)

    def unpack(x):                        # [B, Hkv, G*Q, ...] -> token-major
        tail = x.shape[3:]
        return jnp.moveaxis(x.reshape(B, Hkv, G, Q, *tail), 3, 1) \
            .reshape(B, Q, Hq, *tail)

    if return_stats:
        o, m, l = out
        return unpack(o), unpack(m[..., 0]), unpack(l[..., 0])
    return unpack(out)
