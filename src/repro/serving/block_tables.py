"""Block-table assembly for the paged realtime engine (DESIGN.md §3).

Bridges the host-side ``PagedPool`` bookkeeping and the Pallas
``paged_attention`` kernel: per-round [B, pages_per_seq] int32 tables for
a *fixed-size* decode batch — inactive rows point at a reserved scratch
page so the batch shape (and therefore the compiled step function) never
changes across rounds — plus the layer-stacked K/V page-store adapter the
pool's DRAM tier moves page contents through.

Tables are **replicated** across every mesh axis in the sharded data
plane (DESIGN.md §9): they index the unsharded physical-page dim, so
one table drives all shards; ``LayerStackedPages`` works unchanged on a
sharded store because its reads gather (``np.asarray``) and its writes
are functional updates whose placement the engine re-commits.

Shared-prefix attach (DESIGN.md §13) needs nothing new here: a session
that attached to cached pages simply lists those physical ids in its
block table like any other pages, and the fused plane's per-row
``q_start`` already renders prefill rows from an arbitrary offset — the
attacher's first prefilled token lands mid-sequence with the shared
pages attended read-only, no kernel or table-shape change.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from repro.kvcache.paged import PagedPool


@dataclass
class BatchTables:
    """One decode round's kernel inputs, host-side (cheap int32 arrays)."""
    block_tables: np.ndarray     # [B, pages_per_seq] i32 physical pages
    seq_lens: np.ndarray         # [B] i32 attention length (post-write)
    positions: np.ndarray        # [B] i32 absolute position of new token
    write_page: np.ndarray       # [B] i32 physical page the token writes
    write_slot: np.ndarray       # [B] i32 slot within that page
    active: np.ndarray           # [B] bool — padded rows are False


def assemble(pool: PagedPool, rows: List[Optional[Tuple[str, int]]],
             pages_per_seq: int, scratch_page: int) -> BatchTables:
    """Build the tables for one decode round.

    ``rows[i]`` is ``(seq_id, tokens_written)`` for the session served by
    batch row i, or None for a padding row. Padding rows write to (and
    attend over one slot of) ``scratch_page`` — a physical page outside
    the pool's managed range — so their lanes compute finite garbage that
    is discarded, and real pages are never clobbered.

    Every active sequence must be fully HBM-resident (§5.2 sync-fallback
    contract) and must already own the page its next token writes into.
    """
    B = len(rows)
    bt = np.full((B, pages_per_seq), scratch_page, np.int32)
    seq_lens = np.ones((B,), np.int32)
    positions = np.zeros((B,), np.int32)
    write_page = np.full((B,), scratch_page, np.int32)
    write_slot = np.zeros((B,), np.int32)
    active = np.zeros((B,), bool)
    for i, row in enumerate(rows):
        if row is None:
            continue
        sid, written = row
        s = pool.seq(sid)
        if s.offloaded:
            raise RuntimeError(
                f"{sid} has offloaded pages; reload before scheduling")
        n = len(s.pages)
        if n > pages_per_seq:
            raise ValueError(f"{sid}: {n} pages > table width "
                             f"{pages_per_seq}")
        bt[i, :n] = s.pages
        page_idx, slot = divmod(written, pool.page_size)
        if page_idx >= n:
            raise RuntimeError(
                f"{sid}: page {page_idx} for token {written} not "
                f"allocated (owns {n})")
        write_page[i] = s.pages[page_idx]
        write_slot[i] = slot
        positions[i] = written
        seq_lens[i] = written + 1
        active[i] = True
    return BatchTables(bt, seq_lens, positions, write_page, write_slot,
                       active)


@dataclass
class FusedBatchTables:
    """One fused round's kernel inputs (DESIGN.md §11): every batch row
    carries up to Q consecutive tokens of one sequence."""
    block_tables: np.ndarray     # [B, pages_per_seq] i32 physical pages
    q_start: np.ndarray          # [B] i32 first token's absolute position
    q_lens: np.ndarray           # [B] i32 valid tokens this row (0 = pad)
    positions: np.ndarray        # [B, Q] i32 absolute position per token
    write_pages: np.ndarray      # [B, Q] i32 physical page per token
    write_slots: np.ndarray      # [B, Q] i32 slot within that page


def assemble_fused(pool: PagedPool,
                   rows: List[Optional[Tuple[str, int, int]]], q_tokens: int,
                   pages_per_seq: int, scratch_page: int) -> FusedBatchTables:
    """Build the tables for one fused round.

    ``rows[i]`` is ``(seq_id, tokens_written, n_tokens)`` — the session
    served by batch row i feeds ``n_tokens`` consecutive tokens starting
    at absolute position ``tokens_written`` — or None for a padding row.
    ``q_tokens`` is the (bucketed) query-axis width; token slots past
    ``n_tokens`` and whole padding rows point at ``scratch_page`` with
    ``q_lens`` masking them out of attention, so their lanes compute
    finite garbage that is discarded and real pages are never clobbered.

    Every active sequence must be fully HBM-resident and must already
    own every page its chunk writes into (the caller grew the sequence
    for the whole grant before packing — the §5.2 contract unchanged).
    """
    B = len(rows)
    bt = np.full((B, pages_per_seq), scratch_page, np.int32)
    q_start = np.zeros((B,), np.int32)
    q_lens = np.zeros((B,), np.int32)
    positions = np.zeros((B, q_tokens), np.int32)
    write_pages = np.full((B, q_tokens), scratch_page, np.int32)
    # padded token slots spread over the scratch page so one launch's
    # scatter has as few duplicate targets as possible (their contents
    # are garbage either way; nothing ever attends to them)
    write_slots = np.tile(np.arange(q_tokens, dtype=np.int32)[None, :]
                          % max(1, pool.page_size), (B, 1))
    for i, row in enumerate(rows):
        if row is None:
            continue
        sid, written, n_tok = row
        assert 0 < n_tok <= q_tokens, (sid, n_tok, q_tokens)
        s = pool.seq(sid)
        if s.offloaded:
            raise RuntimeError(
                f"{sid} has offloaded pages; reload before scheduling")
        n = len(s.pages)
        if n > pages_per_seq:
            raise ValueError(f"{sid}: {n} pages > table width "
                             f"{pages_per_seq}")
        bt[i, :n] = s.pages
        q_start[i] = written
        q_lens[i] = n_tok
        pos = written + np.arange(n_tok)
        page_idx = pos // pool.page_size
        if page_idx[-1] >= n:
            raise RuntimeError(
                f"{sid}: page {page_idx[-1]} for token {pos[-1]} not "
                f"allocated (owns {n})")
        positions[i, :n_tok] = pos
        write_pages[i, :n_tok] = np.asarray(s.pages, np.int64)[page_idx]
        write_slots[i, :n_tok] = pos % pool.page_size
    return FusedBatchTables(bt, q_start, q_lens, positions, write_pages,
                            write_slots)


class LayerStackedPages:
    """Adapts layer-major K/V page arrays ([L, P, page, Hkv*hd], the
    scan-friendly layout the decode step wants) to the PagedPool's
    page-major offload/reload interface (``kv_pages[phys]`` -> host copy;
    ``kv_pages.at[phys].set(copy)`` -> updated store).

    A host copy is the stacked ``[2, L, page, Hkv*hd]`` (k, v) contents
    of one physical page — what the DRAM tier stores per page.
    """

    def __init__(self, k, v):
        self.k = k
        self.v = v

    def __getitem__(self, phys: int) -> np.ndarray:
        return np.stack([np.asarray(self.k[:, phys]),
                         np.asarray(self.v[:, phys])])

    @property
    def at(self) -> "_StoreAt":
        return _StoreAt(self)


class _StoreAt:
    def __init__(self, store: LayerStackedPages):
        self._store = store

    def __getitem__(self, phys: int) -> "_StoreSet":
        return _StoreSet(self._store, phys)


class _StoreSet:
    def __init__(self, store: LayerStackedPages, phys):
        self._store = store
        self._phys = phys

    def set(self, host_copy) -> LayerStackedPages:
        """Scalar phys takes one [2, L, page, ...] copy; an index array
        takes the stacked [n, 2, L, page, ...] batch (the pool's batched
        reload) — either way a single functional update per component."""
        s, p = self._store, self._phys
        hc = np.asarray(host_copy)
        if np.ndim(p) == 0:
            k_new, v_new = hc[0], hc[1]
        else:
            k_new = np.moveaxis(hc[:, 0], 0, 1)   # [L, n, page, Hkv*hd]
            v_new = np.moveaxis(hc[:, 1], 0, 1)
        return LayerStackedPages(s.k.at[:, p].set(k_new),
                                 s.v.at[:, p].set(v_new))
