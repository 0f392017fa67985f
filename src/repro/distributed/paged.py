"""Tensor-sharded paged KV plane (DESIGN.md §9).

Shards the serving engine's page store over the ``'model'`` axis of a
``('data', 'model')`` mesh, following the same divisibility rules as the
parameter sharding in ``distributed/sharding.py``:

- ``heads``  — KV heads divide the model axis: each shard owns
  ``Hkv / M`` heads of every page. Attention is fully local per shard
  (softmax is per head); shards' outputs are re-joined with an
  ``all_gather`` over the head dim before the output projection.
- ``slots``  — heads do not divide but the page size does (the common
  case for the assigned archs, whose 2-8 KV heads never divide a
  16-way axis — the rule table's "sequence over 'model'" branch): each
  shard owns ``page / M`` token slots of every physical page. A shard
  computes a *partial* online softmax over its slots
  (``return_stats`` in the kernel) and the shards merge exactly:
  ``m* = pmax(m)``, ``w_s = l_s * exp(m_s - m*)``,
  ``o = psum(o_s * w_s) / psum(w_s)``.
- ``replicated`` — neither divides: fall back to full replication
  (every shard computes everything), mirroring ``ShardingRules._m``.

Block tables, tokens, and all model weights stay **replicated** across
'model' (and across 'data'): the paged plane's scaling target is KV
memory and attention bandwidth, which dominate realtime multi-turn
serving; weight tensor-parallelism composes later via
``ShardingRules``. The decode batch is likewise replicated over 'data'
— every shard runs the same fixed-slot batch, so the host-side control
plane (pool, block tables, KV manager) is identical with and without a
mesh and the offload/reload hooks move *sharded* pages through plain
``np.asarray`` gathers / ``device_put`` scatters.

Cross-session page sharing (DESIGN.md §13) is placement-stable by
construction: attaching to a cached prefix only repoints block tables
at existing physical ids — no page contents move, so each shard keeps
serving exactly the head/slot slice it already owns. COW allocates a
fresh page whose writes land through the same re-committed functional
updates; shared pages never enter the transfer ledger (the pool refuses
to mark a refcount>1 page offloading), so sharing cannot strand a
shard's slice on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention, \
    paged_prefill_attention
from repro.launch.mesh import auto_axes


class PagedKVLayout:
    """How one engine's page store [L, P+1, page, Hkv*hd] shards.

    The mesh is taken with Auto axes (``launch.mesh.auto_axes``): the
    engine moves pages with plain ``device_put`` and functional updates
    outside any mesh context, which Explicit axes refuse."""

    def __init__(self, cfg, mesh, page_size: int):
        assert "model" in mesh.axis_names, mesh.axis_names
        if getattr(cfg, "moe", None) is not None:
            raise NotImplementedError(
                f"{cfg.name}: the sharded page planes serve no MoE "
                "config; its held-expert layer runs on one device (no "
                "cell shards it yet)")
        self.cfg = cfg
        self.mesh = auto_axes(mesh)
        self.page_size = page_size
        self.M = int(mesh.shape["model"])
        if cfg.num_kv_heads % self.M == 0:
            self.kind = "heads"
        elif page_size % self.M == 0:
            self.kind = "slots"
        else:
            self.kind = "replicated"

    def __repr__(self):
        return (f"PagedKVLayout(kind={self.kind!r}, M={self.M}, "
                f"mesh={dict(self.mesh.shape)})")

    # ------------------------------------------------------------ specs
    def page_pspec(self, *, with_layers: bool = True) -> P:
        """PartitionSpec for [L, P+1, page, Hkv*hd] (or the 3D
        kernel-level [P, page, Hkv*hd] with ``with_layers=False``).
        Heads shard the merged lane dim: each shard's slice is its
        whole heads."""
        lead = (None,) if with_layers else ()
        if self.kind == "heads":
            return P(*lead, None, None, "model")
        if self.kind == "slots":
            return P(*lead, None, "model")
        return P()

    def page_sharding(self, *, with_layers: bool = True) -> NamedSharding:
        return NamedSharding(self.mesh,
                             self.page_pspec(with_layers=with_layers))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def stage_host_chunk(self, host_chunk):
        """Stage one transfer-engine reload chunk ([n, 2, L, page,
        Hkv*hd] host stack) onto the mesh. The chunk is replicated — it
        indexes whole logical pages, and the follow-up page-store
        scatter plus the engine's placement re-commit
        (``_place_pages``) land it on the layout's exact sharding. The
        caller blocks on *this* buffer to time the transferred bytes
        alone (never on the sharded page store, whose readiness drags
        in unrelated decode work — DESIGN.md §10)."""
        return jax.device_put(host_chunk, self.replicated)

    # ------------------------------------------------------- shard body
    def write_token(self, kc, vc, k, v, write_page, write_slot):
        """Per-shard page write of one token per batch row.

        Runs *inside* shard_map: ``kc``/``vc`` are local shards
        [P+1, page_local, (Hkv*hd)_local]; ``k``/``v`` [B, Hkv*hd] are
        the full (replicated) projections; ``write_page``/``write_slot``
        [B] i32 are global coordinates.
        """
        if self.kind == "heads":
            idx = jax.lax.axis_index("model")
            w = kc.shape[2]
            k = jax.lax.dynamic_slice_in_dim(k, idx * w, w, axis=1)
            v = jax.lax.dynamic_slice_in_dim(v, idx * w, w, axis=1)
            return (kc.at[write_page, write_slot].set(k),
                    vc.at[write_page, write_slot].set(v))
        if self.kind == "slots":
            idx = jax.lax.axis_index("model")
            psl = kc.shape[1]
            own = (write_slot // psl) == idx
            loc = write_slot % psl
            keep = own[:, None]
            k = jnp.where(keep, k, kc[write_page, loc])
            v = jnp.where(keep, v, vc[write_page, loc])
            return kc.at[write_page, loc].set(k), vc.at[write_page, loc].set(v)
        return (kc.at[write_page, write_slot].set(k),
                vc.at[write_page, write_slot].set(v))

    def attend(self, q, kc, vc, block_tables, seq_lens, *,
               interpret: bool = False):
        """Per-shard paged attention + cross-shard combine.

        Runs *inside* shard_map: ``q`` [B, Hq, D] is the full
        (replicated) query; ``kc``/``vc`` are local page shards
        [P+1, page_local, (Hkv*hd)_local]. Returns the full [B, Hq, D]
        attention output, identical on every shard.
        """
        if self.kind == "heads":
            idx = jax.lax.axis_index("model")
            hq_loc = q.shape[1] // self.M
            q_loc = jax.lax.dynamic_slice_in_dim(q, idx * hq_loc, hq_loc,
                                                 axis=1)
            a = paged_attention(q_loc, kc, vc, block_tables, seq_lens,
                                interpret=interpret)
            return jax.lax.all_gather(a, "model", axis=1, tiled=True)
        if self.kind == "slots":
            idx = jax.lax.axis_index("model")
            psl = kc.shape[1]
            # the shard's slots sit at global offset idx*psl inside each
            # page; shifting seq_lens is equivalent to offsetting every
            # local position (masking is the only use of positions here)
            sl_eff = seq_lens - idx * psl
            o, m, l = paged_attention(
                q, kc, vc, block_tables, sl_eff,
                pos_stride=self.page_size, return_stats=True,
                interpret=interpret)
            m_star = jax.lax.pmax(m, "model")
            w = l * jnp.exp(m - m_star)                    # [B, Hq] f32
            den = jax.lax.psum(w, "model")
            num = jax.lax.psum(o.astype(jnp.float32) * w[..., None],
                               "model")
            a = num / jnp.maximum(den, 1e-30)[..., None]
            return a.astype(q.dtype)
        return paged_attention(q, kc, vc, block_tables, seq_lens,
                               interpret=interpret)

    # ------------------------------------------------- fused chunk plane
    def write_chunk(self, kc, vc, k, v, write_pages, write_slots):
        """Per-shard page write of a whole round's token chunks
        (DESIGN.md §11).

        Runs *inside* shard_map: ``kc``/``vc`` are local shards
        [P+1, page_local, (Hkv*hd)_local]; ``k``/``v`` [B, Q, Hkv*hd]
        are the full (replicated) projections; ``write_pages``/
        ``write_slots`` [B, Q] i32 are global coordinates.
        """
        if self.kind == "heads":
            idx = jax.lax.axis_index("model")
            w = kc.shape[2]
            k = jax.lax.dynamic_slice_in_dim(k, idx * w, w, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, idx * w, w, axis=2)
            return (kc.at[write_pages, write_slots].set(k),
                    vc.at[write_pages, write_slots].set(v))
        if self.kind == "slots":
            # tokens another shard owns are redirected to the scratch
            # page (the store's last physical page) instead of the
            # single-token plane's where-keep write-back: a chunk longer
            # than page_local would otherwise write back a *stale* copy
            # of the same local (page, slot) an owned token targets in
            # the same scatter, and duplicate-index resolution order is
            # implementation-defined
            idx = jax.lax.axis_index("model")
            psl = kc.shape[1]
            own = (write_slots // psl) == idx
            loc = write_slots % psl
            wp = jnp.where(own, write_pages, kc.shape[0] - 1)
            return kc.at[wp, loc].set(k), vc.at[wp, loc].set(v)
        return (kc.at[write_pages, write_slots].set(k),
                vc.at[write_pages, write_slots].set(v))

    def attend_chunk(self, q, kc, vc, block_tables, q_start, q_lens, *,
                     interpret: bool = False):
        """Per-shard fused multi-token attention + cross-shard combine.

        Runs *inside* shard_map: ``q`` [B, Q, Hq, D] is the full
        (replicated) query chunk; ``kc``/``vc`` are local page shards.
        Returns the full [B, Q, Hq, D] attention output, identical on
        every shard.
        """
        if self.kind == "heads":
            idx = jax.lax.axis_index("model")
            hq_loc = q.shape[2] // self.M
            q_loc = jax.lax.dynamic_slice_in_dim(q, idx * hq_loc, hq_loc,
                                                 axis=2)
            a = paged_prefill_attention(q_loc, kc, vc, block_tables,
                                        q_start, q_lens,
                                        interpret=interpret)
            return jax.lax.all_gather(a, "model", axis=2, tiled=True)
        if self.kind == "slots":
            idx = jax.lax.axis_index("model")
            psl = kc.shape[1]
            # the shard's slots sit at global offset idx*psl inside each
            # page; shifting the *traced* q_start shifts every masking
            # comparison (causal limit and derived seq_len alike), which
            # is equivalent to offsetting every local kv position —
            # pos_offset is static and cannot carry the traced idx
            o, m, l = paged_prefill_attention(
                q, kc, vc, block_tables, q_start - idx * psl, q_lens,
                pos_stride=self.page_size, return_stats=True,
                interpret=interpret)
            m_star = jax.lax.pmax(m, "model")          # [B, Q, Hq] f32
            w = l * jnp.exp(m - m_star)
            den = jax.lax.psum(w, "model")
            num = jax.lax.psum(o.astype(jnp.float32) * w[..., None],
                               "model")
            a = num / jnp.maximum(den, 1e-30)[..., None]
            return a.astype(q.dtype)
        return paged_prefill_attention(q, kc, vc, block_tables, q_start,
                                       q_lens, interpret=interpret)


# ======================================================================
# shard_map wrappers
# ======================================================================
def sharded_paged_attention(layout: PagedKVLayout, q, k_pages, v_pages,
                            block_tables, seq_lens, *,
                            interpret: bool = False):
    """Global-view sharded paged attention: q [B, Hq, D] and
    block_tables/seq_lens replicated; k_pages/v_pages [P, page, Hkv*D]
    (or the [P, page, Hkv, D] view) sharded per the layout. Drop-in
    equal to ``paged_attention``."""
    spec = layout.page_pspec(with_layers=False)
    rep = P()

    def body(q, kp, vp, bt, sl):
        return layout.attend(q, kp, vp, bt, sl, interpret=interpret)

    k_pages = k_pages.reshape(*k_pages.shape[:2], -1)
    v_pages = v_pages.reshape(*v_pages.shape[:2], -1)
    f = jax.shard_map(body, mesh=layout.mesh,
                  in_specs=(rep, spec, spec, rep, rep), out_specs=rep,
                  check_vma=False)
    return f(q, k_pages, v_pages, block_tables, seq_lens)


def sharded_flash_prefill(layout: PagedKVLayout, q, k, v, *,
                          causal: bool = True, window=None,
                          q_offset: int = 0, block_q: int = 128,
                          block_kv: int = 128, interpret: bool = False):
    """shard_map-wrapped chunked-prefill flash attention.

    Heads shard over 'model' when divisible (fully local — softmax is
    per head); otherwise every shard computes the full call (the
    replication fallback). q [B, Hq, Sq, D]; k/v [B, Hkv, Skv, D].

    Kernel-level building block, parity-pinned by
    tests/test_sharded_plane.py but not yet on an engine path: the
    engine's turn-0 prefill currently runs the replicated dense forward
    and grafts into sharded pages, and turn-N prefill teacher-forces
    through the sharded decode step. Wiring this into a chunked sharded
    prefill is the follow-up that makes long-prompt admission scale
    with the mesh (DESIGN.md §9)."""
    kernel = functools.partial(flash_prefill, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)
    Hq, Hkv = q.shape[1], k.shape[1]
    if Hq % layout.M == 0 and Hkv % layout.M == 0:
        spec = P(None, "model")
        f = jax.shard_map(kernel, mesh=layout.mesh,
                      in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
    else:
        rep = P()
        f = jax.shard_map(kernel, mesh=layout.mesh,
                      in_specs=(rep, rep, rep), out_specs=rep,
                      check_vma=False)
    return f(q, k, v)


def make_sharded_step(cfg, layout: PagedKVLayout, *,
                      interpret: bool = False):
    """The sharded twin of ``serving.paged_engine.paged_decode_step``:
    one jitted shard_map over the whole step — weights/tokens/tables
    replicated in, pages sharded in/out, logits replicated out. The
    body is the *same* ``paged_decode_step`` code path with this
    layout's write/attend plane swapped in, so sharded and single-
    device engines cannot drift."""
    from repro.serving.paged_engine import paged_decode_step

    body = functools.partial(paged_decode_step, cfg, interpret=interpret,
                             plane=layout)
    spec = layout.page_pspec(with_layers=True)
    rep = P()
    f = jax.shard_map(
        body, mesh=layout.mesh,
        in_specs=(rep, rep, rep, spec, spec, rep, rep, rep, rep),
        out_specs=(rep, spec, spec),
        check_vma=False)
    return jax.jit(f)


def make_sharded_fused_step(cfg, layout: PagedKVLayout, *,
                            interpret: bool = False):
    """The sharded twin of ``serving.paged_engine.paged_fused_step``
    (DESIGN.md §11): one jitted shard_map over the whole fused round —
    weights / token chunks / tables / q_start / q_lens replicated in,
    pages sharded in/out, last-token logits replicated out. Same body,
    same no-drift argument as ``make_sharded_step``."""
    from repro.serving.paged_engine import paged_fused_step

    body = functools.partial(paged_fused_step, cfg, interpret=interpret,
                             plane=layout)
    spec = layout.page_pspec(with_layers=True)
    rep = P()
    f = jax.shard_map(
        body, mesh=layout.mesh,
        in_specs=(rep, rep, rep, spec, spec, rep, rep, rep, rep, rep),
        out_specs=(rep, spec, spec),
        check_vma=False)
    return jax.jit(f)


def make_sharded_spec_step(cfg, layout: PagedKVLayout, *,
                           interpret: bool = False):
    """The sharded twin of the speculative fused round (DESIGN.md §16):
    ``paged_fused_step(..., spec=True)`` under the same shard_map as
    ``make_sharded_fused_step``, with the extra replicated per-position
    argmax output ``outs [B, Q]``. The verify math is the identical
    fused body — only the logits slice/argmax tail differs — so the
    no-drift argument carries over unchanged."""
    from repro.serving.paged_engine import paged_fused_step

    body = functools.partial(paged_fused_step, cfg, interpret=interpret,
                             plane=layout, spec=True)
    spec = layout.page_pspec(with_layers=True)
    rep = P()
    f = jax.shard_map(
        body, mesh=layout.mesh,
        in_specs=(rep, rep, rep, spec, spec, rep, rep, rep, rep, rep),
        out_specs=(rep, rep, spec, spec),
        check_vma=False)
    return jax.jit(f)
