"""Paged multi-turn realtime engine — the LiveServe data plane on real
paged JAX state (DESIGN.md §3).

Where ``RealtimeLLMEngine`` keeps a dense per-slot ring cache and lives
for one turn, this engine runs the paper's full KV story on physical
pages:

- KV lives in a ``PagedPool``-managed page store ([L, P+1, page,
  Hkv*hd] per K and V — heads merged into the lane dim, the layout the
  kernel tiles on the chip; physical page P is a scratch page for
  padded batch rows).
  Decode attends through the Pallas ``paged_attention`` kernel via
  per-round block tables; prefill writes pages through the pool.
- Sessions are **multi-turn**: when a turn ends (or is barged-in via
  ``abort``), committed pages stay owned by the session. ``KVManager``
  eviction decisions *physically* offload suffix pages to the pool's
  host-numpy DRAM tier (bit-exact round-trip), and the
  ``SpeechPreloader`` reloads them during user speech so the next turn
  resumes with warm KV and zero re-prefill tokens.
- The control plane decides; the engine executes. Two driving modes
  share one data path: ``step()`` lets the engine's own
  ``UrgencyScheduler`` pick the round (scripted demos), while the
  realtime gateway (DESIGN.md §4) calls ``submit_turn``/``run_round``
  with *its* scheduler's decision — per-round candidate set, per-slot
  chunk budgets, chunked paged prefill interleaved with decode. Either
  way scheduling affects *when* tokens appear, never *which* (the §5.2
  correctness contract, shared with the dense engine and verified in
  tests/test_paged_engine.py and tests/test_gateway.py).

The decode batch is a fixed ``slots``-row batch (one compiled step for
the whole run): unscheduled/empty rows are padded onto the scratch page,
so — unlike the dense engine — holding a slot needs no cache-length
rewind; nothing the padded row writes is ever addressed again.

Families: global-attention stacks (dense / moe / vlm; no MLA, no sliding
window) — pages hold full-context KV, which is what the LiveServe
offload hierarchy manages.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_manager import KVManager
from repro.core.monitor import RuntimeMonitor
from repro.core.preload import SpeechPreloader
from repro.core.scheduler import SchedulerConfig, UrgencyScheduler
from repro.core.session import Phase, Request, RequestState
from repro.core.transfer_engine import MIGRATE, TransferEngine
from repro.kernels.paged_attention import kv_block_tokens, live_blocks, \
    paged_attention, paged_prefill_attention
from repro.kvcache.paged import OutOfPages, PagedPool
from repro.kvcache.quant import KVWireCodec
from repro.models import init_cache, prefill
from repro.models import layers as L
from repro.models.model import _embed, _logits, _mlp_block
from repro.models.moe import moe_held
from repro.serving.block_tables import BatchTables, FusedBatchTables, \
    LayerStackedPages, assemble, assemble_fused
from repro.serving.engine import RoundLimitExceeded, _StepClock, \
    schedule_round
from repro.serving.tracing import span


# ======================================================================
# jitted data plane
# ======================================================================
def _merge_heads(x):
    """[..., Hkv, hd] projections -> [..., Hkv*hd] page rows."""
    return x.reshape(*x.shape[:-2], -1)


def _held_ffn(cfg, lp, h, valid, interpret):
    """The feed-forward half of a served MoE config's layer: the
    dropless held-expert layer (DESIGN.md §17) on an expert layer, with
    its routing counts; a leading dense layer counts nothing."""
    if "moe" not in lp:
        h, _ = _mlp_block(cfg, lp, h, None)
        return h, jnp.zeros((3,), jnp.int32)
    y, counts = moe_held(lp["moe"], L.rms_norm(h, lp["ln2"], cfg.rms_eps),
                         cfg, valid, interpret=interpret)
    return h + y, counts


def _layers(body, params, x, k_pages, v_pages, moe: bool):
    """Run ``body`` over the leading unstacked layers, then scan it over
    the stacked ones. Returns (x, k_pages, v_pages, the routing counts
    summed over layers, or None)."""
    counts = jnp.zeros((3,), jnp.int32) if moe else None
    npre = len(params.get("layers_pre", []))
    for i, lp in enumerate(params.get("layers_pre", [])):
        x, out = body(x, (lp, k_pages[i], v_pages[i]))
        k_pages = k_pages.at[i].set(out[0])
        v_pages = v_pages.at[i].set(out[1])
        if moe:
            counts = counts + out[2]
    x, out = jax.lax.scan(
        body, x, (params["layers"], k_pages[npre:], v_pages[npre:]))
    kcs, vcs = out[:2]
    k_pages = jnp.concatenate([k_pages[:npre], kcs]) if npre else kcs
    v_pages = jnp.concatenate([v_pages[:npre], vcs]) if npre else vcs
    if moe:
        counts = counts + jnp.sum(out[2], axis=0)
    return x, k_pages, v_pages, counts


def paged_decode_step(cfg, params, tokens, positions, k_pages, v_pages,
                      block_tables, seq_lens, write_page, write_slot,
                      *, interpret: bool = False, plane=None):
    """One token per batch row through the paged KV store.

    tokens/positions/write_page/write_slot [B] i32;
    k_pages/v_pages [L, P+1, page, Hkv*hd]; block_tables [B, pps] i32;
    seq_lens [B] i32 (post-write attention lengths).
    Returns (logits [B, V], k_pages, v_pages), and for an MoE config
    the held-expert layers' routing counts [3] last (``_held_ffn``);
    rows writing to the scratch page are padding and route nothing.

    ``plane`` swaps the page write + attention strategy: None is the
    single-device path; a ``distributed.paged.PagedKVLayout`` makes this
    the per-shard body of a shard_map over the 'model' axis (local page
    shards, replicated everything else — DESIGN.md §9). Same code path
    either way, so sharded and unsharded engines cannot drift.
    """
    x = _embed(cfg, params, tokens[:, None])
    pos = positions[:, None]                            # [B, 1]
    moe = cfg.moe is not None
    if moe:
        valid = (write_page < k_pages.shape[1] - 1)[:, None]

    def body(carry, xs):
        lp, kc, vc = xs
        h = L.rms_norm(carry, lp["ln1"], cfg.rms_eps)
        q, k, v = L.attn_project_qkv(lp["attn"], cfg, h, pos)
        k, v = _merge_heads(k[:, 0]), _merge_heads(v[:, 0])
        if plane is None:
            kc = kc.at[write_page, write_slot].set(k)
            vc = vc.at[write_page, write_slot].set(v)
            a = paged_attention(q[:, 0], kc, vc, block_tables, seq_lens,
                                interpret=interpret)
        else:
            kc, vc = plane.write_token(kc, vc, k, v, write_page,
                                       write_slot)
            a = plane.attend(q[:, 0], kc, vc, block_tables, seq_lens,
                             interpret=interpret)
        h = carry + L.attn_output(lp["attn"], a[:, None])
        if not moe:
            h, _ = _mlp_block(cfg, lp, h, None)
            return h, (kc, vc)
        h, n = _held_ffn(cfg, lp, h, valid, interpret)
        return h, (kc, vc, n)

    x, k_pages, v_pages, counts = _layers(body, params, x, k_pages,
                                          v_pages, moe)
    tail = (counts,) if moe else ()
    return (_logits(cfg, params, x)[:, 0], k_pages, v_pages) + tail


def paged_fused_step(cfg, params, tokens, positions, k_pages, v_pages,
                     block_tables, q_start, q_lens, write_pages,
                     write_slots, *, interpret: bool = False, plane=None,
                     spec: bool = False):
    """One fused round: up to Q consecutive tokens per batch row through
    the paged KV store in a single launch (DESIGN.md §11).

    tokens/positions/write_pages/write_slots [B, Q] i32;
    q_start/q_lens [B] i32 (first absolute position / valid tokens per
    row — 0 marks a padding row); k_pages/v_pages [L, P+1, page,
    Hkv*hd]; block_tables [B, pps] i32. Returns (logits [B, V] of each
    row's *last valid* token, k_pages, v_pages); with ``spec`` (the
    speculative verify variant, DESIGN.md §16) the result is (logits,
    outs [B, Q] i32, k_pages, v_pages) where ``outs[b, t]`` is the
    argmax after position t — fed tokens are ``[pending, d_1..d_m]``,
    so ``outs[b, j] == tokens[b, j+1]`` accepts draft j+1, and the
    committed stream stays exactly the greedy one.

    Per layer the whole chunk's K/V is scattered into the pages first,
    then every query token attends causally over history + chunk prefix
    via ``paged_prefill_attention`` — so a PREFILL slot's C-token grant
    and every DECODE slot's single token share one compiled step.
    ``plane`` swaps the write/attend strategy exactly as in
    ``paged_decode_step`` (None = single device; a ``PagedKVLayout``
    makes this the per-shard body of a shard_map — same code path, so
    sharded and unsharded engines cannot drift).

    For an MoE config the held-expert layers' routing counts [3] come
    last in the result (``_held_ffn``), beside the logits, and the
    tokens past each row's ``q_lens`` route nothing.
    """
    x = _embed(cfg, params, tokens)                     # [B, Q, d]
    moe = cfg.moe is not None
    if moe:
        valid = jnp.arange(tokens.shape[1])[None, :] < q_lens[:, None]

    def body(carry, xs):
        lp, kc, vc = xs
        h = L.rms_norm(carry, lp["ln1"], cfg.rms_eps)
        q, k, v = L.attn_project_qkv(lp["attn"], cfg, h, positions)
        k, v = _merge_heads(k), _merge_heads(v)
        if plane is None:
            kc = kc.at[write_pages, write_slots].set(k)
            vc = vc.at[write_pages, write_slots].set(v)
            a = paged_prefill_attention(q, kc, vc, block_tables,
                                        q_start, q_lens,
                                        interpret=interpret)
        else:
            kc, vc = plane.write_chunk(kc, vc, k, v, write_pages,
                                       write_slots)
            a = plane.attend_chunk(q, kc, vc, block_tables, q_start,
                                   q_lens, interpret=interpret)
        h = carry + L.attn_output(lp["attn"], a)
        if not moe:
            h, _ = _mlp_block(cfg, lp, h, None)
            return h, (kc, vc)
        h, n = _held_ffn(cfg, lp, h, valid, interpret)
        return h, (kc, vc, n)

    x, k_pages, v_pages, counts = _layers(body, params, x, k_pages,
                                          v_pages, moe)
    tail = (counts,) if moe else ()
    last = jnp.maximum(q_lens - 1, 0)
    if spec:
        # the verify step consumes every position's argmax, so the full
        # [B, Q, V] logits materialize here; per-position unembeds are
        # independent dot products, so the last-valid slice is the same
        # values the non-spec step computes (the bit-exactness seam)
        full = _logits(cfg, params, x)                  # [B, Q, V]
        outs = jnp.argmax(full, axis=-1).astype(jnp.int32)
        logits = jnp.take_along_axis(
            full, last[:, None, None], axis=1)[:, 0]
        return (logits, outs, k_pages, v_pages) + tail
    # only each row's last valid token's logits are consumed (the next
    # decode token / first output token); slice before the unembed so
    # the launch never materializes [B, Q, V]
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return (_logits(cfg, params, xl)[:, 0], k_pages, v_pages) + tail


# one jitted step per (config, interpret, mesh layout) shared across
# engine instances — a policy-comparison harness (gateway liveserve vs
# fcfs on the same model) pays the XLA compile once, not per engine.
# Values retain cfg so the id() key can never be recycled; the cache is
# LRU-bounded so a long-lived process churning through configs doesn't
# pin every compiled executable forever (engines keep their own _step_fn
# reference, so eviction only forfeits future sharing).
_STEP_FN_CACHE: Dict[tuple, tuple] = {}
_STEP_FN_CACHE_MAX = 8


def _jitted_step(cfg, interpret: bool, layout=None, *,
                 fused: bool = False, spec: bool = False):
    assert fused or not spec, "spec is a fused-plane variant"
    lkey = None if layout is None else (layout.mesh, layout.kind,
                                        layout.page_size)
    key = (id(cfg), interpret, lkey, fused, spec)
    hit = _STEP_FN_CACHE.pop(key, None)
    if hit is None:
        if layout is None:
            body = paged_fused_step if fused else paged_decode_step
            if spec:
                body = functools.partial(body, spec=True)
            fn = jax.jit(functools.partial(body, cfg,
                                           interpret=interpret))
        elif spec:
            from repro.distributed.paged import make_sharded_spec_step
            fn = make_sharded_spec_step(cfg, layout, interpret=interpret)
        elif fused:
            from repro.distributed.paged import make_sharded_fused_step
            fn = make_sharded_fused_step(cfg, layout, interpret=interpret)
        else:
            from repro.distributed.paged import make_sharded_step
            fn = make_sharded_step(cfg, layout, interpret=interpret)
        hit = (cfg, fn)
    _STEP_FN_CACHE[key] = hit                  # re-insert: LRU order
    while len(_STEP_FN_CACHE) > _STEP_FN_CACHE_MAX:
        _STEP_FN_CACHE.pop(next(iter(_STEP_FN_CACHE)))
    return hit[1]


def _q_bucket(n: int) -> int:
    """Round a round's query-axis width up to a power of two so the
    fused step compiles O(log max_chunk) executables, not one per
    distinct grant size."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# ======================================================================
# host-side session state
# ======================================================================
@dataclass
class PagedSlot:
    """A live decode slot (one in-flight turn)."""
    session_id: str
    request: Request
    pending_token: int              # next token to feed
    tokens: List[int] = field(default_factory=list)
    # prompt tokens still to be teacher-forced (scheduler-driven chunked
    # prefill via submit_turn/run_round; None on the synchronous paths)
    prompt: Optional[np.ndarray] = None


@dataclass
class PagedSession:
    """Survives across turns: the multi-turn identity that owns pages."""
    session_id: str
    kv_len: int = 0                 # tokens whose KV is written
    base_pages: int = 0             # pages owned when current turn began
    turn_index: int = 0
    turn_arrival: float = 0.0
    reload_stall_s: float = 0.0     # on-path stall charged to this turn
    reload_off_path_s: float = 0.0  # reload seconds hidden off-path
    reload_on_path_wall_s: float = 0.0  # measured on-path settlement
    ended: bool = False             # user hung up; pages released
    history: List[List[int]] = field(default_factory=list)
    turn_stats: List[dict] = field(default_factory=list)
    # the committed token-id history (len == kv_len): the radix prefix
    # cache keys on it, and it migrates with the session
    token_ids: List[int] = field(default_factory=list)


class PagedRealtimeEngine:
    def __init__(self, cfg, params, *, slots: int = 4, page_size: int = 16,
                 pages_per_seq: int = 16, num_pages: Optional[int] = None,
                 clock=None, scheduler: Optional[UrgencyScheduler] = None,
                 kv: Optional[KVManager] = None, kv_policy: str = "next_use",
                 pcie_gb_s: float = 25.0, preload: bool = True,
                 interpret: Optional[bool] = None, mesh=None,
                 async_transfers: bool = True,
                 chunk_pages: Optional[int] = None,
                 transfer_chunks_per_round: int = 1,
                 fused_step: bool = True,
                 prefix_cache: bool = False,
                 kv_quant: str = "fp32",
                 spec_decode: int = 0,
                 proposer=None, device=None):
        assert cfg.family in ("dense", "moe", "vlm") and cfg.mla is None \
            and cfg.sliding_window is None, \
            "paged engine serves global-attention KV families"
        assert kv_policy in ("next_use", "lru"), \
            "the physical data plane needs an offload tier ('none' " \
            "discards pages; use the simulator for that baseline)"
        self.cfg = cfg
        self.slots = slots
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.max_context = pages_per_seq * page_size
        self.num_pages = num_pages or 2 * slots * pages_per_seq
        self.scratch_page = self.num_pages     # physical page beyond pool
        self.clock = clock or _StepClock()
        self.monitor = RuntimeMonitor(self.clock)
        # KV wire format (DESIGN.md §14): int8 block-quantizes every host
        # copy on the offload path; fp32 is the bit-exact control.
        self.kv_quant = kv_quant
        self.codec = KVWireCodec(kv_quant)
        self.pool = PagedPool(self.num_pages, page_size, codec=self.codec)

        # tensor-sharded page store (DESIGN.md §9): pages shard KV heads
        # (or page slots) over the mesh's 'model' axis; weights, block
        # tables, and the decode batch stay replicated, so every host-
        # side policy/pool path below is mesh-agnostic.
        # ``device`` pins a replica's params and pages to one device
        # (the fleet puts replica i on jax.devices()[i]); None keeps
        # JAX's default device.
        self.mesh = mesh
        self.layout = None
        self.device = device
        assert mesh is None or device is None, "a mesh places its own"
        if mesh is not None:
            from repro.distributed.paged import PagedKVLayout
            self.layout = PagedKVLayout(cfg, mesh, page_size)
            params = jax.device_put(params, self.layout.replicated)
        elif device is not None:
            params = jax.device_put(params, device)
        self.params = params

        hd = cfg.resolved_head_dim
        dtype = jnp.dtype(cfg.dtype)
        shape = (cfg.num_layers, self.num_pages + 1, page_size,
                 cfg.num_kv_heads * hd)
        self.k_pages = jnp.zeros(shape, dtype, device=device)
        self.v_pages = jnp.zeros(shape, dtype, device=device)
        self._place_pages()
        # the attention kernel's KV block (its block rule at this store)
        self._attn_block = kv_block_tokens(page_size, shape[-1],
                                           dtype.itemsize, pages_per_seq)
        bytes_per_token = 2 * cfg.num_layers * cfg.num_kv_heads * hd \
            * dtype.itemsize
        self.kv = kv or KVManager(
            capacity_blocks=self.num_pages, block_size=page_size,
            bytes_per_token=float(bytes_per_token), monitor=self.monitor,
            policy=kv_policy, pcie_gb_s=pcie_gb_s, clock=self.clock)
        assert self.kv.capacity == self.num_pages \
            and self.kv.block_size == page_size, \
            "KVManager accounting must be 1:1 with pool pages"
        # price the wire format into the modeled PCIe channel before the
        # transfer engine sizes its chunks off transfer_time(1): every
        # consumer (chunk sizing, preload admission, stall settlement,
        # migration) then sees compressed bytes. block_bytes stays the
        # logical page size for capacity accounting.
        self.kv.channel.wire_scale = self.codec.wire_scale(dtype)
        # the async chunked transfer engine (DESIGN.md §10): DRAM<->HBM
        # movement queues as page-group chunks drained by run_round (and
        # the gateways' idle loops); async_transfers=False degrades to
        # the synchronous move-at-decision-time plane (the differential
        # control for bit-exactness tests)
        self.async_transfers = async_transfers
        self.transfer_chunks_per_round = transfer_chunks_per_round
        self.transfer = TransferEngine(self.kv.channel,
                                       chunk_pages=chunk_pages)
        self.transfer.set_io(reload_chunk=self._io_reload_chunk,
                             offload_chunk=self._io_offload_chunk)
        self.kv.set_page_hooks(
            on_evict=self._offload_pages, on_reload=self._reload_pages,
            on_cancel_reload=self._cancel_reload_pages,
            on_finish_transfers=(self._finish_transfers
                                 if async_transfers else None),
            pending_offload=self.transfer.pending_offload_pages)
        self.preloader = SpeechPreloader(self.kv, self.monitor,
                                         enabled=preload)
        # speculative multi-token decode (DESIGN.md §16): a decode slot
        # feeds [pending, d_1..d_K] drafts as one fused row and the
        # verify launch's per-position argmax accepts the longest
        # matching prefix — lossless by construction (the committed
        # stream is exactly the greedy one; spec_decode=0 keeps today's
        # one-token plane as the bit-exact differential control, the
        # async_transfers=False pattern).
        assert spec_decode >= 0
        assert spec_decode == 0 or fused_step, \
            "spec_decode verifies drafts in one fused launch; it " \
            "cannot run on the per-token control plane " \
            "(fused_step=False)"
        self.spec_decode = int(spec_decode)
        self.proposer = None
        if self.spec_decode > 0:
            from repro.serving.spec_decode import build_proposer
            self.proposer = build_proposer(
                proposer if proposer is not None else "ngram")
        # prefill_chunk clamps to the self-scheduled round budget
        # (= slots*(1+K) tokens) exactly as the gateway clamps its own —
        # a bigger chunk could never be admitted (Algorithm 1
        # head-of-line); decode grants become "up to 1+K" draft budgets
        self.scheduler = scheduler or UrgencyScheduler(
            SchedulerConfig(), self.monitor, stage="thinker",
            kv_occupancy=self.kv.occupancy,
            prefill_chunk=max(1, slots),
            decode_chunk=1 + self.spec_decode)

        self.sessions: Dict[str, PagedSession] = {}
        self.slot_state: Dict[int, Optional[PagedSlot]] = {
            i: None for i in range(slots)}
        if interpret is None:
            # Pallas compiles for the chip the pages live on and runs
            # interpreted anywhere else (the CPU test suite)
            interpret = next(iter(self.k_pages.devices())).platform != "tpu"
        self.interpret = interpret
        self._step_fn = _jitted_step(cfg, interpret, self.layout)
        # the fused token-budget plane (DESIGN.md §11): one launch per
        # round, C-token prefill chunks included. fused_step=False keeps
        # the per-token plane as the differential control (the role
        # async_transfers=False plays for the transfer engine).
        self.fused_step = fused_step
        self._fused_fn = _jitted_step(cfg, interpret, self.layout,
                                      fused=True) if fused_step else None
        # with speculation on, EVERY fused round runs the spec variant
        # (prefill rows simply ignore the per-position argmaxes) so the
        # engine compiles one executable family, not two
        self._spec_fn = _jitted_step(cfg, interpret, self.layout,
                                     fused=True, spec=True) \
            if self.spec_decode > 0 else None
        # shared-prefix KV subsystem (DESIGN.md §13): a radix index over
        # committed pages + refcounted attach/COW in the pool.
        # prefix_cache=False keeps today's private-pages behavior as the
        # bit-exact differential twin (the async_transfers=False /
        # fused_step=False pattern).
        self.prefix_cache = None
        if prefix_cache:
            from repro.kvcache.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(page_size)
            self.kv.set_cache_hooks(reclaim=self._reclaim_cached,
                                    reclaimable=self._cached_reclaimable)
        self._pending_hit: Dict[str, int] = {}
        # telemetry
        self.reload_wall_s: List[float] = []   # measured host->device time
        self.offload_events: List[tuple] = []
        self.pressure_holds = 0                # feeds held mid-round
        self.fused_launches = 0                # fused-plane step launches
        # token slots of every launch: valid tokens fed, and the grid
        # (slots x Q bucket) the step computed
        self.launch_valid_tokens = 0
        self.launch_grid_tokens = 0
        # attention KV blocks of every launch: those the kernel fetched
        # and computed (each row's blocks up to its length), and the
        # grid a fixed slots x context-cap tiling would step through
        self.attn_blocks_live = 0
        self.attn_blocks_grid = 0
        # the held-expert layers' routing, summed over layers and
        # launches (MoE configs; fetched with each launch's logits):
        # (token, expert) pairs routed, those landing on experts this
        # chip holds, and held experts touched
        self.moe_routed_pairs = 0
        self.moe_held_pairs = 0
        self.moe_experts_touched = 0
        # reload pages landed inside a turn-start settlement (on the
        # turn's path) and by transfer drains (off it)
        self.settled_reload_pages = 0
        self.drained_reload_pages = 0
        self._settling = False
        self.peak_shared_pages = 0             # max pages with refcount>1
        self.cow_copies = 0                    # copy-on-write page copies
        # speculation accounting (the §16 invariant:
        # accepted + rejected == drafted, always)
        self.spec_drafted = 0                  # draft tokens verified
        self.spec_accepted = 0                 # drafts matching argmax
        self.spec_rejected = 0                 # drafts rolled back
        self.spec_rounds = 0                   # verify rows with drafts
        # quality-gate tap: when set, called as logit_tap(sid, logits)
        # for every fed row (fused rows report last-valid-token logits —
        # the ones the argmax commits)
        self.logit_tap = None

    # ------------------------------------------------------------ pages
    def _place_pages(self) -> None:
        """Re-commit the page store to its mesh sharding. Host-driven
        page updates (DRAM reload scatter, dense-prefill graft) run
        outside the jitted step and may leave the result on inferred
        shardings; the jitted shard_map expects the layout's exact
        placement, so re-place after every such update (a no-op copy
        when the sharding already matches, and always a no-op without a
        mesh)."""
        if self.layout is not None:
            sh = self.layout.page_sharding()
            self.k_pages = jax.device_put(self.k_pages, sh)
            self.v_pages = jax.device_put(self.v_pages, sh)

    def _sync_page_counts(self, sid: str) -> None:
        # read-only bounds: a session released from the pool (hangup) or
        # never admitted must report 0/0, not have `pool.seq` re-create a
        # ghost entry for it (check_invariants iterates pool.seqs)
        s = self.pool.seqs.get(sid)
        # resident = usable on device (offloading pages still count: the
        # copy-then-free slot holds valid contents); offloaded = host
        # copy is authoritative (loading pages still count: contents
        # have not landed yet) — the two partitions sum to committed
        self.monitor.on_page_movement(
            sid, resident=self.pool.resident_pages(sid),
            offloaded=len(s.offloaded) if s else 0)

    def _offload_pages(self, sid: str, blocks: int) -> None:
        """KVManager eviction hook: queue suffix pages for DRAM
        (copy-then-free — slots stay usable until each chunk drains;
        allocation pressure demand-drains via ``_demand_free_pages``).
        Suffix pages whose *reload* is still in flight are cancelled
        instead: freeing them needs no copy, their bytes never left the
        host store (the eviction-of-a-loading-session rule)."""
        cancel_lis, offload_lis = self.pool.evictable_suffix(sid, blocks)
        assert len(cancel_lis) + len(offload_lis) == blocks, \
            f"accounting evicted {blocks} but only " \
            f"{len(cancel_lis) + len(offload_lis)} evictable ({sid})"
        if cancel_lis:
            dropped = self.transfer.cancel_reload_pages(sid, cancel_lis)
            assert dropped == len(cancel_lis), (sid, cancel_lis)
            self.pool.cancel_loading(sid, cancel_lis)
        if offload_lis:
            if self.prefix_cache is not None:
                # about to leave HBM: forget these pages (and their
                # unreachable subtrees) in the radix index first — the
                # never-offload-shared rule is then an assert, not a
                # hope (rc>1 pages were excluded by evictable_suffix)
                seq = self.pool.seq(sid)
                self._forget_cached([seq.pages[li] for li in offload_lis])
            self.pool.mark_offloading(sid, offload_lis)
            self.transfer.submit_offload(sid, offload_lis)
            if not self.async_transfers:
                self.transfer.drain(self.clock.now(),
                                    kinds=("offload",))
        self.offload_events.append((self.clock.now(), sid, blocks))
        self._sync_page_counts(sid)

    def _reload_pages(self, sid: str, blocks: int, *, background: bool,
                      transfer=None) -> None:
        """KVManager reload hook: queue the offloaded pages as chunked
        host->device transfers. In-flight offloads cancel for free
        (copy-then-free); slots for the rest are reserved now (the
        pool's ``loading`` marks), contents land as chunks drain — or
        at turn-start settlement for the on-path remainder."""
        cancelled = self.pool.cancel_offloading(sid)
        if cancelled:
            self.transfer.cancel_offload_pages(sid, cancelled)
        # reserving slots may need room the accounting freed but the
        # copy-then-free plane has not physically drained yet
        s = self.pool.seq(sid)
        need = sum(1 for li in s.offloaded if li not in s.loading)
        self._demand_free_pages(need)
        lis = self.pool.begin_reload(sid)
        assert len(lis) + len(cancelled) == blocks, \
            f"accounting reloaded {blocks} but pool restored " \
            f"{len(lis)} + cancelled {len(cancelled)} ({sid})"
        self.transfer.submit_reload(sid, lis, transfer)
        if not background or not self.async_transfers:
            # synchronous path: settle immediately; the preloader (or
            # direct kv.reload caller) reads the split via the ledger
            self.transfer.finish_session(sid, self.clock.now())
        self._sync_page_counts(sid)

    def _cancel_reload_pages(self, sid: str) -> int:
        """KVManager burst-cancel hook: drop the session's queued
        reload chunks, free their reserved slots (host copies stay
        authoritative). Returns pages cancelled."""
        dropped = self.transfer.cancel_reload_pages(sid)
        if dropped:
            lis = sorted(self.pool.seq(sid).loading)
            assert len(lis) == dropped, (sid, lis, dropped)
            self.pool.cancel_loading(sid, lis)
            self._sync_page_counts(sid)
        return dropped

    def _finish_transfers(self, sid: str, now: float):
        """KVManager settlement hook (turn start): complete the
        session's queued reload chunks; (on_path_s, off_path_s)."""
        self.transfer.finish_session(sid, now)
        return self.transfer.pop_split(sid)

    # ------------------------------------------------------ transfer io
    def _io_reload_chunk(self, sid: str, lis: List[int]) -> None:
        """Physically land one reload chunk. The host stack is staged
        to the device and *only that buffer* is blocked on for the
        wall-time measurement — blocking on the whole page store would
        over-synchronize unrelated decode work (ISSUE 4 satellite)."""
        with span("serve.kv.reload_chunk", sid=sid, pages=len(lis)):
            s = self.pool.seq(sid)
            host = np.stack([self.codec.decode(s.offloaded[li])
                             for li in lis])
            t0 = time.perf_counter()
            if self.layout is not None:
                staged = self.layout.stage_host_chunk(host)
            else:
                staged = jax.device_put(host, self.device)
            jax.block_until_ready(staged)
            self.reload_wall_s.append(time.perf_counter() - t0)
            store = self.pool.complete_reload(
                sid, lis, LayerStackedPages(self.k_pages, self.v_pages),
                staged=staged)
            self.k_pages, self.v_pages = store.k, store.v
            self._place_pages()
            self._sync_page_counts(sid)
        if self._settling:
            self.settled_reload_pages += len(lis)
        else:
            self.drained_reload_pages += len(lis)

    def _io_offload_chunk(self, sid: str, lis: List[int]) -> None:
        """Physically land one offload chunk: gather the device pages
        to host copies, then free the slots (copy-then-free step 2)."""
        with span("serve.kv.offload_chunk", sid=sid, pages=len(lis)):
            s = self.pool.seq(sid)
            phys = np.asarray([s.pages[li] for li in lis], np.int64)
            hk = np.asarray(self.k_pages[:, phys])  # [L, n, page, Hkv*hd]
            hv = np.asarray(self.v_pages[:, phys])
            self.pool.complete_offload(
                sid, {li: self.codec.encode(np.stack([hk[:, i], hv[:, i]]))
                      for i, li in enumerate(lis)})
            self._sync_page_counts(sid)

    def drain_transfers(self, max_chunks: Optional[int] = None) -> int:
        """Complete up to ``max_chunks`` queued transfer chunks (both
        directions, FIFO). run_round calls this with the per-round
        budget; the gateways call it from their idle loops so preloads
        progress even when nothing is decoding."""
        with span("serve.xfer.drain", chunks=max_chunks):
            return self.transfer.drain(self.clock.now(), max_chunks)

    def flush_transfers(self) -> int:
        """Drain everything (tests / shutdown)."""
        return self.transfer.drain(self.clock.now(), None)

    def _demand_free_pages(self, need: int) -> None:
        """Allocation needs physical slots the accounting already freed:
        complete queued offload chunks until the pool can satisfy it."""
        self.transfer.drain_offloads_until(
            self.clock.now(), lambda: self.pool.free_pages >= need)

    # ---------------------------------------------------- shared prefix
    # (DESIGN.md §13.) The radix cache holds NON-refcount references:
    # registering marks pages `cache_held` in the pool without touching
    # refcounts, so `sum(refcounts) == live block-table references`
    # stays the conservation invariant. Charging: every allocated page
    # bills exactly one accountant — its owner session (kv.hbm_blocks)
    # or, once the owner released/COW'd it away, the prefix cache
    # (kv.cached_blocks, pool.page_owner[p] is None).

    def _refresh_shared_pins(self) -> None:
        """Recompute every session's shared-pinned block count (own
        resident pages some other session references — never
        offloadable) after any refcount 1<->2+ transition. Sessions per
        engine are few; recomputing all of them keeps every call site
        trivially correct."""
        if self.prefix_cache is None:
            return
        for sid, kvs in self.kv.sessions.items():
            kvs.shared_pinned_blocks = self.pool.shared_charged_pages(sid)
        self.peak_shared_pages = max(self.peak_shared_pages,
                                     self.pool.shared_pages())

    def _attach_prefix(self, sess: PagedSession,
                       prompt: np.ndarray) -> np.ndarray:
        """Session birth: walk the radix index for the prompt's longest
        cached prefix and attach to it — the block table points at the
        shared pages, kv_len skips ahead, and prefill starts at the
        first uncached token (the fused kernel's per-row q_start
        renders from any offset; no kernel math changes). Returns the
        remaining (uncached) prompt."""
        if self.prefix_cache is None or sess.kv_len > 0:
            return prompt
        sid = sess.session_id
        matched, phys = self.prefix_cache.lookup(prompt)
        # the last prompt token always prefills: its logits are the
        # turn's first output token
        matched = min(matched, int(prompt.shape[0]) - 1)
        if matched <= 0:
            return prompt
        n_phys = self.pool.pages_for(matched)
        self.pool.attach_prefix(sid, phys[:n_phys], matched)
        sess.kv_len = matched
        sess.token_ids = [int(t) for t in prompt[:matched]]
        kvs = self.kv.session(sid)
        kvs.total_blocks = n_phys
        kvs.shared_blocks = n_phys      # charged to owners / the cache
        kvs.hbm_blocks = 0
        self._pending_hit[sid] = matched
        self.prefix_cache.hits += 1
        self.prefix_cache.hit_tokens += matched
        self._refresh_shared_pins()
        self._sync_page_counts(sid)
        return prompt[matched:]

    def _ensure_writable(self, sid: str) -> None:
        """Copy-on-write before a write lands: the next token's target
        page may be shared (an attached partial tail, or this session's
        own committed tail another session attached to). Allocate a
        private copy, repoint, copy the bytes. Only the FIRST page of a
        write region can be shared — everything past it is freshly
        allocated. Raises OutOfPages recoverably (same contract as
        _grow)."""
        if self.prefix_cache is None:
            return
        sess = self.sessions[sid]
        s = self.pool.seqs.get(sid)
        if s is None:
            return
        li = sess.kv_len // self.page_size
        if li >= len(s.pages):
            return
        phys = s.pages[li]
        if phys < 0 or self.pool.refcount[phys] <= 1:
            return
        now = self.clock.now()
        if not self.kv.try_allocate_working(1, now):
            raise OutOfPages(f"{sid}: no page free for copy-on-write")
        self._demand_free_pages(1)
        old, new, was_owner = self.pool.cow(sid, li)
        self.kv.release_working(1)
        kvs = self.kv.session(sid)
        if was_owner:
            # the old page stays for its sharers, now charged to the
            # cache; our new private copy replaces it 1:1 in hbm
            self.kv.cached_blocks += 1
        else:
            # an attached page became a private one
            kvs.shared_blocks -= 1
            kvs.hbm_blocks += 1
        self.k_pages = self.k_pages.at[:, new].set(self.k_pages[:, old])
        self.v_pages = self.v_pages.at[:, new].set(self.v_pages[:, old])
        self._place_pages()
        self.cow_copies += 1
        self._refresh_shared_pins()

    def _register_prefix(self, sid: str) -> None:
        """Turn close: index the session's committed chain — full pages
        as interior radix nodes, the partially-filled tail as this
        node's partial child. Newly indexed pages become `cache_held`
        (kept allocated even at refcount 0 until forgotten/reclaimed);
        charging is unchanged — this session still owns them."""
        sess = self.sessions[sid]
        s = self.pool.seqs.get(sid)
        if s is None or sess.kv_len <= 0:
            return
        assert len(sess.token_ids) == sess.kv_len, \
            f"{sid}: token history {len(sess.token_ids)} != " \
            f"kv_len {sess.kv_len}"
        now = self.clock.now()
        newly = self.prefix_cache.register(
            sess.token_ids, s.pages,
            est=self.kv.next_use_estimate(sid, now),
            protect=self.kv.session(sid).protected_until)
        self.pool.cache_held.update(newly)

    def _forget_cached(self, phys: List[int]) -> None:
        """Drop pages (and their now-unreachable radix subtrees) from
        the index before they offload/migrate; orphans whose last
        reference was the index free immediately."""
        dropped = self.prefix_cache.forget_phys(phys)
        self.kv.cached_blocks -= self.pool.cache_release(dropped)

    def _reclaim_cached(self, n: int, now: float) -> int:
        """KVManager cache hook: free up to n orphaned cached pages
        (leaves-first, farthest banked next-use first). Returns blocks
        freed; the manager adjusts cached_blocks."""
        phys = self.prefix_cache.reclaim(n, now, self.pool.refcount)
        freed = self.pool.cache_release(phys)
        assert freed == len(phys), (phys, freed)
        return freed

    def _cached_reclaimable(self, now: float) -> int:
        return self.prefix_cache.reclaimable(now, self.pool.refcount)

    def _bank_detach(self, sid: str, now: float) -> None:
        """A sharer is leaving (hangup or migration): bank its Eq.4
        next-use estimate and protection TTL on every indexed/shared
        page it references — reclaim order for the eventual orphans is
        min-over-sharers next-use (last detacher wins) with protection
        extended to the max over sharers' TTLs."""
        s = self.pool.seqs.get(sid)
        if s is None:
            return
        held = [p for p in s.pages
                if p >= 0 and (p in self.pool.cache_held
                               or self.pool.refcount[p] > 1)]
        if held:
            self.prefix_cache.on_detach(
                held, est=self.kv.next_use_estimate(sid, now),
                protect=self.kv.session(sid).protected_until)

    def _grow(self, sid: str, token_capacity: int, *,
              best_effort: bool = False) -> bool:
        """Own enough pages for token_capacity tokens; KVManager evicts
        idle sessions (physically, via the hook) when the pool is short."""
        token_capacity = min(token_capacity, self.max_context)
        need = self.pool.pages_for(token_capacity) \
            - len(self.pool.seq(sid).pages)
        if need <= 0:
            return True
        now = self.clock.now()
        if best_effort and (self.kv.free_blocks < need
                            or self.pool.free_pages
                            + self.transfer.pending_offload_pages()
                            < need):
            return False
        if not self.kv.try_allocate_working(need, now):
            raise OutOfPages(
                f"{sid}: need {need} pages, {self.kv.free_blocks} free "
                "and nothing evictable")
        # accounting freed the blocks; copy-then-free may still hold the
        # physical slots until its chunks drain — demand them now
        self._demand_free_pages(need)
        if best_effort and self.pool.free_pages < need:
            self.kv.release_working(need)     # undo the allocation above
            return False
        self.pool.ensure_capacity(sid, token_capacity)
        return True

    # ------------------------------------------------------------ admit
    def free_slot(self) -> Optional[int]:
        for i, s in self.slot_state.items():
            if s is None:
                return i
        return None

    def add_session(self, session_id: str, prompt: np.ndarray,
                    max_new_tokens: int) -> int:
        """Turn 0, synchronous path: prefill the prompt into pool pages
        before returning; returns slot id."""
        sess = self._prep_first_turn(session_id)
        prompt = self._attach_prefix(sess, np.asarray(prompt, np.int32))
        return self._begin_turn(sess, prompt, max_new_tokens, first=True)

    def start_turn(self, session_id: str, prompt: np.ndarray,
                   max_new_tokens: int) -> int:
        """A later turn reaches the LLM stage (synchronous path): reload
        whatever KV is still offloaded (warm no-op on a preload hit),
        then extend the paged context with the new prompt — the
        committed history is never re-prefilled."""
        sess = self._prep_next_turn(session_id)
        return self._begin_turn(sess, np.asarray(prompt, np.int32),
                                max_new_tokens, first=False)

    def submit_turn(self, session_id: str, prompt: np.ndarray,
                    max_new_tokens: int, *,
                    request: Optional[Request] = None) -> int:
        """Scheduler-drivable turn admission (DESIGN.md §4): bind a free
        slot and run the reload path, but leave the request in PREFILL —
        prompt tokens are teacher-forced through the shared fixed-batch
        step as ``run_round`` chunks grant them (chunked paged prefill
        that interleaves with other sessions' decode), and the first
        output token appears the round the last prompt token is fed.
        A pre-built ``request`` lets the control plane rank the turn
        while it was still queued (its arrival_time is the instant the
        utterance reached the gateway, preserving queue wait in TTFP).
        Works for turn 0 and later turns alike."""
        sess = self.sessions.get(session_id)
        with span("serve.admit", sid=session_id,
                  turn=sess.turn_index + 1 if sess else 0):
            prompt = np.asarray(prompt, np.int32)
            if sess is None:
                sess = self._prep_first_turn(session_id)
                prompt = self._attach_prefix(sess, prompt)
            else:
                sess = self._prep_next_turn(session_id)
            if request is not None:
                sess.turn_arrival = min(sess.turn_arrival,
                                        request.arrival_time)
            slot = self.free_slot()
            assert slot is not None, "no free decode slot"
            req = self._make_request(sess, prompt, max_new_tokens,
                                     request=request)
            self.slot_state[slot] = PagedSlot(session_id, req, -1, [],
                                              prompt=prompt)
            self._sync_page_counts(session_id)
            return slot

    def _prep_first_turn(self, session_id: str) -> PagedSession:
        assert session_id not in self.sessions, \
            "session exists — use start_turn/submit_turn for later turns"
        self.monitor.register(session_id)
        self.monitor.on_turn_start(session_id, 0)
        sess = PagedSession(session_id)
        self.sessions[session_id] = sess
        sess.turn_arrival = self.clock.now()
        sess.reload_stall_s = 0.0
        sess.reload_off_path_s = 0.0
        return sess

    def _prep_next_turn(self, session_id: str) -> PagedSession:
        sess = self.sessions[session_id]
        assert not sess.ended, f"{session_id} ended; KV pages are gone"
        # reload FIRST, before any turn bookkeeping mutates: on a
        # saturated pool (every other session pinned or speech-protected)
        # the sync-fallback reload can fail to fit, and that must surface
        # as recoverable pressure the control plane can retry — not as a
        # half-started turn. Pin before the reload path: its eviction
        # pass must never pick the session being brought back as its own
        # victim.
        self.kv.pin(session_id)
        settled = self.settled_reload_pages
        t0 = time.perf_counter()
        with span("serve.kv.settle", sid=session_id,
                  pages=self.kv.missing_blocks(session_id)):
            self._settling = True
            try:
                stall = self.preloader.on_turn_ready(session_id,
                                                     self.clock.now())
            finally:
                self._settling = False
        settle_s = time.perf_counter() - t0
        # the accounting view (dram blocks), not the host-copy dict, is
        # the guard: under copy-then-free a saturated-pool session can
        # have its suffix still *offloading* (chunks queued, `offloaded`
        # empty) — starting its turn anyway would let a later round's
        # FIFO drain move the pages to DRAM mid-decode and crash the
        # block-table build instead of requeueing recoverably
        if self.kv.missing_blocks(session_id) > 0:
            self.kv.session(session_id).pinned = False
            # the settlement that just ran stalled nothing (this turn is
            # requeued): its seconds carry forward as off-path credit
            # and its pages reclassify, so the overlap accounting never
            # drops already-done reload work on a requeue
            self.preloader.requeue_split(session_id)
            self.transfer.requeue_settlement(session_id)
            raise OutOfPages(
                f"{session_id}: pool too saturated to restore "
                f"{self.kv.missing_blocks(session_id)} non-resident "
                "blocks; keep the turn queued and retry")
        self.transfer.settlement_committed(session_id)
        assert self.pool.inflight_pages(session_id) == (0, 0) \
            and not self.pool.seq(session_id).offloaded, \
            f"{session_id}: turn starting with pages still in flight"
        sess.turn_index += 1
        # the utterance is over once its turn reaches the LLM stage —
        # clear `speaking` or the session stays immediate_reuse forever
        # and its idle KV becomes permanently unevictable
        self.monitor.on_speech_end(session_id)
        self.monitor.on_turn_start(session_id, sess.turn_index)
        sess.turn_arrival = self.clock.now()
        if stall > 0 and not getattr(self.clock, "self_advancing", False):
            # on-path sync reload residual, on a clock that only moves
            # when charged; a wall clock already spent the settlement
            self.clock.tick(stall)
        sess.reload_stall_s = stall
        sess.reload_on_path_wall_s = (
            settle_s if self.settled_reload_pages > settled else 0.0)
        _, sess.reload_off_path_s = self.preloader.pop_split(session_id)
        return sess

    def _make_request(self, sess: PagedSession, prompt: np.ndarray,
                      max_new_tokens: int, *,
                      request: Optional[Request] = None) -> Request:
        sid = sess.session_id
        P = int(prompt.shape[0])
        assert sess.kv_len + P + max_new_tokens <= self.max_context, \
            f"{sid}: turn would exceed pages_per_seq*page_size context"
        self.kv.pin(sid)
        sess.base_pages = len(self.pool.seq(sid).pages)
        re_prefill = self.kv.recompute_tokens(sid)
        if request is None:
            req = Request(session_id=sid, stage="thinker",
                          turn_index=sess.turn_index,
                          arrival_time=sess.turn_arrival, prompt_len=P,
                          context_len=sess.kv_len,
                          max_new_tokens=max_new_tokens)
        else:
            req = request
            req.turn_index = sess.turn_index
            req.prompt_len = P
            req.context_len = sess.kv_len
            req.max_new_tokens = max_new_tokens
        req.reload_stall_s = sess.reload_stall_s
        req.reload_off_path_s = sess.reload_off_path_s
        req.reload_on_path_wall_s = sess.reload_on_path_wall_s
        req.prefix_hit_tokens = self._pending_hit.pop(sid, 0)
        sess.turn_stats.append({
            "turn": sess.turn_index,
            "context_tokens": req.context_len,
            "prompt_tokens": P,
            "ttft_s": None,                 # set at first output token
            "reload_stall_s": sess.reload_stall_s,
            "reload_off_path_s": sess.reload_off_path_s,
            "re_prefill_tokens": re_prefill,
            "prefix_hit_tokens": req.prefix_hit_tokens,
            "generated": 0,
            "aborted": False,
        })
        return req

    def _begin_turn(self, sess: PagedSession, prompt: np.ndarray,
                    max_new_tokens: int, *, first: bool) -> int:
        sid = sess.session_id
        slot = self.free_slot()
        assert slot is not None, "no free decode slot"
        req = self._make_request(sess, prompt, max_new_tokens)
        self._grow(sid, sess.kv_len + req.prompt_len)
        self._ensure_writable(sid)
        if self.fused_step:
            # turn 0 (the former dense-prefill graft) and turn-N
            # extension share the one fused path (DESIGN.md §11)
            tok = self._prefill_fused(slot, sess, prompt)
        elif first and sess.kv_len == 0:
            # the dense graft writes whole pages from position 0 — only
            # valid when nothing (no attached prefix) precedes it
            tok = self._prefill_dense(sess, prompt)
        else:
            tok = self._prefill_paged(slot, sess, prompt)
        req.phase = Phase.DECODE
        req.prefilled = req.prompt_len
        req.first_output_time = self.clock.now()
        self.slot_state[slot] = PagedSlot(sid, req, tok, [tok])
        sess.turn_stats[-1]["ttft_s"] = self.clock.now() - sess.turn_arrival
        self._sync_page_counts(sid)
        return slot

    def _prefill_dense(self, sess: PagedSession, prompt: np.ndarray) -> int:
        """Turn-0 fast path: one dense B=1 prefill, grafted into the
        session's pool pages (page-aligned scatter)."""
        sid = sess.session_id
        P = int(prompt.shape[0])
        npages = self.pool.pages_for(P)
        cap = npages * self.page_size
        c1 = init_cache(self.cfg, 1, cap)
        logits, c1 = prefill(self.cfg, self.params,
                             jnp.asarray(prompt, jnp.int32)[None, :], c1)
        phys = np.asarray(self.pool.seq(sid).pages[:npages], np.int64)
        kl = c1["k"][:, 0].reshape(self.cfg.num_layers, npages,
                                   self.page_size, -1)
        vl = c1["v"][:, 0].reshape(kl.shape)
        self.k_pages = self.k_pages.at[:, phys].set(kl)
        self.v_pages = self.v_pages.at[:, phys].set(vl)
        self._place_pages()
        sess.kv_len = P
        sess.token_ids = [int(t) for t in prompt]
        self.clock.tick()
        return int(jnp.argmax(logits[0]))

    def _prefill_fused(self, slot: int, sess: PagedSession,
                       prompt: np.ndarray) -> int:
        """Synchronous prefill on the fused plane: the whole prompt is
        one multi-token launch — turn 0 lands in fresh pages, turn N
        extends the committed context (never re-prefilled) — and the
        last token's logits are the first output token."""
        logits = self._run_chunk_rows(
            {slot: (sess.session_id,
                    np.asarray(prompt, np.int32))})[0][slot]
        sess.kv_len += int(prompt.shape[0])
        sess.token_ids += [int(t) for t in prompt]
        self.clock.tick()
        return int(np.argmax(logits))

    def _prefill_paged(self, slot: int, sess: PagedSession,
                       prompt: np.ndarray) -> int:
        """Turn-N extension on the per-token plane (``fused_step=False``
        differential control): teacher-force the new prompt through the
        paged step so its KV lands behind the committed context — no
        re-prefill of history.

        Like the dense engine's add_session, this runs synchronously:
        concurrent decode holds for prompt_len rounds (turn prompts are
        short utterance transcripts); the fused plane collapses this to
        one launch (DESIGN.md §11)."""
        logits = None
        for t in prompt:
            logits = self._run_rows({slot: (sess.session_id, int(t))})[slot]
            sess.kv_len += 1
            sess.token_ids.append(int(t))
            self.clock.tick()
        return int(np.argmax(logits))

    # ------------------------------------------------------------ speech
    def user_speech_start(self, session_id: str,
                          expected_dur_s: Optional[float] = None):
        """VAD speech-start: update telemetry and fire the speech-time
        preload (§5.2) — admitted preloads physically reload pages via
        the KVManager hook while the user is still speaking."""
        self.monitor.on_speech_start(session_id, expected_dur_s)
        return self.preloader.on_speech_start(session_id, self.clock.now())

    def barge_in(self, session_id: str,
                 expected_dur_s: Optional[float] = None):
        """User interrupts playback: abort the in-flight turn (keeping
        committed pages) and treat the interruption as speech start."""
        self.abort(session_id)
        if expected_dur_s is not None:
            self.monitor.register(session_id).expected_speech_end = \
                self.clock.now() + expected_dur_s
        return self.preloader.on_speech_start(session_id, self.clock.now())

    def tool_call_start(self, session_id: str,
                        expected_latency_s: float = 0.0) -> None:
        """The turn's reply ended in a tool invocation: the session goes
        idle mid-conversation with hot KV. Protect it under the
        tool-pause TTL and point Eq. 4 next-use at the tool's expected
        return instead of the reply-gap EMA."""
        now = self.clock.now()
        self.monitor.on_tool_call_start(session_id, expected_latency_s)
        self.kv.protect_tool(session_id, now, expected_latency_s)
        self.kv.refresh_session(session_id, now)

    def tool_call_result(self, session_id: str,
                         resume_gap_s: float = 0.0):
        """The tool returned; the resume turn arrives in ~resume_gap_s.
        Lift the tool-pause protection and fire the ordinary speech-time
        preload machinery over the gap, so a session whose pages were
        evicted anyway (TTL lapse, pool pressure) reloads off-path and
        resumes without re-prefill."""
        now = self.clock.now()
        self.monitor.on_tool_call_result(session_id, resume_gap_s)
        self.kv.clear_tool_protection(session_id, now)
        return self.preloader.on_speech_start(session_id, now)

    def end_session(self, session_id: str) -> None:
        """User hung up: free the session's pages (HBM and DRAM copies)
        and its accounting. History/turn stats stay readable."""
        assert all(s is None or s.session_id != session_id
                   for s in self.slot_state.values()), \
            "abort the live turn before ending the session"
        # drop queued transfer chunks first: release() frees the slots
        # (including loading reservations) and the host copies, so a
        # hangup mid-transfer leaks nothing
        self.transfer.cancel_session(session_id)
        self.preloader.forget_session(session_id)
        if self.prefix_cache is not None:
            self._bank_detach(session_id, self.clock.now())
        rep = self.pool.release(session_id)
        if self.prefix_cache is not None:
            # own pages surviving via sharers/the index re-charge to
            # the cache; cache-charged pages whose last reference died
            # here freed with the release
            self.kv.cached_blocks += rep["orphaned"]
            self.kv.cached_blocks -= rep["freed_orphan"]
        self.kv.release_session(session_id)
        if self.prefix_cache is not None:
            self._refresh_shared_pins()
        self.sessions[session_id].ended = True
        self.monitor.on_page_movement(session_id, resident=0, offloaded=0)

    def abort(self, session_id: str) -> None:
        """Barge-in: drop the in-flight request. Committed pages (context
        + tokens already written) stay owned; in-flight lookahead pages
        are trimmed back to the pool."""
        for i, s in self.slot_state.items():
            if s is None or s.session_id != session_id:
                continue
            s.request.state = RequestState.ABORTED
            self.monitor.on_barge_in(session_id)
            self._close_turn(i, aborted=True)

    # --------------------------------------------------------- migration
    # Cross-replica migration (serving/fleet, DESIGN.md §12) rides the
    # same chunked ledger as eviction/preload: the source queues its
    # whole committed context as MIGRATE-tagged copy-then-free offload
    # chunks (drained across rounds while the user is still speaking),
    # and once everything is host-resident the session state transplants
    # wholesale to the destination engine, which pages it back in with
    # the ordinary speech-time preload machinery — so the on-path vs
    # off-path split of migration bytes needs no new accounting.

    def migrate_out_begin(self, session_id: str) -> int:
        """Queue the session's entire device-resident KV for host
        offload (copy-then-free, MIGRATE-tagged). The session must be
        idle (no live slot); any in-flight speech-time preload is
        cancelled first — its pages are about to leave this replica.
        Returns the number of pages queued for offload now (pages
        already offloaded or already offloading are not re-queued)."""
        sid = session_id
        sess = self.sessions[sid]
        assert not sess.ended, f"{sid} ended; nothing to migrate"
        assert all(s is None or s.session_id != sid
                   for s in self.slot_state.values()), \
            f"{sid}: migration requires an idle session (no live slot)"
        now = self.clock.now()
        self.preloader.cancel(sid, now)
        self.kv.cancel_reload(sid, now)
        s = self.pool.seq(sid)
        kvs = self.kv.session(sid)
        deep_copied = 0
        if self.prefix_cache is not None:
            # Shared pages cannot ride the copy-then-free ledger (their
            # slot must NOT free — sharers still need it hot). Private
            # pages the index holds are forgotten (plain again); truly
            # shared pages deep-copy to host synchronously and the
            # departing session drops its reference — the destination
            # re-resolves against its own radix index on later turns.
            self._forget_cached(
                [p for p in s.pages
                 if p >= 0 and self.pool.refcount[p] == 1
                 and p in self.pool.cache_held
                 and self.pool.page_owner[p] == sid])
            shared_lis = [li for li, p in enumerate(s.pages)
                          if p >= 0 and (self.pool.refcount[p] > 1
                                         or self.pool.page_owner[p] != sid)]
            if shared_lis:
                self._bank_detach(sid, now)
                for li in shared_lis:
                    phys = s.pages[li]
                    hk = np.asarray(self.k_pages[:, phys])
                    hv = np.asarray(self.v_pages[:, phys])
                    was_owner, freed = self.pool.detach_page(sid, li)
                    s.offloaded[li] = self.codec.encode(
                        np.stack([hk, hv]))
                    if was_owner:
                        # stays for its sharers, cache-charged now
                        kvs.hbm_blocks -= 1
                        self.kv.cached_blocks += 1
                    else:
                        kvs.shared_blocks -= 1
                        if freed:
                            # last reference to an orphan: the cache
                            # was paying and the slot just freed
                            self.kv.cached_blocks -= 1
                deep_copied = len(shared_lis)
                self._refresh_shared_pins()
                self._sync_page_counts(sid)
        lis = [li for li, p in enumerate(s.pages)
               if p >= 0 and li not in s.loading and li not in s.offloading]
        assert not s.loading and kvs.hbm_blocks == len(lis), \
            f"{sid}: accounting ({kvs.hbm_blocks}) disagrees with " \
            f"resident pages ({len(lis)}) at migrate-out"
        if lis:
            self.pool.mark_offloading(sid, lis)
            self.transfer.submit_offload(sid, lis, tag=MIGRATE)
            # accounting mirrors the eviction hook: the blocks leave
            # this replica's HBM budget now; copy-then-free keeps the
            # physical slots (and their usability) until chunks drain
            kvs.hbm_blocks = 0
            self._sync_page_counts(sid)
        return len(lis) + deep_copied

    def migrate_out_pending(self, session_id: str) -> int:
        """Pages still queued on the source's offload ledger."""
        return self.transfer.pending_offload_pages(session_id)

    def migrate_out_cancel(self, session_id: str) -> int:
        """Abandon a not-yet-handed-off migration zero-copy: queued
        offload chunks drop from the ledger and their pages stay
        resident (the bytes never left HBM — the same copy-then-free
        win as a reload racing an eviction). Pages whose chunks already
        drained stay host-resident; the session's next turn on *this*
        replica reloads them through the normal path. Returns pages
        restored to resident."""
        sid = session_id
        dropped = self.transfer.cancel_offload_pages(sid)
        restored = self.pool.cancel_offloading(sid)
        assert len(restored) == dropped, (sid, restored, dropped)
        if dropped:
            self.kv.session(sid).hbm_blocks += dropped
            self._sync_page_counts(sid)
        return dropped

    def migrate_out_finalize(self, session_id: str) -> dict:
        """Every page is host-resident: detach the session wholesale —
        PagedSession (history, turn stats), monitor view (reply-gap
        EMA, expected speech end), host page copies — and scrub every
        source-side table, exactly like a hangup except the state moves
        instead of dying. The returned dict feeds ``migrate_in_adopt``
        on the destination engine."""
        sid = session_id
        s = self.pool.seqs[sid]
        assert self.transfer.pending_offload_pages(sid) == 0 \
            and not s.offloading and not s.loading \
            and all(p == -1 for p in s.pages), \
            f"{sid}: migrate-out finalize before all chunks drained"
        state = {
            "session": self.sessions.pop(sid),
            "view": self.monitor.forget(sid),
            "n_pages": len(s.pages),
            "length": s.length,
            "host": dict(s.offloaded),
        }
        self.transfer.cancel_session(sid)     # clears split accumulators
        self.preloader.forget_session(sid)
        self.pool.release(sid)
        self.kv.release_session(sid)
        return state

    def migrate_in_adopt(self, session_id: str, state: dict) -> None:
        """Install a migrated session: pool entry fully host-resident
        (``pages[li] == -1`` everywhere), KV accounting with zero HBM
        blocks, interaction view transplanted so Eq. 4 and the preload
        window keep their learned state. The caller then fires
        ``preloader.on_speech_start`` so the page-in rides the remaining
        speech window like any preload (admission-checked, chunked,
        cancellable, OutOfPages-recoverable at turn start)."""
        sid = session_id
        assert sid not in self.sessions, f"{sid} already on this replica"
        self.sessions[sid] = state["session"]
        if state["view"] is not None:
            self.monitor.adopt(sid, state["view"])
        else:
            self.monitor.register(sid)
        self.pool.adopt(sid, state["n_pages"], state["length"],
                        state["host"])
        kvs = self.kv.session(sid)
        kvs.total_blocks = state["n_pages"]
        kvs.hbm_blocks = 0
        kvs.last_access = self.clock.now()
        self._sync_page_counts(sid)

    # ------------------------------------------------------------ rounds
    def active(self) -> List[PagedSlot]:
        return [s for s in self.slot_state.values()
                if s is not None and s.request.is_live()
                and s.request.generated < s.request.max_new_tokens]

    def step(self) -> List[int]:
        """One self-scheduled round: the engine's own scheduler picks the
        slots *and their token grants* (``chunk_for`` — a PREFILL slot
        gets its prefill chunk, a decode slot one token), then one
        fixed-batch paged round. Returns scheduled slot ids. (The
        gateway bypasses this and calls ``run_round`` with its own
        scheduler's decision — DESIGN.md §4.)"""
        self.clock.tick()
        act = self.active()
        if not act:
            return []
        sched_slots, grants = schedule_round(
            self.scheduler, self.kv, self.clock, self.slot_state, act,
            self.slots * (1 + self.spec_decode),
            block_size=self.page_size)
        if not sched_slots:
            return []
        self.run_round(grants)
        return sched_slots

    def run_round(self, chunks: Dict[int, int]) -> Dict[int, List[tuple]]:
        """Execute one already-scheduled round: ``chunks[slot]`` is the
        token budget the control plane granted that slot this round.
        A decode slot advances one token; a PREFILL slot (submit_turn)
        teacher-forces up to its chunk of prompt tokens.

        On the fused plane (``fused_step=True``, the default) the whole
        round — every slot's grant, C-token prefill chunks included —
        packs into **one jitted launch** (DESIGN.md §11): each slot's
        chunk KV is scattered in one paged write and every query token
        attends causally over history + chunk prefix. With
        ``fused_step=False`` chunks > 1 run as sequential single-token
        sub-batches in which every other granted slot participates only
        once — the per-token differential control.

        Returns per-slot event lists for the caller to stream out:
        ``("prefill", n_prefilled)``, ``("token", tok)`` (playable output
        token, the first of which marks TTFT), ``("finished", n_tokens)``.
        Safe to interleave with ``abort``/``submit_turn`` between calls
        (asyncio single-thread discipline: never called concurrently).

        Around the launch (between decode sub-batches on the per-token
        plane) the round drains up to ``transfer_chunks_per_round``
        queued transfer chunks — this is where a speech-time preload
        physically lands while other sessions keep decoding
        (DESIGN.md §10)."""
        with span("serve.round", slots=len(chunks),
                  tokens=sum(chunks.values())):
            if self.fused_step:
                return self._run_round_fused(chunks)
            return self._run_round_tokenwise(chunks)

    def _round_feeds(self, chunks: Dict[int, int]) -> Dict[int, tuple]:
        """The round's grants as token arrays: ``{slot: (sid, tokens)}``
        — a PREFILL slot's next chunk of prompt tokens, one pending
        token for a decode slot — growing each sequence once for its
        whole grant (plus one best-effort lookahead page). A slot whose
        mandatory growth hits pool pressure is held for the round
        (``pressure_holds``): it retries next round when pressure
        drains; scheduling moves WHEN tokens appear, never WHICH
        (§5.2), so holding is safe."""
        feeds: Dict[int, tuple] = {}
        for i, c in chunks.items():
            s = self.slot_state[i]
            if s is None or not s.request.is_live():
                continue
            r = s.request
            if r.phase == Phase.PREFILL:
                n = min(c, r.prompt_len - r.prefilled)
                if n > 0:
                    feeds[i] = (s.session_id,
                                np.asarray(s.prompt[r.prefilled:
                                                    r.prefilled + n],
                                           np.int32))
            elif c > 0 and r.generated < r.max_new_tokens:
                # a zero grant is "not scheduled this round" on both
                # planes — the planes' bit-exactness contract covers
                # every run_round input, not just scheduler outputs
                toks = [s.pending_token]
                if self.spec_decode > 0:
                    # the grant is an "up to 1+K" draft budget: the
                    # proposer fills as much of it as it can guess,
                    # capped so accepted tokens can never overshoot the
                    # turn's generation cap (frontier/cap accounting
                    # counts accepted tokens only — §16)
                    m = min(self.spec_decode, c - 1,
                            r.max_new_tokens - r.generated - 1)
                    if m > 0:
                        if hasattr(self.proposer, "session_id"):
                            self.proposer.session_id = s.session_id
                        hist = self.sessions[s.session_id].token_ids \
                            + [s.pending_token]
                        toks += [int(t) for t in
                                 self.proposer.propose(hist, m)[:m]]
                feeds[i] = (s.session_id, np.asarray(toks, np.int32))
        for i in list(feeds):
            sid, toks = feeds[i]
            sess = self.sessions[sid]
            try:
                self._grow(sid, sess.kv_len + len(toks))
                self._ensure_writable(sid)   # COW a shared write target
            except OutOfPages:
                # allocation failure mid-round: admission accounted
                # blocks that interaction events (speech protection, a
                # barge-in trim re-pinning pressure elsewhere) made
                # unreclaimable by the time this round allocates.
                del feeds[i]
                self.pressure_holds += 1
                continue
            # best-effort lookahead, hoisted to once per slot per round
            # (ISSUE 5 satellite): own the page past the whole grant
            # before any write crosses into it, so boundary tokens never
            # wait on allocation/eviction (these are the in-flight pages
            # a barge-in trims)
            self._grow(sid, sess.kv_len + len(toks) + self.page_size,
                       best_effort=True)
        return feeds

    def _run_round_fused(self, chunks: Dict[int, int]) \
            -> Dict[int, List[tuple]]:
        """One round = one launch: pack every grant into a padded
        [slots, Q] token batch and advance all of it in a single jitted
        fused step."""
        events: Dict[int, List[tuple]] = {i: [] for i in chunks}
        xfer_budget = self.transfer_chunks_per_round
        if xfer_budget > 0:
            xfer_budget -= self.drain_transfers(1)
        with span("serve.round.feeds"):
            feeds = self._round_feeds(chunks)
        out, outs = self._run_chunk_rows(feeds) if feeds else (None, None)
        with span("serve.round.sample"):
            for i, (sid, toks) in feeds.items():
                s = self.slot_state[i]
                sess = self.sessions[sid]
                n = len(toks)
                r = s.request
                if r.phase == Phase.PREFILL:
                    sess.kv_len += n
                    sess.token_ids += [int(t) for t in toks]
                    tok = int(np.argmax(out[i]))
                    r.prefilled += n
                    # same event stream as the per-token plane: one
                    # progress event per intermediate prompt token, and
                    # the chunk's last logits become the first output
                    # token iff the prompt completed this round
                    events[i] += [("prefill", r.prefilled - n + 1 + t)
                                  for t in range(n - (1 if r.done_prefill
                                                     else 0))]
                    if r.done_prefill:
                        r.phase = Phase.DECODE
                        r.first_output_time = self.clock.now()
                        s.pending_token = tok
                        s.tokens.append(tok)
                        sess.turn_stats[-1]["ttft_s"] = \
                            self.clock.now() - sess.turn_arrival
                        events[i].append(("token", tok))
                    continue
                # decode: without speculation the row fed exactly
                # [pending] and emits its one argmax; with it the row
                # fed [pending, d_1..d_m] and the verify launch's
                # per-position argmaxes accept the longest matching
                # draft prefix (the committed stream is exactly the
                # greedy one — §16)
                if outs is None:
                    accepted, emit = 0, [int(np.argmax(out[i]))]
                else:
                    row = outs[i]
                    accepted = 0
                    while accepted < n - 1 \
                            and int(toks[accepted + 1]) \
                            == int(row[accepted]):
                        accepted += 1
                    emit = [int(row[j]) for j in range(accepted + 1)]
                    if n > 1:
                        self.spec_rounds += 1
                        self.spec_drafted += n - 1
                        self.spec_accepted += accepted
                        self.spec_rejected += (n - 1) - accepted
                # commit pending + accepted drafts; rejected KV rolls
                # back (length clamp — pages stay owned, the garbage
                # slots are never attended and are overwritten before
                # any future attend; _close_turn's trim reclaims)
                sess.kv_len += 1 + accepted
                sess.token_ids += [int(t) for t in toks[:1 + accepted]]
                if 1 + accepted < n:
                    self.pool.rollback(sid, sess.kv_len)
                for tok in emit:
                    r.generated += 1
                    s.pending_token = tok
                    if r.generated < r.max_new_tokens:
                        s.tokens.append(tok)
                        events[i].append(("token", tok))
                    else:
                        r.state = RequestState.FINISHED
                        self._close_turn(i, aborted=False)
                        events[i].append(("finished", r.generated))
                        break
        if xfer_budget > 0:
            self.drain_transfers(xfer_budget)
        return events

    def _run_round_tokenwise(self, chunks: Dict[int, int]) \
            -> Dict[int, List[tuple]]:
        """The per-token plane (``fused_step=False``): chunks > 1 run as
        sequential single-token sub-batches — the differential control
        the fused plane is bit-exactness-tested against."""
        events: Dict[int, List[tuple]] = {i: [] for i in chunks}
        xfer_budget = self.transfer_chunks_per_round
        lookahead_done = set()
        for j in range(max(chunks.values(), default=0)):
            if xfer_budget > 0:
                xfer_budget -= self.drain_transfers(1)
            with span("serve.round.feeds"):
                feeds = {}
                for i, c in chunks.items():
                    s = self.slot_state[i]
                    if s is None or not s.request.is_live():
                        continue
                    r = s.request
                    if r.phase == Phase.PREFILL:
                        if j < c and r.prefilled < r.prompt_len:
                            feeds[i] = (s.session_id,
                                        int(s.prompt[r.prefilled]))
                    elif j == 0 and c > 0 \
                            and r.generated < r.max_new_tokens:
                        feeds[i] = (s.session_id, s.pending_token)
                if not feeds:
                    break
                for i in list(feeds):
                    s = self.slot_state[i]
                    sess = self.sessions[s.session_id]
                    try:
                        self._grow(s.session_id, sess.kv_len + 1)
                        self._ensure_writable(s.session_id)
                    except OutOfPages:
                        # mid-chunk allocation failure: admission accounted
                        # blocks that interaction events (speech protection,
                        # a barge-in trim re-pinning pressure elsewhere)
                        # made unreclaimable by the time this sub-batch
                        # allocates. Hold the slot — it retries next round
                        # when pressure drains; scheduling moves WHEN tokens
                        # appear, never WHICH (§5.2), so holding is safe.
                        del feeds[i]
                        self.pressure_holds += 1
                        continue
                    # best-effort lookahead, hoisted to once per slot per
                    # round: cover the slot's remaining
                    # grant plus the page past it, so the boundary token
                    # never waits on allocation/eviction (these are the
                    # in-flight pages a barge-in trims)
                    if i not in lookahead_done:
                        lookahead_done.add(i)
                        r = s.request
                        rest = min(chunks[i] - j,
                                   r.prompt_len - r.prefilled) \
                            if r.phase == Phase.PREFILL else 1
                        self._grow(s.session_id,
                                   sess.kv_len + rest + self.page_size,
                                   best_effort=True)
            if not feeds:
                continue                     # everything held this round
            out = self._run_rows(feeds)
            with span("serve.round.sample"):
                for i in feeds:
                    s = self.slot_state[i]
                    sess = self.sessions[s.session_id]
                    sess.kv_len += 1
                    sess.token_ids.append(int(feeds[i][1]))
                    r = s.request
                    tok = int(np.argmax(out[i]))
                    if r.phase == Phase.PREFILL:
                        r.prefilled += 1
                        if r.done_prefill:
                            # the last prompt token's logits are the first
                            # output token — same contract as the sync paths
                            r.phase = Phase.DECODE
                            r.first_output_time = self.clock.now()
                            s.pending_token = tok
                            s.tokens.append(tok)
                            sess.turn_stats[-1]["ttft_s"] = \
                                self.clock.now() - sess.turn_arrival
                            events[i].append(("token", tok))
                        else:
                            events[i].append(("prefill", r.prefilled))
                    else:
                        r.generated += 1
                        s.pending_token = tok
                        if r.generated < r.max_new_tokens:
                            s.tokens.append(tok)
                            events[i].append(("token", tok))
                        else:
                            r.state = RequestState.FINISHED
                            self._close_turn(i, aborted=False)
                            events[i].append(("finished", r.generated))
        if xfer_budget > 0:
            self.drain_transfers(xfer_budget)
        return events

    def _count_attn_blocks(self, q_start, q_lens) -> None:
        """Add one launch's attention blocks, from the kernel's own
        ``q_start``/``q_lens`` rows (a decode launch's padding rows
        attend over one slot, so each counts one block)."""
        nblk = -(-self.max_context // self._attn_block)
        self.attn_blocks_live += int(live_blocks(
            q_start, q_lens, block_span=self._attn_block, nblk=nblk).sum())
        self.attn_blocks_grid += self.slots * nblk

    def _fetch(self, logits, counts: list) -> np.ndarray:
        """The launch's logits on the host; an MoE step's routing counts
        come in the same copy and are added to the engine's counters."""
        if not counts:
            return np.asarray(logits)
        logits, (routed, held, touched) = jax.device_get(
            (logits, counts[0]))
        self.moe_routed_pairs += int(routed)
        self.moe_held_pairs += int(held)
        self.moe_experts_touched += int(touched)
        return logits

    def _run_rows(self, feeds: Dict[int, tuple]) -> Dict[int, np.ndarray]:
        """Run one compiled step with `feeds[row] = (sid, token)`; other
        rows are padded to the scratch page. Returns per-row logits."""
        with span("serve.round.tables"):
            rows: List[Optional[tuple]] = [None] * self.slots
            tokens = np.zeros((self.slots,), np.int32)
            for i, (sid, tok) in feeds.items():
                rows[i] = (sid, self.sessions[sid].kv_len)
                tokens[i] = tok
            tabs: BatchTables = assemble(self.pool, rows,
                                         self.pages_per_seq,
                                         self.scratch_page)
            args = (self.params, jnp.asarray(tokens),
                    jnp.asarray(tabs.positions), self.k_pages,
                    self.v_pages, jnp.asarray(tabs.block_tables),
                    jnp.asarray(tabs.seq_lens),
                    jnp.asarray(tabs.write_page),
                    jnp.asarray(tabs.write_slot))
        with span("serve.round.launch", B=self.slots, Q=1,
                  valid=len(feeds)):
            logits, self.k_pages, self.v_pages, *counts = \
                self._step_fn(*args)
        self.launch_valid_tokens += len(feeds)
        self.launch_grid_tokens += self.slots
        self._count_attn_blocks(tabs.seq_lens - 1,
                                np.ones_like(tabs.seq_lens))
        with span("serve.round.logits"):
            logits = self._fetch(logits, counts)
        if self.logit_tap is not None:
            for i, (sid, _) in feeds.items():
                self.logit_tap(sid, logits[i])
        return {i: logits[i] for i in feeds}

    def _run_chunk_rows(self, feeds: Dict[int, tuple]) -> tuple:
        """Run one fused step with ``feeds[row] = (sid, tokens)`` —
        up to Q consecutive tokens per row, padded (rows and token
        slots alike) onto the scratch page. Returns
        ``(logits, outs)``: each row's last-valid-token logits, plus
        each row's per-position argmaxes when the engine runs the
        speculative verify variant (None on the non-spec plane)."""
        q_tokens = _q_bucket(max(len(t) for _, t in feeds.values()))
        valid = sum(len(t) for _, t in feeds.values())
        with span("serve.round.tables"):
            rows: List[Optional[tuple]] = [None] * self.slots
            tokens = np.zeros((self.slots, q_tokens), np.int32)
            for i, (sid, toks) in feeds.items():
                rows[i] = (sid, self.sessions[sid].kv_len, len(toks))
                tokens[i, :len(toks)] = toks
            tabs: FusedBatchTables = assemble_fused(
                self.pool, rows, q_tokens, self.pages_per_seq,
                self.scratch_page)
            args = (self.params, jnp.asarray(tokens),
                    jnp.asarray(tabs.positions), self.k_pages,
                    self.v_pages, jnp.asarray(tabs.block_tables),
                    jnp.asarray(tabs.q_start), jnp.asarray(tabs.q_lens),
                    jnp.asarray(tabs.write_pages),
                    jnp.asarray(tabs.write_slots))
        with span("serve.round.launch", B=self.slots, Q=q_tokens,
                  valid=valid):
            if self._spec_fn is not None:
                logits, outs, self.k_pages, self.v_pages, *counts = \
                    self._spec_fn(*args)
            else:
                logits, self.k_pages, self.v_pages, *counts = \
                    self._fused_fn(*args)
        self.fused_launches += 1
        self.launch_valid_tokens += valid
        self.launch_grid_tokens += self.slots * q_tokens
        self._count_attn_blocks(tabs.q_start, tabs.q_lens)
        with span("serve.round.logits"):
            out_rows = None
            if self._spec_fn is not None:
                outs = np.asarray(outs)
                out_rows = {i: outs[i] for i in feeds}
            logits = self._fetch(logits, counts)
        if self.logit_tap is not None:
            for i, (sid, _) in feeds.items():
                self.logit_tap(sid, logits[i])
        return {i: logits[i] for i in feeds}, out_rows

    def _close_turn(self, slot: int, *, aborted: bool) -> None:
        s = self.slot_state[slot]
        sid = s.session_id
        sess = self.sessions[sid]
        now = self.clock.now()
        trimmed = self.pool.trim(sid, sess.kv_len)   # in-flight lookahead
        grown = len(self.pool.seq(sid).pages) - sess.base_pages
        self.kv.release_working(grown + trimmed)
        self.kv.commit_turn(sid, sess.kv_len, now)
        if self.prefix_cache is not None:
            self._register_prefix(sid)
        if not aborted:
            self.monitor.on_response_complete(sid)
        sess.history.append(list(s.tokens))
        sess.turn_stats[-1].update(generated=s.request.generated,
                                   aborted=aborted)
        self.slot_state[slot] = None
        self._sync_page_counts(sid)

    def run_to_completion(self, max_rounds: int = 10_000) -> Dict[str, list]:
        for _ in range(max_rounds):
            if not self.active():
                break
            self.step()
        if self.active():
            raise RoundLimitExceeded(
                f"{len(self.active())} slots still live after "
                f"{max_rounds} rounds")
        out = {}
        for sid, sess in self.sessions.items():
            if sess.history:
                out[sid] = sess.history[-1]
        for s in self.slot_state.values():
            if s is not None:
                out[s.session_id] = s.tokens
        return out

    # ------------------------------------------------------------ checks
    def check_invariants(self) -> None:
        """Pool/accounting consistency (exercised by tests)."""
        from collections import Counter
        # refcount conservation (the §13 property): every allocated
        # page's refcount equals its live block-table references, and
        # zero-ref pages are exactly the orphans the radix index holds
        refs = Counter(p for s in self.pool.seqs.values()
                       for p in s.pages if p >= 0)
        for p, c in self.pool.refcount.items():
            assert refs.get(p, 0) == c, \
                f"page {p}: refcount {c} != {refs.get(p, 0)} references"
            if c == 0:
                assert p in self.pool.cache_held, \
                    f"page {p}: zero refs and not cache-held — leaked"
        allocated = set(self.pool.refcount)
        assert set(refs).issubset(allocated)
        assert allocated.isdisjoint(self.pool.free), "free+allocated page"
        assert len(allocated) + self.pool.free_pages == self.num_pages
        assert self.pool.cache_held.issubset(allocated)
        if self.prefix_cache is not None:
            assert set(self.prefix_cache.by_phys) == self.pool.cache_held
            assert self.kv.cached_blocks == sum(
                1 for p in allocated
                if self.pool.page_owner[p] is None), \
                f"cached_blocks {self.kv.cached_blocks} != owner-less pages"
            for sid in self.pool.seqs:
                kvs = self.kv.sessions.get(sid)
                if kvs is not None:
                    assert kvs.shared_pinned_blocks == \
                        self.pool.shared_charged_pages(sid), \
                        f"{sid}: stale shared-pin count"
        else:
            assert self.kv.cached_blocks == 0 \
                and not self.pool.cache_held \
                and all(c == 1 for c in self.pool.refcount.values())
        # copy-then-free: an offloading page is accounting-evicted but
        # physically still owned until its chunk drains
        offloading = sum(len(s.offloading)
                         for s in self.pool.seqs.values())
        assert self.kv.used_blocks == len(allocated) - offloading, \
            f"accounting {self.kv.used_blocks} != physical " \
            f"{len(allocated)} - offloading {offloading}"
        # per-session page-state conservation (the ISSUE 4 property):
        # resident + in-flight + offloaded == committed, disjointly
        for sid, s in self.pool.seqs.items():
            resident = sum(1 for li, p in enumerate(s.pages)
                           if p >= 0 and li not in s.loading
                           and li not in s.offloading)
            assert s.loading.isdisjoint(s.offloading), sid
            assert all(li in s.offloaded for li in s.loading), sid
            pure_off = len(s.offloaded) - len(s.loading)
            assert resident + len(s.loading) + len(s.offloading) \
                + pure_off == len(s.pages), \
                f"{sid}: page states do not partition the page list"
        # ledger <-> pool bijection (queued chunks match the marks)
        self.transfer.check(self.pool)
        if self.layout is not None:
            sh = self.layout.page_sharding()
            assert self.k_pages.sharding.is_equivalent_to(sh,
                                                          self.k_pages.ndim) \
                and self.v_pages.sharding.is_equivalent_to(sh,
                                                           self.v_pages.ndim), \
                "page store drifted off its mesh sharding"


# ======================================================================
# demo driver (launch/serve.py --engine real and examples/)
# ======================================================================
def run_multiturn_demo(*, seed: int = 0, mesh=None,
                       fused_step: bool = True, model=None,
                       page_size: int = 8, log=print) -> dict:
    """An end-to-end conversation on the real data plane, walking the
    whole §5 mechanism:

    1. alice's turn 1 prefills+decodes; her reply keeps playing.
    2. bob's heavy session *physically* evicts alice's suffix pages to
       the DRAM tier under pool pressure.
    3. alice speaks again — the pool is saturated, so the preloader's
       bounded-background-work guard skips; her turn 2 takes the
       synchronous on-path reload (stall reported, zero re-prefill) and
       is then barged-in mid-decode; turn 3 resumes on committed pages.
    4. alice hangs up (pages freed) — when bob's user speaks next, the
       speech-time preload is admitted and reloads his pages *during*
       the utterance: his turn 2 starts warm (zero stall, zero
       re-prefill).

    ``model`` is a ``(cfg, params)`` pair (default: the laptop-scale
    reduced config). The pool is always 11 pages, so the evictions above
    happen at any width; every token count scales with ``page_size / 8``
    so each turn spans the same number of pages.

    Returns per-turn stats for both sessions.
    """
    from repro.configs import get_config, reduced
    from repro.models import init_params

    assert page_size % 8 == 0, "token counts scale with page_size / 8"
    x = page_size // 8
    if model is None:
        cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                      vocab=503)
        params = init_params(cfg, jax.random.PRNGKey(seed))
        # pcie_gb_s scaled down with the laptop-scale pool (KB, not GB)
        # so transfer times land in the milliseconds the paper plots
        pcie = dict(pcie_gb_s=0.01)
    else:
        (cfg, params), pcie = model, {}
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=page_size,
                              pages_per_seq=9, num_pages=11, mesh=mesh,
                              fused_step=fused_step, **pcie)
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, size=n)

    log(f"engine: {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab_size} {cfg.dtype} slots=2 page={page_size} "
        f"pool={eng.num_pages} pages interpret={eng.interpret}"
        + (f" layout={eng.layout}" if eng.layout else ""))
    # ---- alice turn 1: admitted, decoded to completion -------------
    eng.add_session("alice", prompt(28 * x), max_new_tokens=10 * x)
    eng.run_to_completion()
    eng.monitor.on_audio("alice", 30.0)     # long reply still playing
    log(f"alice turn 1: kv_len={eng.sessions['alice'].kv_len} "
        f"pages={eng.pool.resident_pages('alice')}")

    # ---- pool pressure: bob's growth evicts alice's suffix ---------
    eng.add_session("bob", prompt(30 * x), max_new_tokens=26 * x)
    eng.run_to_completion()
    eng.monitor.on_audio("bob", 60.0)
    res, off = eng.monitor.page_counts("alice")
    log(f"bob served: alice pages resident={res} offloaded-to-DRAM={off} "
        f"(evictions so far: {len(eng.offload_events)})")

    # ---- alice speaks: saturated pool -> preload guard skips -------
    eng.user_speech_start("alice", expected_dur_s=2.0)
    eng.clock.tick(2.0)                     # the utterance itself
    log(f"alice speaks: preload admitted={eng.preloader.stats.admitted} "
        f"skipped={eng.preloader.stats.skipped} (pool saturated -> "
        f"sync fallback on turn start)")

    # ---- alice turn 2: on-path reload, zero re-prefill; barge-in ---
    eng.start_turn("alice", prompt(6 * x), max_new_tokens=12 * x)
    for _ in range(4 * x):
        eng.step()
    eng.barge_in("alice", expected_dur_s=1.0)
    eng.clock.tick(1.0)

    # ---- alice turn 3 resumes on committed pages -------------------
    eng.start_turn("alice", prompt(5 * x), max_new_tokens=6 * x)
    eng.run_to_completion()
    eng.check_invariants()

    # ---- alice hangs up; bob speaks -> preload admitted ------------
    eng.end_session("alice")
    log(f"alice hung up: pool free={eng.pool.free_pages} pages; "
        f"bob offloaded={eng.monitor.page_counts('bob')[1]}")
    eng.user_speech_start("bob", expected_dur_s=2.5)
    eng.clock.tick(2.5)
    res, off = eng.monitor.page_counts("bob")
    log(f"bob speaks: preload admitted={eng.preloader.stats.admitted} "
        f"hits pending; resident={res} offloaded={off}")

    # ---- bob turn 2: warm KV, zero stall, zero re-prefill ----------
    eng.start_turn("bob", prompt(6 * x), max_new_tokens=6 * x)
    eng.run_to_completion()
    eng.check_invariants()

    all_stats = {}
    log("")
    log(f"{'session':>8} {'turn':>4} {'ctx':>5} {'prompt':>6} {'gen':>4} "
        f"{'ttft_ms':>8} {'reload_ms':>9} {'re_prefill':>10} {'aborted':>7}")
    for sid in ("alice", "bob"):
        stats = eng.sessions[sid].turn_stats
        all_stats[sid] = stats
        for t in stats:
            log(f"{sid:>8} {t['turn']:4d} {t['context_tokens']:5d} "
                f"{t['prompt_tokens']:6d} {t['generated']:4d} "
                f"{t['ttft_s'] * 1e3:8.1f} "
                f"{t['reload_stall_s'] * 1e3:9.3f} "
                f"{t['re_prefill_tokens']:10d} {str(t['aborted']):>7}")
    log("")
    log(f"preload: {eng.preloader.stats}")
    log(f"pool: {eng.pool.stats()}  evictions={len(eng.offload_events)}")
    return {"turns": all_stats,
            "interpret": eng.interpret,
            "preload": vars(eng.preloader.stats),
            "pool": eng.pool.stats(),
            "offload_events": len(eng.offload_events)}
