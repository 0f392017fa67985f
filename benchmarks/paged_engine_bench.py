"""Real-data-plane microbenchmarks: paged vs dense decode-step latency
and DRAM->HBM reload time per page.

Section ``paged_engine`` of benchmarks/run.py. These are wall-clock
numbers for the CPU container (Pallas interpret mode) — a perf
trajectory for future PRs on the paged engine, not absolutes; on TPU the
paged step runs the Mosaic kernel.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import fmt, row


def _mean_step_us(eng, steps: int):
    t0 = time.perf_counter()
    n = 0
    for _ in range(steps):
        if not eng.step():
            break
        n += 1
    return (time.perf_counter() - t0) / max(1, n) * 1e6, n


def run(quick: bool = False) -> None:
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving.engine import RealtimeLLMEngine
    from repro.serving.paged_engine import PagedRealtimeEngine

    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                  vocab=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    slots = 4
    steps = 8 if quick else 32

    def admit(eng):
        for i in range(slots):
            eng.add_session(f"s{i}",
                            rng.integers(0, cfg.vocab_size, size=16),
                            max_new_tokens=steps + 16)

    dense = RealtimeLLMEngine(cfg, params, slots=slots, capacity=256)
    admit(dense)
    dense.step()
    dense.step()                               # warm the jit cache
    us, n = _mean_step_us(dense, steps)
    row("paged_engine/dense_step", us, f"slots={slots};rounds={n}")

    paged = PagedRealtimeEngine(cfg, params, slots=slots, page_size=16,
                                pages_per_seq=16)
    admit(paged)
    paged.step()
    paged.step()
    us, n = _mean_step_us(paged, steps)
    row("paged_engine/paged_step", us, f"slots={slots};rounds={n}")

    # tensor-sharded data plane (DESIGN.md §9): decode step time and
    # tokens/s vs mesh shape. Needs >1 device — on CPU run under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI
    # multidevice job does); on a single device the section reports a
    # skip row so the JSON artifact stays schema-stable.
    ndev = len(jax.devices())
    mesh_shapes = [(1, m) for m in (2, 4, 8) if m <= ndev]
    if ndev >= 4:
        mesh_shapes.append((2, 2))
    if not mesh_shapes:
        row("paged_engine/sharded_step", 0.0,
            f"skipped;devices={ndev};need>=2")
    for d, m in mesh_shapes:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
        sharded = PagedRealtimeEngine(cfg, params, slots=slots,
                                      page_size=16, pages_per_seq=16,
                                      mesh=mesh)
        admit(sharded)
        sharded.step()
        sharded.step()                     # warm the sharded jit cache
        us, n = _mean_step_us(sharded, steps)
        tok_s = slots / (us * 1e-6) if us else 0.0
        row(f"paged_engine/sharded_step_{d}x{m}", us,
            f"kind={sharded.layout.kind};slots={slots};rounds={n};"
            f"tokens_s={tok_s:.0f}")

    # DRAM->HBM reload path: finish the turns (unpin), offload suffix
    # pages via the manager (flushed so the copies are durably in DRAM
    # — otherwise copy-then-free would hand them back for free), then
    # time the physical reload per page (the engine's per-chunk io
    # records the staged host->device wall time)
    paged.run_to_completion()
    want = 4 if quick else 8
    freed = paged.kv.evict(want, paged.clock.now())
    paged.flush_transfers()
    paged.reload_wall_s.clear()
    reloaded = 0
    for sid in list(paged.kv.sessions):
        n = paged.kv.missing_blocks(sid)
        if n > 0:
            paged.kv.reload(sid, paged.clock.now(), background=False)
            reloaded += n
    us_page = sum(paged.reload_wall_s) / max(1, reloaded) * 1e6
    page_kb = np.prod(paged.k_pages.shape[2:]) * 2 \
        * paged.k_pages.dtype.itemsize * cfg.num_layers / 1024.0
    row("paged_engine/reload_per_page", us_page,
        f"pages={reloaded};evicted={freed};page_kb={page_kb:.1f}")

    _fused_prefill_section(cfg, params, quick)
    _spec_decode_section(cfg, params, quick)
    _overlap_section(cfg, params, quick)
    _prefix_section(cfg, params, quick)


class _StreamOracle:
    """Draft proposer that replays a known greedy stream — the
    perfect-acceptance upper bound (self-speculation with an oracle).
    Verification is lossless either way; this isolates the *launch*
    economics of speculative decode from proposer quality."""

    def __init__(self, prompt_len: int, stream):
        self.prompt_len = prompt_len
        self.stream = [int(t) for t in stream]
        self.session_id = None            # set by the engine

    def propose(self, history, k):
        if self.session_id != "s":
            return [0] * k                # warm turns: any tokens do —
            #                               rejected drafts only compile
            #                               the verify bucket
        g = len(history) - self.prompt_len
        return self.stream[g:g + k]


def _spec_decode_section(cfg, params, quick: bool) -> None:
    """Speculative multi-token decode (DESIGN.md §16, the ISSUE 10
    acceptance row): the same seeded turn decodes on the plain fused
    plane (one committed token per launch) and under ``spec_decode=4``
    with an oracle proposer replaying the greedy stream (the perfect-
    acceptance bound: 1+K committed tokens per launch). Accepted
    streams must be bit-exact; accepted tokens per wall-second is the
    headline (acceptance: >= 2x at K=4)."""
    from repro.core.session import Phase
    from repro.serving.paged_engine import PagedRealtimeEngine

    rng = np.random.default_rng(4)
    K = 4
    P, N = (16, 24) if quick else (16, 48)
    prompt = rng.integers(0, cfg.vocab_size, size=P)
    warm_prompt = rng.integers(0, cfg.vocab_size, size=P)

    def decode_turn(spec: int, proposer=None):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=16,
                                  pages_per_seq=16, fused_step=True,
                                  spec_decode=spec, proposer=proposer)
        grant = 1 + spec
        # throwaway turn compiles the prefill bucket and the decode /
        # verify bucket outside the timed window (max_new large enough
        # that the draft budget is not clamped below K on round one —
        # otherwise the full verify bucket compiles inside the timing)
        warm = eng.submit_turn("warm", warm_prompt, max_new_tokens=12)
        while eng.active():
            s = eng.slot_state[warm]
            g = P if s.request.phase == Phase.PREFILL else grant
            eng.run_round({warm: g})
        # the warm turn's junk drafts land in the counters; the reported
        # accounting should cover the measured turn only
        eng.spec_drafted = eng.spec_accepted = 0
        eng.spec_rejected = eng.spec_rounds = 0
        slot = eng.submit_turn("s", prompt, max_new_tokens=N)
        toks = []
        while eng.slot_state[slot].request.phase == Phase.PREFILL:
            for ev in eng.run_round({slot: P})[slot]:
                if ev[0] == "token":
                    toks.append(ev[1])
        t0 = time.perf_counter()
        launches = 0
        while eng.active():
            for ev in eng.run_round({slot: grant})[slot]:
                if ev[0] == "token":
                    toks.append(ev[1])
            launches += 1
        wall = time.perf_counter() - t0
        eng.check_invariants()
        return toks, wall, launches, eng

    base_toks, base_wall, base_launch, _ = decode_turn(0)
    oracle = _StreamOracle(P, base_toks)
    spec_toks, spec_wall, spec_launch, eng = decode_turn(K, oracle)
    assert spec_toks == base_toks, "spec stream drifted from control"
    assert eng.spec_accepted + eng.spec_rejected == eng.spec_drafted
    base_tps = len(base_toks) / base_wall
    spec_tps = len(spec_toks) / spec_wall
    row("paged_engine/spec_decode_off", base_wall / len(base_toks) * 1e6,
        f"tokens_s={base_tps:.0f};launches={base_launch};tokens={N}")
    row("paged_engine/spec_decode_k4", spec_wall / len(spec_toks) * 1e6,
        f"tokens_s={spec_tps:.0f};speedup={spec_tps / base_tps:.2f};"
        f"launches={spec_launch};"
        f"accept_rate={eng.spec_accepted / max(1, eng.spec_drafted):.2f};"
        f"tokens_per_launch="
        f"{(eng.spec_rounds + eng.spec_accepted) / max(1, eng.spec_rounds):.2f};"
        f"bit_exact=1")


def _fused_prefill_section(cfg, params, quick: bool) -> None:
    """Fused vs per-token chunked prefill (DESIGN.md §11, the ISSUE 5
    acceptance row): the same long prompt is teacher-forced through
    ``run_round`` under identical 16-token chunk grants on both planes.
    The fused plane runs each grant as ONE jitted launch; the per-token
    control pays one launch per prompt token — the measured tokens/s
    gap is the point of the fused refactor."""
    from repro.core.session import Phase
    from repro.serving.paged_engine import PagedRealtimeEngine

    rng = np.random.default_rng(2)
    P = 64 if quick else 128
    chunk = 16
    prompt = rng.integers(0, cfg.vocab_size, size=P)
    stats = {}
    for fused in (True, False):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=16,
                                  pages_per_seq=16, fused_step=fused)
        # a throwaway turn warms every compiled shape outside the
        # timed window (Q=chunk and Q=1 buckets on the fused plane)
        warm = eng.submit_turn(
            "warm", rng.integers(0, cfg.vocab_size, size=chunk),
            max_new_tokens=2)
        while eng.active():
            eng.run_round({warm: chunk})
        slot = eng.submit_turn("s", prompt, max_new_tokens=2)
        launches0 = eng.fused_launches
        t0 = time.perf_counter()
        rounds = 0
        while eng.slot_state[slot].request.phase == Phase.PREFILL:
            eng.run_round({slot: chunk})
            rounds += 1
        wall = time.perf_counter() - t0
        eng.check_invariants()
        name = "fused" if fused else "tokenwise"
        stats[name] = P / wall
        launches = (eng.fused_launches - launches0) if fused \
            else P                       # one jitted launch per token
        row(f"paged_engine/prefill_{name}", wall / P * 1e6,
            f"tokens_s={P / wall:.0f};prompt={P};chunk={chunk};"
            f"rounds={rounds};launches={launches}")
    row("paged_engine/prefill_fused_speedup",
        (1.0 / stats["tokenwise"] - 1.0 / stats["fused"]) * 1e6,
        f"fused_over_tokenwise={stats['fused'] / stats['tokenwise']:.2f};"
        f"prompt={P};chunk={chunk}")


def _prefix_section(cfg, params, quick: bool) -> None:
    """Shared-prefix KV capacity (ISSUE 7, DESIGN.md §13): sessions of
    one prompt family (identical 32-token system prompt, unique
    4-token suffix) are admitted and pinned hot until the fixed pool
    refuses the next one. With the radix prefix cache each new session
    attaches to the family's committed pages and pays only its private
    suffix; without it every session carries full private copies. The
    row reports resident sessions cached vs control (the ISSUE 7
    acceptance: strictly more) and the attach-time prefill saving."""
    from repro.kvcache.paged import OutOfPages
    from repro.serving.paged_engine import PagedRealtimeEngine

    rng = np.random.default_rng(3)
    fam = rng.integers(0, cfg.vocab_size, size=32)
    suffixes = rng.integers(0, cfg.vocab_size, size=(16, 4))

    def fill(prefix: bool):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=8,
                                  pages_per_seq=8, num_pages=16,
                                  fused_step=True, prefix_cache=prefix)
        resident, ttfp = 0, []
        for i in range(16):
            t0 = time.perf_counter()
            try:
                eng.add_session(f"s{i}",
                                np.concatenate([fam, suffixes[i]]),
                                max_new_tokens=2)
            except OutOfPages:
                break
            ttfp.append(time.perf_counter() - t0)
            eng.run_to_completion()
            eng.kv.pin(f"s{i}")          # hold every session hot
            resident += 1
        eng.check_invariants()
        # sessions after the first skip the family prefill entirely;
        # the second is excluded too — the first attacher pays the
        # one-time jit compile of the small suffix-only query bucket
        later = ttfp[2:] or [0.0]
        return resident, sum(later) / len(later) * 1e6, eng

    n_cached, us_cached, eng = fill(True)
    n_control, us_control, _ = fill(False)
    hit = eng.prefix_cache.hit_tokens
    lookups = eng.prefix_cache.lookups
    row("paged_engine/prefix_resident_sessions", us_cached,
        f"cached={n_cached};control={n_control};pool_pages=16;"
        f"family_prefix=32;hit_tokens={hit};lookups={lookups}")
    row("paged_engine/prefix_attach_turn_start",
        us_cached,
        f"control_us={fmt(us_control, 1)};"
        f"speedup={us_control / max(us_cached, 1e-9):.2f};"
        f"cow_copies={eng.cow_copies};"
        f"peak_shared={eng.peak_shared_pages}")


def _overlap_drive(cfg, params, quick: bool, kv_quant: str):
    """Shared overlap workload (one drive per wire format): a's
    speech-time preloads drain chunk-by-chunk between b's decode
    rounds across ``turns`` evict/reload cycles."""
    import jax.numpy as jnp
    from repro.serving.paged_engine import PagedRealtimeEngine

    rng = np.random.default_rng(1)
    page_size = 8
    bytes_per_token = 2 * cfg.num_layers * cfg.num_kv_heads \
        * cfg.resolved_head_dim * jnp.dtype(cfg.dtype).itemsize
    # ~0.2 modeled s per fp32 page: slow enough that the time credit
    # never fires inside the bench's millisecond rounds — every
    # off-path page got there by a real drain between decode
    # sub-batches. int8 shrinks per-page channel time by its wire scale.
    eng = PagedRealtimeEngine(
        cfg, params, slots=2, page_size=page_size, pages_per_seq=12,
        num_pages=64, chunk_pages=1,
        pcie_gb_s=bytes_per_token * page_size / 0.2e9,
        kv_quant=kv_quant)
    per_page_s = eng.kv.channel.transfer_time(1)
    turns = 2 if quick else 3
    evict_pages = 4
    t0 = time.perf_counter()
    eng.add_session("a", rng.integers(0, cfg.vocab_size, size=24),
                    max_new_tokens=6)
    eng.run_to_completion()
    eng.add_session("b", rng.integers(0, cfg.vocab_size, size=8),
                    max_new_tokens=12 * turns + 6)
    for _ in range(turns):
        # idle gap long enough to lapse the previous preload's
        # protection TTL, so the eviction pass can pick a again
        eng.clock.tick(12.0)
        assert eng.kv.evict(evict_pages, eng.clock.now()) == evict_pages
        eng.flush_transfers()                # copies durable in DRAM
        window = (evict_pages + 2) * per_page_s / 0.8
        eng.user_speech_start("a", expected_dur_s=window)
        for _ in range(evict_pages + 2):     # b decodes; chunks drain
            eng.step()
        eng.start_turn("a", rng.integers(0, cfg.vocab_size, size=4),
                       max_new_tokens=3)
        # drive only a's turn to completion (b keeps its budget)
        while any(s is not None and s.session_id == "a"
                  and s.request.is_live()
                  for s in eng.slot_state.values()):
            eng.step()
    eng.check_invariants()
    return eng, turns, time.perf_counter() - t0


def _overlap_section(cfg, params, quick: bool) -> None:
    """Async chunked transfer overlap (ISSUE 4): the fraction of
    preloaded reload bytes completed off the turn critical path
    (acceptance: >= 0.70) plus the mean per-chunk drain wall time —
    then the same workload on the int8 KV wire tier (DESIGN.md §14):
    identical trace, ~4x less modeled PCIe per page, so the overlap
    fraction must hold or improve while reload wire bytes drop under
    0.5x of fp32 (the quantized acceptance rows)."""
    results = {}
    for kv_quant in ("fp32", "int8"):
        eng, turns, wall = _overlap_drive(cfg, params, quick, kv_quant)
        st = eng.transfer.stats
        stalls = [t["reload_stall_s"]
                  for t in eng.sessions["a"].turn_stats[1:]]
        results[kv_quant] = (eng, st)
        suffix = "" if kv_quant == "fp32" else "_int8"
        row(f"paged_engine/reload_overlap_frac{suffix}",
            st.overlap_fraction() * 100.0,
            f"off_path={st.reload_pages_off_path};"
            f"on_path={st.reload_pages_on_path};turns={turns};"
            f"mean_stall_ms="
            f"{fmt(1e3 * sum(stalls) / max(1, len(stalls)))};"
            f"wall_s={fmt(wall, 2)}")
        if kv_quant == "fp32":
            walls = eng.reload_wall_s            # per-chunk staged io
            row("paged_engine/transfer_chunk_drain",
                sum(walls) / max(1, len(walls)) * 1e6,
                f"chunks={st.chunks_drained};"
                f"reload_chunks={len(walls)};"
                f"chunk_pages={eng.transfer.chunk_pages}")

    # quantized wire + DRAM-capacity rows: same trace, so the logical
    # page flow is identical and the byte ratios are pure codec effect
    eng8, st8 = results["int8"]
    _, st32 = results["fp32"]
    bb = eng8.kv.channel.block_bytes
    ratio = st8.reload_wire_bytes / max(1e-9, st32.reload_wire_bytes)
    row("paged_engine/quant_reload_wire_bytes", st8.reload_wire_bytes,
        f"fp32_bytes={st32.reload_wire_bytes:.0f};"
        f"int8_over_fp32={ratio:.3f};"
        f"wire_bytes_saved={st8.wire_bytes_saved:.0f}")
    # the offload tier's capacity win: host-store bytes per offloaded
    # page (the DRAM tier holds ~1/wire_scale more sessions per GB)
    kb8 = bb * eng8.kv.channel.wire_scale / 1024.0
    kb32 = bb / 1024.0
    row("paged_engine/quant_dram_page_kb", kb8,
        f"fp32_kb={fmt(kb32)};"
        f"pages_per_gb_int8={int(1e9 / (kb8 * 1024))};"
        f"pages_per_gb_fp32={int(1e9 / (kb32 * 1024))}")
