"""Fused token-budget step (DESIGN.md §11): one round = one launch.

Covers the ISSUE 5 contracts:
- kernel parity: ``paged_prefill_attention`` against the pure-jnp
  oracle across shapes/dtypes (MQA, ragged ``q_lens``, padding rows),
  Q=1 equality with the single-token decode kernel, and the
  striped-slot stats merge that backs the sharded plane;
- a round granting a C-token prefill chunk executes as exactly ONE
  jitted launch on the fused path (the per-token ``_step_fn`` is never
  entered);
- fused vs per-token (``fused_step=False``) differential: bit-exact
  token streams AND event streams on full multi-turn traces — chunked
  prefill with interleaved decode, barge-in mid-chunk, physical
  evict-to-DRAM/reload — as an always-on deterministic sweep plus a
  hypothesis property over random chunk budgets/barge rounds/evictions
  (slow lane), plus the deterministic replay gateway (scheduler,
  frontier cap, barge storms) as a whole-system differential;
- the self-scheduled path passes the scheduler's chunk grants through
  (``step()`` no longer flattens PREFILL grants to one token);
- 8-virtual-device mesh twins stay token-exact (multidev lane).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_config, reduced
from repro.core.session import Phase
from repro.kernels import ref
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.models import init_params
from repro.serving.paged_engine import PagedRealtimeEngine, _q_bucket

NDEV = len(jax.devices())
multidev = pytest.mark.skipif(
    NDEV < 2,
    reason="needs >1 device; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                  vocab=331)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _case(key, B, Q, Hq, Hkv, D, page, pps, dtype=jnp.float32):
    num_pages = B * pps + 3
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Q, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (num_pages, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (num_pages, page, Hkv, D), dtype)
    bt = jax.random.permutation(
        ks[3], num_pages)[:B * pps].reshape(B, pps).astype(jnp.int32)
    # ragged starts/lengths incl. a zero-history row, a padding-heavy
    # row, and (when B allows) a fully-padded q_lens == 0 row
    qs = jnp.array([(i * 7) % (page * pps - Q) for i in range(B)],
                   jnp.int32)
    ql = jnp.array([0 if (B > 2 and i == B - 1)
                    else 1 + (i * 3) % Q for i in range(B)], jnp.int32)
    return q, kp, vp, bt, qs, ql


# ======================================================================
# kernel parity
# ======================================================================
def _fused_case(*dims, rows=None, kv_block=None):
    """(B, Q, Hq, Hkv, D, page, pps), optional explicit (q_start,
    q_lens) rows and block."""
    return pytest.param(*dims, rows, kv_block,
                        id="-".join(map(str, dims)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Q,Hq,Hkv,D,page,pps,rows,kv_block",
    [
        _fused_case(3, 4, 4, 2, 16, 8, 4),      # GQA, mixed q_lens
        _fused_case(2, 8, 8, 2, 32, 8, 5),      # chunk spans pages
        _fused_case(1, 7, 4, 1, 16, 4, 6),      # MQA, odd Q
        _fused_case(4, 5, 6, 3, 16, 5, 4),      # non-pow2 page, padded row
        _fused_case(2, 1, 4, 2, 16, 8, 4),      # decode-only round (Q=1)
        # 2-page blocks over a 10-page table: rows end on a block edge
        # (32, 32, 64), mid-block (41, 12), and a q_lens == 0 row
        # between live ones; Q 32, Hkv 2 with G 4
        _fused_case(6, 32, 8, 2, 16, 8, 10,
                    rows=((0, 16, 40, 7, 3, 63), (32, 16, 1, 0, 9, 1)),
                    kv_block=16),
        # the block rule's 128 tokens over 20 pages (not a multiple):
        # Q 1 with Hkv 8 and G 6; Q 32 with Hkv 1 and G 4
        _fused_case(4, 1, 48, 8, 16, 8, 20,
                    rows=((149, 127, 0, 5), (1, 1, 0, 1))),
        _fused_case(3, 32, 4, 1, 32, 8, 20,
                    rows=((100, 96, 0), (32, 32, 0))),
        # 2-page blocks over 9 pages of 16: Hkv 2 with G 6, Q 32
        _fused_case(4, 32, 12, 2, 16, 16, 9,
                    rows=((0, 100, 60, 0), (32, 32, 4, 0)),
                    kv_block=32),
    ])
def test_fused_kernel_matches_ref(B, Q, Hq, Hkv, D, page, pps, rows,
                                  kv_block, dtype):
    tol = TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]
    q, kp, vp, bt, qs, ql = _case(jax.random.PRNGKey(0), B, Q, Hq, Hkv,
                                  D, page, pps, dtype)
    if rows is not None:
        qs, ql = (jnp.array(r, jnp.int32) for r in rows)
    got = paged_prefill_attention(q, kp, vp, bt, qs, ql, interpret=True,
                                  kv_block=kv_block)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, qs, ql)
    for b in range(B):       # padding tokens are unspecified: skip them
        n = int(ql[b])
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[b, :n],
            np.asarray(want, np.float32)[b, :n], rtol=tol, atol=tol)


def test_fused_kernel_q1_matches_decode_kernel():
    """A decode-only fused round must reproduce the single-token kernel
    bit for bit — the two planes share numerics at Q=1."""
    q, kp, vp, bt, qs, ql = _case(jax.random.PRNGKey(1), 3, 1, 8, 2, 32,
                                  8, 5)
    ql = jnp.ones_like(ql)
    got = paged_prefill_attention(q, kp, vp, bt, qs, ql, interpret=True)
    want = paged_attention(q[:, 0], kp, vp, bt, qs + 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(want))


def test_fused_kernel_stats_stripes_merge():
    """The shard-side contract without a mesh: striping each page's
    slots, computing per-stripe (o, m, l) with the shifted q_start, and
    flash-merging reproduces the full intra-chunk causal softmax —
    including rows whose causal limit falls entirely inside one stripe
    (the fully-masked-shard case the finite NEG_INF sentinel covers)."""
    q, kp, vp, bt, qs, ql = _case(jax.random.PRNGKey(2), 3, 6, 4, 2, 16,
                                  8, 4)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, qs, ql)
    page = kp.shape[1]
    for S in (2, 4, 8):
        psl = page // S
        outs = []
        for s in range(S):
            o, m, l = paged_prefill_attention(
                q, kp[:, s * psl:(s + 1) * psl],
                vp[:, s * psl:(s + 1) * psl], bt, qs - s * psl, ql,
                pos_stride=page, return_stats=True, interpret=True)
            outs.append((o.astype(jnp.float32), m, l))
        m_star = jnp.max(jnp.stack([m for _, m, _ in outs]), axis=0)
        ws = [l * jnp.exp(m - m_star) for _, m, l in outs]
        den = jnp.maximum(sum(ws), 1e-30)
        got = sum(o * w[..., None] for (o, _, _), w in zip(outs, ws)) \
            / den[..., None]
        for b in range(q.shape[0]):
            n = int(ql[b])
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(want, np.float32)[b, :n],
                rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shift", ["q_start", "pos_offset"])
def test_fused_kernel_stats_merge_over_blocks(shift):
    """The stripe merge of ``test_fused_kernel_stats_stripes_merge`` on
    multi-page blocks: pages of 16 slots striped in 2 or 4 shards of 8
    or 4 slots (2-page blocks of 8-slot shards; a 4-slot shard is a
    block alone), Q 32, a ``q_lens == 0`` row between live ones, rows
    ending mid-block and on a block edge; each shard's slot offset goes
    either into the traced ``q_start`` or into the static
    ``pos_offset``."""
    q, kp, vp, bt, _, _ = _case(jax.random.PRNGKey(3), 4, 32, 8, 2, 16,
                                16, 6)
    qs = jnp.array([0, 40, 5, 64], jnp.int32)
    ql = jnp.array([32, 23, 0, 17], jnp.int32)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, qs, ql)
    page = kp.shape[1]
    for S in (2, 4):
        psl = page // S
        outs = []
        for s in range(S):
            kw = (dict(pos_offset=s * psl) if shift == "pos_offset"
                  else {})
            o, m, l = paged_prefill_attention(
                q, kp[:, s * psl:(s + 1) * psl],
                vp[:, s * psl:(s + 1) * psl], bt,
                qs if kw else qs - s * psl, ql, pos_stride=page,
                return_stats=True, interpret=True,
                kv_block=2 * psl if psl % 8 == 0 else psl, **kw)
            outs.append((o.astype(jnp.float32), m, l))
        m_star = jnp.max(jnp.stack([m for _, m, _ in outs]), axis=0)
        ws = [l * jnp.exp(m - m_star) for _, m, l in outs]
        den = jnp.maximum(sum(ws), 1e-30)
        got = sum(o * w[..., None] for (o, _, _), w in zip(outs, ws)) \
            / den[..., None]
        for b in range(q.shape[0]):
            n = int(ql[b])
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(want, np.float32)[b, :n],
                rtol=2e-5, atol=2e-5, err_msg=f"S={S} row {b}")


def test_q_bucket():
    assert [_q_bucket(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 16, 17)] == \
        [1, 1, 2, 4, 4, 8, 8, 16, 16, 32]


# ======================================================================
# one round = one launch
# ======================================================================
def test_chunked_round_is_one_launch(tiny):
    """A round granting a C-token prefill chunk plus concurrent decode
    runs as ONE fused launch — no Python-level per-token sub-batches,
    and the per-token step function is never entered."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                              pages_per_seq=16)
    eng.add_session("b", rng.integers(0, cfg.vocab_size, size=5),
                    max_new_tokens=30)           # decode participant
    sb = next(i for i, s in eng.slot_state.items() if s is not None)

    def forbidden(*a, **k):
        raise AssertionError("per-token step entered on the fused plane")

    eng._step_fn = forbidden
    sa = eng.submit_turn("a", rng.integers(0, cfg.vocab_size, size=12),
                         max_new_tokens=4)
    launches = eng.fused_launches
    rounds = 0
    while eng.slot_state[sa].request.phase == Phase.PREFILL:
        eng.run_round({sa: 5, sb: 1})
        rounds += 1
        assert eng.fused_launches == launches + rounds, \
            "a C-token chunk must cost exactly one launch per round"
    assert rounds == 3                           # ceil(12 / 5)
    eng.check_invariants()


def test_self_scheduled_step_passes_chunk_grants(tiny):
    """ISSUE 5 satellite: ``step()`` forwards the scheduler's
    ``chunk_for`` grant instead of flattening every slot to one token —
    a PREFILL slot advances a whole chunk per self-scheduled round."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    eng = PagedRealtimeEngine(cfg, params, slots=4, page_size=8,
                              pages_per_seq=16)
    eng.submit_turn("a", rng.integers(0, cfg.vocab_size, size=11),
                    max_new_tokens=3)
    launches = eng.fused_launches
    eng.step()
    r = next(s for s in eng.slot_state.values()
             if s is not None).request
    # engine's self-scheduler clamps prefill_chunk to the round budget
    # (= slots = 4): one round teacher-forces 4 tokens in one launch
    assert r.prefilled == 4
    assert eng.fused_launches == launches + 1
    eng.run_to_completion()
    eng.check_invariants()


def test_hoisted_lookahead_covers_chunk(tiny):
    """ISSUE 5 satellite: the best-effort lookahead grows once per slot
    per round covering the whole grant plus the boundary page — on a
    roomy pool a mid-prompt chunk round leaves the next page owned."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    for fused in (True, False):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                                  pages_per_seq=16, fused_step=fused)
        sa = eng.submit_turn("a", rng.integers(0, cfg.vocab_size,
                                               size=10),
                             max_new_tokens=4)
        eng.run_round({sa: 6})                   # mid-prompt round
        sess = eng.sessions["a"]
        assert sess.kv_len == 6
        assert len(eng.pool.seq("a").pages) >= eng.pool.pages_for(
            sess.kv_len + eng.page_size), \
            f"lookahead page not owned (fused={fused})"
        eng.check_invariants()


# ======================================================================
# fused vs per-token differential
# ======================================================================
def _drive_differential(cfg, params, seed, *, mesh=None,
                        fused: bool = True, max_chunk: int = 5,
                        barge_round: int = 3, evict_pages: int = 6,
                        page_size: int = 4, num_pages: int = 24):
    """One seeded multi-turn trace through ``run_round`` with random
    chunk grants: chunked prefill interleaving decode, a barge-in that
    lands mid-trace, physical evict-to-DRAM + reload across a turn
    boundary, a second/third turn on committed pages. Returns
    (histories, event streams, turn stats) for exact comparison.

    The rng is consumed identically on both planes as long as the
    planes stay token-exact — which is the property under test; any
    drift cascades into the final assertion."""
    rng = np.random.default_rng(seed)
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=page_size,
                              pages_per_seq=16, num_pages=num_pages,
                              mesh=mesh, fused_step=fused)
    stream = []

    def drive(live_grants, barge_at=None):
        rounds = 0
        while eng.active() and rounds < 400:
            grants = {}
            for slot, sid in list(live_grants.items()):
                s = eng.slot_state[slot]
                if s is None or s.session_id != sid \
                        or not s.request.is_live():
                    continue
                grants[slot] = int(rng.integers(1, max_chunk + 1))
            if not grants:
                break
            stream.append((rounds, eng.run_round(grants)))
            rounds += 1
            if barge_at is not None and rounds == barge_at:
                eng.barge_in("a")
                stream.append(("barge", rounds))
                return

    pa = rng.integers(0, cfg.vocab_size, size=int(rng.integers(8, 14)))
    pb = rng.integers(0, cfg.vocab_size, size=int(rng.integers(5, 10)))
    sa = eng.submit_turn("a", pa, max_new_tokens=int(rng.integers(5, 9)))
    sb = eng.submit_turn("b", pb, max_new_tokens=int(rng.integers(4, 8)))
    drive({sa: "a", sb: "b"})
    # physical offload of a's suffix; flush makes the DRAM copies
    # durable so the next session's growth really clobbers the slots
    evicted = eng.kv.evict(evict_pages, eng.clock.now())
    eng.flush_transfers()
    stream.append(("evicted", evicted))
    pc = rng.integers(0, cfg.vocab_size, size=8)
    sc = eng.submit_turn("c", pc, max_new_tokens=int(rng.integers(4, 8)))
    drive({sc: "c"})
    # a returns: reload path (zero re-prefill), then a barge mid-decode
    pa2 = rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 9)))
    sa2 = eng.submit_turn("a", pa2, max_new_tokens=10)
    drive({sa2: "a"}, barge_at=barge_round)
    # turn 3 resumes on exactly the committed tokens
    pa3 = rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 7)))
    sa3 = eng.submit_turn("a", pa3, max_new_tokens=int(rng.integers(3, 6)))
    drive({sa3: "a"})
    eng.check_invariants()
    hist = {sid: s.history for sid, s in eng.sessions.items()}
    stats = {sid: [(t["re_prefill_tokens"], t["generated"], t["aborted"])
                   for t in s.turn_stats]
             for sid, s in eng.sessions.items()}
    return hist, stream, stats, eng


SWEEP = [(0, 3, 2), (1, 5, 4), (2, 1, 1), (3, 7, 6), (4, 4, 3)]


@pytest.mark.parametrize("seed,max_chunk,barge_round", SWEEP)
def test_fused_vs_tokenwise_deterministic_sweep(tiny, seed, max_chunk,
                                                barge_round):
    """Always-on sweep: identical token streams, event streams, and
    turn stats across the two planes on full traces (barge-in +
    physical evict/reload included)."""
    cfg, params = tiny
    want = _drive_differential(cfg, params, seed, fused=False,
                               max_chunk=max_chunk,
                               barge_round=barge_round)
    got = _drive_differential(cfg, params, seed, fused=True,
                              max_chunk=max_chunk,
                              barge_round=barge_round)
    assert got[0] == want[0], "token histories diverged"
    assert got[1] == want[1], "event streams diverged"
    assert got[2] == want[2], "turn stats diverged"
    # the trace exercised the reload path for real on both planes
    assert got[3].kv.reloaded_blocks >= 1
    assert want[3].kv.reloaded_blocks >= 1


@pytest.mark.slow
@given(seed=st.integers(0, 2 ** 16),
       max_chunk=st.integers(1, 9),
       barge_round=st.integers(1, 8),
       evict_pages=st.integers(2, 8))
@settings(max_examples=15, deadline=None)
def test_fused_vs_tokenwise_property(tiny, seed, max_chunk, barge_round,
                                     evict_pages):
    cfg, params = tiny
    want = _drive_differential(cfg, params, seed, fused=False,
                               max_chunk=max_chunk,
                               barge_round=barge_round,
                               evict_pages=evict_pages)
    got = _drive_differential(cfg, params, seed, fused=True,
                              max_chunk=max_chunk,
                              barge_round=barge_round,
                              evict_pages=evict_pages)
    assert got[:3] == want[:3]


def test_zero_grant_is_not_scheduled_on_both_planes(tiny):
    """Regression (review): ``run_round({slot: 0})`` must advance
    nothing on either plane — a zero grant means "not scheduled this
    round" for DECODE slots too, even mixed with positive grants, so
    the planes' bit-exactness contract covers every run_round input."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    pa = rng.integers(0, cfg.vocab_size, size=5)
    pb = rng.integers(0, cfg.vocab_size, size=6)
    for fused in (True, False):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                                  pages_per_seq=16, fused_step=fused)
        sa = eng.add_session("a", pa, max_new_tokens=8)
        sb = eng.submit_turn("b", pb, max_new_tokens=4)
        gen0 = eng.slot_state[sa].request.generated
        assert eng.run_round({sa: 0}) == {sa: []}, fused
        assert eng.slot_state[sa].request.generated == gen0, fused
        # zero grant alongside a positive one: only the granted slot runs
        evs = eng.run_round({sa: 0, sb: 2})
        assert evs[sa] == [] and len(evs[sb]) == 2, (fused, evs)
        assert eng.slot_state[sa].request.generated == gen0, fused
        eng.check_invariants()


def test_sync_paths_parity(tiny):
    """add_session / start_turn route turn-0 and turn-N prefill through
    the fused launch: token streams match the per-token engine across a
    multi-turn conversation driven by the self-scheduled step."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    turns = [(rng.integers(0, cfg.vocab_size, size=9), 6),
             (rng.integers(0, cfg.vocab_size, size=5), 7),
             (rng.integers(0, cfg.vocab_size, size=4), 5)]

    def drive(fused):
        eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                                  pages_per_seq=16, fused_step=fused)
        eng.add_session("a", turns[0][0], max_new_tokens=turns[0][1])
        eng.run_to_completion()
        for prompt, n in turns[1:]:
            eng.start_turn("a", prompt, max_new_tokens=n)
            eng.run_to_completion()
        eng.check_invariants()
        return eng.sessions["a"].history

    assert drive(True) == drive(False)


# ======================================================================
# whole-system differential: the deterministic replay gateway
# ======================================================================
def test_replay_gateway_fused_vs_tokenwise(tiny):
    """The full control plane (Algorithm 1, frontier cap, barge storms,
    OutOfPages requeue) over both planes on the same virtual clock:
    the scheduling-visible record — TTFP, completion order, barges,
    token counts — must be identical."""
    from repro.serving.gateway.replay import ReplayConfig, run_replay
    from repro.serving.workload import WorkloadConfig
    cfg, params = tiny
    wl = WorkloadConfig(kind="interactive", num_sessions=4, seed=5,
                        p_barge_in=0.5, arrival="poisson", rate_rps=4.0)

    def run(fused):
        def factory(clock):
            return PagedRealtimeEngine(
                cfg, params, slots=2, page_size=8, pages_per_seq=8,
                clock=clock, fused_step=fused)
        m, gw = run_replay(factory, wl,
                           ReplayConfig(round_token_budget=8,
                                        prefill_chunk=6), seed=5)
        return [(t.session_id, t.turn_index, t.ttfp, t.finish_time,
                 t.completed, t.barged, t.talker_generated)
                for t in m.turns], gw

    want, _ = run(False)
    got, gw = run(True)
    assert got == want
    # at most one launch per executed round (a round whose feeds were
    # all pressure-held launches nothing)
    assert 0 < gw.eng.fused_launches <= gw.rounds


# ======================================================================
# mesh twins (multidev lane; CI multidevice job / full local runs)
# ======================================================================
@multidev
@pytest.mark.parametrize("shape", [(1, 2), (1, 8), (2, 2)])
def test_fused_sharded_engine_token_exact(tiny, shape):
    """heads (1,2 / 2,2) and slots (1,8 — chunk spans several shards'
    slot stripes) layouts: the mesh-sharded fused engine is token-exact
    with the single-device per-token control on the full differential
    trace."""
    if shape[0] * shape[1] > NDEV:
        pytest.skip(f"mesh {shape} > {NDEV} devices")
    cfg, params = tiny
    want = _drive_differential(cfg, params, 11, fused=False,
                               page_size=8)
    mesh = jax.make_mesh(shape, ("data", "model"))
    got = _drive_differential(cfg, params, 11, mesh=mesh, fused=True,
                              page_size=8)
    assert got[:3] == want[:3]
    assert got[3].kv.reloaded_blocks >= 1     # reload ran on the mesh
