"""The serving loop's own spans (``serving/tracing.py``), its always-on
counters and turn stamps, and the reload charged once on the wall clock.

Spans are recorded through a fake ``jax.profiler.TraceAnnotation`` that
keeps the stack of open spans, so nesting is checked without a
profiler."""
import asyncio
import time

import jax
import numpy as np
import pytest

from repro.core.scheduler import RoundBudget, SchedulerConfig, \
    UrgencyScheduler
from repro.core.session import Request
from repro.serving import tracing
from repro.serving.gateway import (GatewayConfig, RealtimeGateway,
                                   ScaledWallClock, SpeechEnd,
                                   SpeechStart, TurnRequest)
from repro.serving.gateway.harness import tiny_model
from repro.serving.paged_engine import PagedRealtimeEngine

ROUND_CHILDREN = {"serve.xfer.drain", "serve.round.feeds",
                  "serve.round.tables", "serve.round.launch",
                  "serve.round.logits", "serve.round.sample"}


@pytest.fixture(scope="module")
def tiny():
    return tiny_model()


class Recorder:
    """Stands in for ``TraceAnnotation``: logs (name, parent, ids)."""

    def __init__(self):
        self.stack, self.spans = [], []
        rec = self

        class Annotation:
            def __init__(self, name, **ids):
                self.name, self.ids = name, ids

            def __enter__(self):
                rec.spans.append((self.name,
                                  rec.stack[-1] if rec.stack else None,
                                  self.ids))
                rec.stack.append(self.name)

            def __exit__(self, *exc):
                assert rec.stack.pop() == self.name
        self.Annotation = Annotation

    def children(self, parent):
        return {n for n, p, _ in self.spans if p == parent}

    def named(self, name):
        return [(p, ids) for n, p, ids in self.spans if n == name]


@pytest.fixture
def recorder(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", r.Annotation)
    tracing.enable(True)
    try:
        yield r
    finally:
        tracing.enable(False)
    assert not r.stack


def test_span_off_is_one_shared_noop(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("built a TraceAnnotation with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    tracing.enable(False)
    a = tracing.span("serve.round", slots=3, tokens=7)
    assert a is tracing.span("serve.idle", reason="empty")
    with a:
        with tracing.span("serve.round.launch"):
            pass


def test_span_on_is_a_trace_annotation(recorder):
    with tracing.span("serve.round", slots=3, tokens=7):
        with tracing.span("serve.round.launch", B=4, Q=1, valid=3):
            pass
    assert recorder.spans == [
        ("serve.round", None, {"slots": 3, "tokens": 7}),
        ("serve.round.launch", "serve.round", {"B": 4, "Q": 1, "valid": 3})]


@pytest.mark.parametrize("fused", [True, False])
def test_round_opens_the_documented_spans(tiny, recorder, fused):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                              pages_per_seq=16, num_pages=32,
                              fused_step=fused)
    slot = eng.submit_turn("a", rng.integers(0, cfg.vocab_size, size=6),
                           max_new_tokens=3)
    eng.run_round({slot: 6})                   # prefill, first token
    eng.run_round({slot: 1})                   # one decode token
    assert recorder.named("serve.admit") == [(None,
                                              {"sid": "a", "turn": 0})]
    rounds = recorder.named("serve.round")
    assert [ids for _, ids in rounds] == [{"slots": 1, "tokens": 6},
                                          {"slots": 1, "tokens": 1}]
    assert recorder.children("serve.round") == ROUND_CHILDREN
    for name in ROUND_CHILDREN - {"serve.xfer.drain"}:
        assert recorder.children(name) == set(), name
    launches = [ids for _, ids in recorder.named("serve.round.launch")]
    if fused:
        # one launch a round: the whole prompt, then one token
        assert launches == [{"B": 2, "Q": 8, "valid": 6},
                            {"B": 2, "Q": 1, "valid": 1}]
    else:
        assert launches == [{"B": 2, "Q": 1, "valid": 1}] * 7
    assert eng.launch_valid_tokens == 7
    assert eng.launch_grid_tokens == sum(
        x["B"] * x["Q"] for x in launches)


def test_attention_block_counters_match_a_hand_count(tiny):
    """``attn_blocks_live``/``attn_blocks_grid`` count the kernel's KV
    blocks: 8-token pages stack into the rule's 128-token blocks, so a
    320-token context is 3 blocks a row and the grid is slots x 3."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=8,
                              pages_per_seq=40, num_pages=64)
    assert eng._attn_block == 128
    a = eng.submit_turn("a", rng.integers(0, cfg.vocab_size, size=140),
                        max_new_tokens=3)
    b = eng.submit_turn("b", rng.integers(0, cfg.vocab_size, size=10),
                        max_new_tokens=3)
    eng.run_round({a: 140, b: 10})    # 140 tokens: 2 blocks; 10: 1
    assert (eng.attn_blocks_live, eng.attn_blocks_grid) == (3, 6)
    eng.run_round({a: 1, b: 1})       # 141 tokens: 2 blocks; 11: 1
    assert (eng.attn_blocks_live, eng.attn_blocks_grid) == (6, 12)


def _reload_on_path(eng, rng, cfg):
    """Session "a" finishes a turn, loses two pages to DRAM, and comes
    back: its next admission must reload them on the turn's path."""
    eng.add_session("a", rng.integers(0, cfg.vocab_size, size=10),
                    max_new_tokens=6)
    eng.run_to_completion()
    assert eng.kv.evict(2, eng.clock.now()) == 2
    eng.flush_transfers()
    assert len(eng.pool.seq("a").offloaded) == 2


def test_settlement_spans_nest_under_the_admission(tiny, recorder):
    cfg, params = tiny
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                              pages_per_seq=16, num_pages=32)
    _reload_on_path(eng, np.random.default_rng(1), cfg)
    assert recorder.named("serve.kv.evict") == [(None, {"pages": 2})]
    # flushed outside any round
    assert [p for p, _ in recorder.named("serve.kv.offload_chunk")] \
        == [None]
    eng.submit_turn("a", np.arange(4), max_new_tokens=2)
    assert recorder.named("serve.kv.settle") == [
        ("serve.admit", {"sid": "a", "pages": 2})]
    assert {p for p, _ in recorder.named("serve.kv.reload_chunk")} \
        == {"serve.kv.settle"}
    assert eng.settled_reload_pages == 2 and eng.drained_reload_pages == 0


def test_on_path_reload_is_charged_once_on_the_wall_clock(tiny):
    """The live gateway's clock already spent the settlement: it keeps
    a zero offset, and the turn's record carries the measured seconds
    beside the modelled stall."""
    cfg, params = tiny
    clock = ScaledWallClock(1.0)
    # a slow modelled channel: the reload's residual outlasts the wall
    # time the settlement takes, so the model charges a stall
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                              pages_per_seq=16, num_pages=32, clock=clock,
                              pcie_gb_s=1e-4)
    gw = RealtimeGateway(eng, cfg=GatewayConfig(round_token_budget=16,
                                                prefill_chunk=16))
    rng = np.random.default_rng(1)
    _reload_on_path(eng, rng, cfg)
    gw.connect("a")
    gw._sessions["a"].turn_no = 0            # turn 0 ran on the engine
    gw._handle(TurnRequest("a", prompt=rng.integers(0, cfg.vocab_size,
                                                    size=4),
                           max_new_tokens=3, t_wall=time.perf_counter()))
    assert gw._round()
    rec = gw._recs[("a", 1)]
    assert rec.reload_stall_s > 0.0          # the channel's model
    assert rec.reload_on_path_wall_s > 0.0   # what the settlement took
    assert clock._offset == 0.0
    assert eng.settled_reload_pages == 2


def test_on_path_reload_still_charges_a_virtual_clock(tiny):
    cfg, params = tiny
    eng = PagedRealtimeEngine(cfg, params, slots=2, page_size=4,
                              pages_per_seq=16, num_pages=32)
    _reload_on_path(eng, np.random.default_rng(1), cfg)
    t0 = eng.clock.now()
    eng.submit_turn("a", np.arange(4), max_new_tokens=2)
    stall = eng.sessions["a"].reload_stall_s
    assert stall > 0.0
    assert eng.clock.now() == pytest.approx(t0 + stall)


# ----------------------------------------------------------- counters
def _req(sid, prompt_len, *, bound=False):
    r = Request(session_id=sid, stage="thinker", turn_index=0,
                arrival_time=0.0, prompt_len=prompt_len)
    r.slot_bound = bound
    return r


class _NoTelemetry:
    def playback_buffer_s(self, sid):
        return None

    def view(self, sid):
        return None


def test_schedule_decision_records_why_a_turn_waits():
    s = UrgencyScheduler(SchedulerConfig(), _NoTelemetry(),
                         stage="thinker", prefill_chunk=8)
    a, b, c = _req("a", 8), _req("b", 8), _req("c", 8)
    d = s.schedule([a, b, c], RoundBudget(token_budget=12,
                                          free_kv_blocks=100,
                                          free_slots=3), now=1.0)
    # a fits; b breaks the budget, and c, behind it, is never reached
    assert [r.req_id for r in d.batch] == [a.req_id]
    assert d.waiting == {b.req_id: "budget", c.req_id: "budget"}
    d = s.schedule([a, b], RoundBudget(token_budget=64,
                                       free_kv_blocks=100,
                                       free_slots=1), now=1.0)
    assert d.waiting == {b.req_id: "slot"}


def _gateway(tiny, **cfg):
    model_cfg, params = tiny
    eng = PagedRealtimeEngine(model_cfg, params, slots=2, page_size=4,
                              pages_per_seq=16, num_pages=32,
                              clock=ScaledWallClock(1.0))
    return RealtimeGateway(eng, cfg=GatewayConfig(**cfg))


def _request(gw, sid, n, max_new=3):
    if sid not in gw._sessions:
        gw.connect(sid)
    gw._handle(TurnRequest(sid, prompt=np.arange(n), max_new_tokens=max_new,
                           t_wall=time.perf_counter()))


def test_admission_counters(tiny):
    gw = _gateway(tiny, round_token_budget=9, prefill_chunk=8)
    _request(gw, "a", 8)
    _request(gw, "b", 8)
    _request(gw, "c", 8)
    counts = []
    for _ in range(3):
        gw._round()
        counts.append((gw.queued_rounds, gw.budget_held_rounds,
                       gw.slot_held_rounds))
    # 1: a binds with 8 of the 9 tokens, b and c wait behind the break;
    # 2: a prefills its 8, so they wait again; 3: a decodes one token,
    # b binds the last slot, and c waits only for want of a slot
    assert counts == [(1, 1, 0), (2, 2, 0), (3, 2, 1)]
    assert gw.requeued_turns == 0
    assert set(gw._pending) == {"c"}


def test_idle_reasons(tiny):
    gw = _gateway(tiny, round_token_budget=16, prefill_chunk=16,
                  frontier_cap_s=0.5, audio_per_token_s=1.0)
    assert gw._idle_reason() == "empty"
    _request(gw, "a", 4, max_new=20)
    assert gw._round()                       # admitted
    assert gw._round()                       # prefilled: the first token
    # a second of audio queued against a 0.5 s cap: the one live slot
    # decodes past the frontier, so the round runs nothing
    assert not gw._round()
    assert gw._idle_reason() == "frontier"
    _request(gw, "b", 4)
    assert gw._idle_reason() == "held"


def test_serve_loop_waits_and_stamps_turns(tiny, recorder):
    """Through the asyncio loop: an idle wait is counted and spanned
    with its reason, every inbox event is spanned by kind, and a turn's
    stamps are ordered from the SpeechEnd to the first packet."""
    gw = _gateway(tiny, round_token_budget=16, prefill_chunk=16,
                  idle_sleep_s=0.01)

    async def main():
        serve = asyncio.create_task(gw.run())
        h = gw.connect("a")
        await asyncio.sleep(0.05)
        await h.send(SpeechStart("a"))
        await h.send(SpeechEnd("a"))
        await h.send(TurnRequest("a", prompt=np.arange(5),
                                 max_new_tokens=3))
        while True:
            ev = await h.recv()
            if type(ev).__name__ == "TurnDone":
                break
        gw.stop()
        await serve

    asyncio.run(main())
    assert gw.idle_waits["empty"] > 0
    assert {ids["reason"] for _, ids in recorder.named("serve.idle")} \
        >= {"empty"}
    kinds = [ids["kind"] for _, ids in recorder.named("serve.handle")]
    assert kinds == ["SpeechStart", "SpeechEnd", "TurnRequest"]
    assert recorder.named("serve.admit")[0][0] == "serve.schedule"
    assert {p for p, _ in recorder.named("serve.dispatch")} == {None}
    rec = gw._recs[("a", 0)]
    stamps = [rec.wall_speech_end, rec.wall_request, rec.wall_handled,
              rec.wall_admitted, rec.wall_first_packet]
    assert all(t is not None for t in stamps)
    assert stamps == sorted(stamps)
    assert rec.ttfp_wall_s == rec.wall_first_packet - rec.wall_speech_end
    s = gw.metrics().summary()
    assert s["p50_ttfp_wall"] == s["p90_ttfp_wall"] == rec.ttfp_wall_s
