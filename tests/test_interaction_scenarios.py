"""Full-duplex / agentic scenario suite (ISSUE 9).

Three new session shapes exercise the interaction plane end to end:

- **duplex**  — periodic-frame sessions: the turn request fires at
  speech onset and every output token carries a hard frame deadline
  (armed at the request, advancing one period per emitted frame);
- **toolcall** — agentic sessions whose turns end in a tool call: the
  session idles with hot KV (its own protection state, distinct from
  the preload TTL) and resumes without a new utterance or re-prefill;
- **handoff** — sessions that request a transfer to a different model
  config between turns, riding the fleet MIGRATE machinery as a
  targeted plan.

Unit tests cover the satellite bugfixes (burstgpt mean conservation,
preload double-speech-start merge, monitor staleness) and the
scheduler's frame-deadline urgency/pacing interplay. Scenario smokes
replay each shape through the virtual-time twin; live-vs-twin
differentials run one small example per shape in the fast lane with
seeded sweeps behind ``-m slow`` — same comparison discipline as
tests/test_differential.py (trace-determined outcomes, never
wall-clock latencies).
"""
import jax
import numpy as np
import pytest

from repro.core.kv_manager import KVManager
from repro.core.monitor import RuntimeMonitor
from repro.core.preload import Preloader
from repro.core.scheduler import (RoundBudget, SchedulerConfig,
                                  UrgencyScheduler)
from repro.core.session import Phase, Request
from repro.serving.workload import (TOOL_RESUME_GAP_S, WorkloadConfig,
                                    _burst_wave, generate)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def now(self):
        return self.t


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config, reduced
    from repro.models import init_params
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                  vocab=331)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ======================================================================
# burstgpt arrivals: the peak/mean contract (satellite bugfix)
# ======================================================================
def _empirical_rate(cfg: WorkloadConfig) -> float:
    times = [s.arrival_time for s in generate(cfg)]
    return len(times) / times[-1]


@pytest.mark.parametrize("bf", [1.5, 2.0, 4.0, 8.0])
def test_burst_wave_mean_identity(bf):
    """duty*peak + (1-duty)*off == rate_rps exactly, for every
    burst_factor — including bf > 1/0.3 where the nominal 0.3 duty
    would have needed a negative off-phase rate (the old clamp-to-0.1
    bug inflated the mean ~27% at bf=4)."""
    cfg = WorkloadConfig(arrival="burstgpt", rate_rps=2.0,
                         burst_factor=bf)
    duty, peak, off = _burst_wave(cfg)
    assert duty * peak + (1.0 - duty) * off \
        == pytest.approx(cfg.rate_rps)
    assert off >= 0.0
    assert peak == pytest.approx(cfg.rate_rps * bf)


@pytest.mark.parametrize("bf", [2.0, 4.0])
def test_burstgpt_empirical_mean_conserved(bf):
    """Regression: at burst_factor=4 the off-phase clamp used to push
    the empirical mean to ~1.27x rate_rps. The hazard-integrated draw
    must keep it within 5% (ISSUE 9 acceptance)."""
    cfg = WorkloadConfig(kind="sharegpt", arrival="burstgpt",
                         rate_rps=2.0, burst_factor=bf,
                         num_sessions=4000, seed=3)
    rate = _empirical_rate(cfg)
    assert abs(rate - cfg.rate_rps) / cfg.rate_rps < 0.05, rate


def test_burstgpt_still_bursty_and_deterministic():
    """The fix must not flatten the process: interarrival CV stays
    well above Poisson's 1.0, and the same seed reproduces the same
    arrival times exactly."""
    cfg = WorkloadConfig(kind="sharegpt", arrival="burstgpt",
                         rate_rps=2.0, burst_factor=4.0,
                         num_sessions=2000, seed=5)
    times = np.array([s.arrival_time for s in generate(cfg)])
    gaps = np.diff(times)
    cv = gaps.std() / gaps.mean()
    assert cv > 1.3, cv
    times2 = np.array([s.arrival_time for s in generate(cfg)])
    assert np.array_equal(times, times2)


def test_duplex_trace_shape():
    """Duplex turns carry a frame period and never barge (the user
    holds the channel); the other kinds stay frame-free."""
    cfg = WorkloadConfig(kind="duplex", num_sessions=50, seed=0,
                         p_barge_in=0.9)
    turns = [t for s in generate(cfg) for t in s.turns]
    assert all(2.0 <= t.frame_period_tokens <= 4.0 for t in turns)
    assert not any(t.barge_in for t in turns)
    cfg2 = WorkloadConfig(kind="interactive", num_sessions=20, seed=0)
    assert all(t.frame_period_tokens == 0.0
               for s in generate(cfg2) for t in s.turns)


def test_toolcall_and_handoff_trace_shapes():
    tc = WorkloadConfig(kind="toolcall", num_sessions=50, seed=1)
    tool_turns = [t for s in generate(tc) for t in s.turns if t.tool_call]
    assert tool_turns
    assert all(0.8 <= t.tool_latency_s <= 8.0 for t in tool_turns)
    # the last turn of a session never starts a tool pause
    for s in generate(tc):
        assert not s.turns[-1].tool_call
    ho = WorkloadConfig(kind="handoff", num_sessions=50, seed=1)
    hand = [t for s in generate(ho) for t in s.turns if t.handoff]
    assert hand
    assert all(0 <= t.handoff_target < 8 for t in hand)
    # a session's first turn has no committed context to hand off
    for s in generate(ho):
        assert not s.turns[0].handoff


# ======================================================================
# monitor: staleness fixes + the new interaction events
# ======================================================================
def test_turn_start_clears_stale_speech_state():
    """Regression: a turn that starts with no SpeechEnd (duplex,
    tool-resume) used to leave ``speaking``/``expected_speech_end``
    from the previous utterance — Eq. 4 then read a stale estimate and
    immediate_reuse protected an idle session forever."""
    clock = FakeClock(0.0)
    mon = RuntimeMonitor(clock)
    mon.on_speech_start("a", expected_dur_s=4.0)
    v = mon.view("a")
    assert v.speaking and v.expected_speech_end == 4.0
    clock.t = 1.0
    mon.on_turn_start("a", 0)          # no SpeechEnd ever arrived
    assert not v.speaking
    assert v.expected_speech_end is None
    assert v.tool_call_until is None
    assert not mon.immediate_reuse("a")


def test_frame_deadline_lifecycle():
    clock = FakeClock(10.0)
    mon = RuntimeMonitor(clock)
    mon.on_frame_turn("a", 2.0)
    v = mon.view("a")
    assert v.frame_period_s == 2.0
    assert v.frame_deadline == 12.0
    # admission (on_turn_start) fires AFTER the request armed the
    # deadline: it must not clear it, or queueing delay would be
    # exempt from miss accounting
    mon.on_turn_start("a", 0)
    assert v.frame_deadline == 12.0
    mon.on_response_complete("a")
    assert v.frame_deadline is None
    mon.on_frame_turn("a", 2.0)
    mon.on_barge_in("a")
    assert v.frame_deadline is None
    assert v.frame_period_s == 2.0     # period is sticky (duplex mark)


def test_tool_call_events_skip_the_reply_gap_ema():
    """Tool latencies are not think time: the pause events must leave
    the reply-gap EMA alone while opening/closing the tool window."""
    clock = FakeClock(0.0)
    mon = RuntimeMonitor(clock)
    v = mon.register("a")
    v.last_playback_end = 0.0
    v.reply_gap_ema = 1.5
    clock.t = 5.0
    mon.on_tool_call_start("a", 3.0)
    assert v.tool_call_until == 8.0
    assert not v.speaking and v.expected_speech_end is None
    assert v.reply_gap_ema == 1.5
    clock.t = 8.0
    mon.on_tool_call_result("a", resume_gap_s=TOOL_RESUME_GAP_S)
    assert v.tool_call_until is None
    # the result opens a preload window exactly one resume gap wide
    assert v.expected_speech_end == pytest.approx(8.0 + TOOL_RESUME_GAP_S)
    assert v.reply_gap_ema == 1.5


# ======================================================================
# KV manager: tool-pause protection (distinct from the preload TTL)
# ======================================================================
def _kv(monitor=None, clock=None, capacity=100):
    clock = clock or FakeClock()
    return KVManager(capacity_blocks=capacity, block_size=16,
                     bytes_per_token=1024.0, monitor=monitor,
                     clock=clock), clock


def _resident(kv, sid, blocks):
    s = kv.session(sid)
    s.total_blocks = blocks
    s.hbm_blocks = blocks
    return s


def test_tool_protection_blocks_eviction_until_expiry():
    mon = RuntimeMonitor(FakeClock(0.0))
    mon.register("a")
    kv, clock = _kv(monitor=mon)
    _resident(kv, "a", 10)
    kv.protect_tool("a", 0.0, expected_latency_s=5.0)
    assert kv.evict(4, 1.0) == 0                # mid-pause: held
    assert kv.evict(4, 5.5) == 4                # tool window lapsed
    assert kv.session("a").hbm_blocks == 6


def test_tool_protection_ttl_caps_runaway_tools():
    kv, _ = _kv()
    kv.tool_protect_ttl_s = 2.0
    _resident(kv, "a", 10)
    kv.protect_tool("a", 0.0, expected_latency_s=500.0)
    assert kv.session("a").tool_protected_until == 2.0
    assert kv.evict(4, 1.0) == 0
    assert kv.evict(4, 2.5) == 4                # TTL beat the tool


def test_clear_tool_protection_lifts_hold():
    kv, _ = _kv()
    _resident(kv, "a", 10)
    kv.protect_tool("a", 0.0, expected_latency_s=50.0)
    assert kv.evict(4, 1.0) == 0
    kv.clear_tool_protection("a", 1.0)
    assert kv.evict(4, 1.0) == 4


def test_next_use_reads_tool_pause_window():
    """Eq. 4 during a pause: next use is the tool's expected return,
    not playback + reply gap; after the window it falls back."""
    clock = FakeClock(0.0)
    mon = RuntimeMonitor(clock)
    v = mon.register("a")
    v.reply_gap_ema = 2.0
    kv, _ = _kv(monitor=mon, clock=clock)
    _resident(kv, "a", 10)
    mon.on_tool_call_start("a", 6.0)
    assert kv.next_use_estimate("a", 1.0) == 6.0
    assert kv.next_use_estimate("a", 7.0) == pytest.approx(9.0)


# ======================================================================
# preloader: double speech-start merges, never orphans (satellite)
# ======================================================================
def test_double_speech_start_merges_pending_preload():
    """Regression: speech -> barge-in before the turn arrived used to
    overwrite the first PendingPreload, orphaning its transfer — the
    settlement then credited only the second transfer's span. Both
    admissions must fold into one entry whose blocks and span cover
    both transfers."""
    clock = FakeClock(0.0)
    mon = RuntimeMonitor(clock)
    v = mon.register("a")
    v.playback.started = True
    v.playback.play_end = 0.0
    v.reply_gap_ema = 1.0
    kv, _ = _kv(monitor=mon)
    s = _resident(kv, "a", 20)
    s.hbm_blocks = 0                             # fully offloaded
    pre = Preloader(kv, mon, speech_prior_s=6.0)
    mon.on_speech_start("a", expected_dur_s=6.0)
    t1 = pre.on_speech_start("a", 0.0)
    assert t1 is not None
    # barge-in: part of the resident reply KV leaves again before the
    # second trigger fires (pool churn), so the re-trigger re-admits
    s.hbm_blocks = 10
    kv.reloaded_blocks -= 10
    clock.t = 1.0
    mon.on_speech_start("a", expected_dur_s=6.0)
    t2 = pre.on_speech_start("a", 1.0)
    assert t2 is not None and t2 is not t1
    p = pre.pending["a"]
    assert p.blocks == t1.blocks + t2.blocks
    assert p.span_s == pytest.approx((t1.done - t1.start)
                                     + (t2.done - t2.start))
    # the later-finishing transfer anchors the settlement
    assert p.transfer is (t2 if t2.done >= t1.done else t1)
    # warm hit after both landed: the off-path credit covers BOTH
    # transfers' seconds (the orphaned-transfer bug dropped t1's)
    clock.t = max(t1.done, t2.done) + 0.1
    assert pre.on_turn_ready("a", clock.t) == 0.0
    assert pre.stats.hits == 1
    on_s, off_s = pre.pop_split("a")
    assert on_s == 0.0
    assert off_s == pytest.approx(p.span_s)


def test_duplex_preload_window_is_one_frame_period():
    """A duplex session has no speech window (the request fires at
    onset): preload admission gets exactly one frame period to hide
    in, and a transfer that cannot is refused."""
    clock = FakeClock(0.0)
    mon = RuntimeMonitor(clock)
    v = mon.register("a")
    v.frame_period_s = 0.5
    kv, _ = _kv(monitor=mon)
    s = _resident(kv, "a", 20)
    s.hbm_blocks = 0
    pre = Preloader(kv, mon, speech_prior_s=30.0)
    t = pre.on_speech_start("a", 0.0)            # tiny transfer: fits
    assert t is not None
    pre.forget_session("a")
    big = _resident(kv, "b", 10 ** 6)
    big.hbm_blocks = 0
    mon.register("b").frame_period_s = 0.5
    assert pre.on_speech_start("b", 0.0) is None # cannot hide in frame
    assert pre.stats.skipped == 1


# ======================================================================
# scheduler: frame deadlines vs pacing (tentpole + test satellite)
# ======================================================================
def _duplex_setup(buffers, frames, *, p_safe=1.0, p_max=3.0, occ=0.0):
    """buffers: sid -> playback buffer s; frames: sid -> (period,
    deadline) armed on the view."""
    clock = FakeClock(100.0)
    mon = RuntimeMonitor(clock)
    for sid, buf in buffers.items():
        mon.register(sid)
        v = mon.view(sid)
        v.playback.started = True
        v.playback.play_end = clock.t + buf
        v.playback.appended_s = buf + 5.0
    for sid, (period, deadline) in frames.items():
        v = mon.register(sid)
        v.frame_period_s = period
        v.frame_deadline = deadline
    cfg = SchedulerConfig(p_safe_s=p_safe, p_max_s=p_max)
    return UrgencyScheduler(cfg, mon, stage="talker",
                            kv_occupancy=lambda: occ), clock


def _decode_req(sid):
    r = Request(session_id=sid, stage="talker", turn_index=0,
                arrival_time=0.0, prompt_len=0, max_new_tokens=100)
    r.phase = Phase.DECODE
    r.generated = 5
    r.first_output_time = 0.0
    return r


def test_frame_slack_promotes_to_u0():
    """A frame due within P_safe outranks a healthy buffer: the session
    joins U0 keyed by slack, ahead of buffer-keyed U0 peers with more
    seconds until trouble."""
    sched, clock = _duplex_setup(
        {"dup": 2.0, "low": 0.8, "easy": 2.0},
        {"dup": (2.0, clock_t := 100.5)})       # slack 0.5 < buffer 0.8
    reqs = {s: _decode_req(s) for s in ("dup", "low", "easy")}
    budget = RoundBudget(token_budget=4096, free_kv_blocks=10 ** 6)
    d = sched.schedule(list(reqs.values()), budget, clock.now())
    assert [r.session_id for r in d.batch] == ["dup", "low", "easy"]
    assert d.classes[reqs["dup"].req_id] == 0
    assert d.classes[reqs["easy"].req_id] == 2


def test_far_frame_deadline_does_not_promote():
    sched, clock = _duplex_setup({"dup": 2.0},
                                 {"dup": (10.0, 100.0 + 8.0)})
    r = _decode_req("dup")
    budget = RoundBudget(token_budget=4096, free_kv_blocks=10 ** 6)
    d = sched.schedule([r], budget, clock.now())
    assert d.classes[r.req_id] == 2             # slack 8 > p_safe: normal


def test_hold_wake_bounded_by_frame_slack():
    """A pace-held duplex session bounds the driver's sleep: it must be
    back before the frame slack shrinks to P_safe, not merely when the
    buffer drains to P_max."""
    sched, clock = _duplex_setup({"dup": 10.0},
                                 {"dup": (3.0, 100.0 + 2.5)})
    r = _decode_req("dup")
    budget = RoundBudget(token_budget=4096, free_kv_blocks=10 ** 6)
    d = sched.schedule([r], budget, clock.now())
    assert [q.session_id for q, _ in d.held] == ["dup"]
    # buffer-only wake would be 10 - 3 = 7s; the frame bound is
    # 2.5 - 1.0 = 1.5s and must win
    assert sched.hold_wake_s(d) == pytest.approx(7.0)
    assert sched.hold_wake_s(d, now=clock.now()) == pytest.approx(1.5)


def test_pacing_never_causes_a_frame_miss():
    """Deterministic sweep (ISSUE 9 satellite): for every (buffer,
    period, slack) shape, a held periodic-frame session is either
    promoted to U0 before its deadline ever arrives, or the hold wake
    lands early enough that classify promotes it with >= 0 slack —
    pacing alone can never turn into a deadline miss when
    pacing_kv_override is not tripped."""
    p_safe, p_max = 1.0, 3.0
    for buf in (3.1, 4.0, 6.0, 10.0):
        for period in (0.5, 1.0, 2.0, 4.0):
            for slack in (0.2, 0.8, 1.5, 3.0, 6.0):
                sched, clock = _duplex_setup(
                    {"dup": buf}, {"dup": (period, 100.0 + slack)},
                    p_safe=p_safe, p_max=p_max)
                r = _decode_req("dup")
                budget = RoundBudget(token_budget=4096,
                                     free_kv_blocks=10 ** 6)
                d = sched.schedule([r], budget, clock.now())
                shape = (buf, period, slack)
                if slack <= p_safe:
                    # due soon: promoted past pacing outright
                    assert d.batch and d.classes[r.req_id] == 0, shape
                    continue
                assert [q.session_id for q, _ in d.held] == ["dup"], shape
                wake = sched.hold_wake_s(d, now=clock.now())
                # woken while the frame still has >= P_safe slack
                # (0.01s floor keeps the driver from busy-spinning)
                assert wake <= max(0.01, slack - p_safe) + 1e-9, shape
                clock.t += wake
                d2 = sched.schedule([r], budget, clock.now())
                mon_v = sched.monitor.view("dup")
                if mon_v.frame_deadline - clock.now() <= p_safe:
                    assert d2.batch, shape      # promoted, not held
                    assert mon_v.frame_deadline >= clock.now(), shape


# ======================================================================
# scenario smokes through the virtual-time twin (fast lane)
# ======================================================================
from repro.serving.gateway.replay import ReplayConfig, run_replay  # noqa: E402
from repro.serving.paged_engine import PagedRealtimeEngine  # noqa: E402

APT = 0.25


def _factory(tiny_model, num_pages=128):
    cfg, params = tiny_model

    def make(clock):
        return PagedRealtimeEngine(cfg, params, slots=2, page_size=8,
                                   pages_per_seq=8, num_pages=num_pages,
                                   clock=clock)
    return make


def _twin(tiny_model, kind, sessions, seed, *, barge=0.0):
    wl = WorkloadConfig(kind=kind, num_sessions=sessions, seed=seed,
                        p_barge_in=barge, arrival="poisson", rate_rps=4.0)
    return run_replay(_factory(tiny_model), wl,
                      ReplayConfig(audio_per_token_s=APT,
                                   frontier_cap_s=3.0), seed=seed)


def test_twin_duplex_smoke(tiny):
    m, gw = _twin(tiny, "duplex", 3, 0)
    s = m.summary()
    assert s["frames"] > 0
    assert 0.0 <= s["deadline_miss_rate"] <= 1.0
    # every duplex turn completes (no barge) and every emitted token
    # was a counted frame
    assert all(t.completed for t in m.turns)
    assert all(t.frames == t.talker_generated for t in m.turns)
    # deadlines disarm between turns: no view left armed at the end
    for v in gw.eng.monitor.sessions.values():
        assert v.frame_deadline is None
    # twin determinism: the comparison surface reproduces exactly
    m2, _ = _twin(tiny, "duplex", 3, 0)
    assert m.summary() == m2.summary()


def test_twin_toolcall_smoke(tiny):
    m, gw = _twin(tiny, "toolcall", 4, 0)
    s = m.summary()
    assert s["tool_pauses"] > 0
    resumed = [t for t in m.turns if t.tool_resumed]
    assert len(resumed) == s["tool_pauses"]
    # resume-without-reprefill: a generous pool + pause protection keep
    # the context hot, so no resumed turn paid a reload stall and the
    # engine never re-prefilled committed tokens
    assert all(t.reload_stall_s == 0.0 for t in resumed)
    assert all(t.completed or t.barged for t in m.turns)
    # no pause leaks protection past its resume
    now = gw.clock.now()
    for sid, skv in gw.eng.kv.sessions.items():
        assert skv.tool_protected_until <= now
    m2, _ = _twin(tiny, "toolcall", 4, 0)
    assert m.summary() == m2.summary()


# ======================================================================
# fleet handoff through the twin (fast lane)
# ======================================================================
from repro.serving.fleet.replay import run_fleet_replay  # noqa: E402

REPLICAS = 3


def _fleet_twin(tiny_model, kind, sessions, seed, *, barge=0.0):
    wl = WorkloadConfig(kind=kind, num_sessions=sessions, seed=seed,
                        p_barge_in=barge, arrival="poisson", rate_rps=2.0)
    return run_fleet_replay(
        _factory(tiny_model), REPLICAS, wl,
        ReplayConfig(max_prompt=6, max_response=6), seed=seed)


def _expected_handoffs(kind, sessions, seed, max_turns=2):
    """Trace-predictable handoff decisions: session i routes to
    i % REPLICAS; its turn-1 handoff lands iff target % REPLICAS is a
    different replica."""
    wl = WorkloadConfig(kind=kind, num_sessions=sessions, seed=seed,
                        p_barge_in=0.0, arrival="poisson", rate_rps=2.0)
    want = {}
    for i, s in enumerate(generate(wl)):
        src = i % REPLICAS
        for turn in s.turns[1:max_turns]:
            if turn.handoff and turn.handoff_target % REPLICAS != src:
                want[s.session_id] = [(src, turn.handoff_target
                                       % REPLICAS)]
    return want


def test_twin_handoff_smoke(tiny):
    sessions, seed = 6, 0
    m, gw = _fleet_twin(tiny, "handoff", sessions, seed)
    want = _expected_handoffs("handoff", sessions, seed)
    assert want, "seed produced no handoffs — pick another"
    got = {}
    for _, sid, src, dst in gw.router.handoff_decisions():
        got.setdefault(sid, []).append((src, dst))
    assert got == want
    # barge-free: every decided handoff ran to DONE as a kind='handoff'
    # plan, and the resumed turn is marked
    assert not gw.migrator.plans
    done = [p for p in gw.migrator.completed() if p.kind == "handoff"]
    assert len(done) == len(want)
    assert m.summary()["handoffs"] == len(want)
    assert {t.session_id for t in m.turns if t.handoff} == set(want)
    # a handoff is a migration underneath: placement flipped, source
    # scrubbed, and the shared migration accounting saw it
    for p in done:
        assert p.session_id not in gw.replicas[p.src].sessions
        assert p.session_id in gw.replicas[p.dst].sessions
    assert m.migrations >= len(want)
    for e in gw.replicas:
        e.flush_transfers()
        e.check_invariants()
        assert e.pool.free_pages == e.num_pages


def test_router_refuses_self_and_draining_handoffs():
    from repro.serving.fleet.router import SessionRouter

    class _Stub(list):
        clock = FakeClock()

        def live_slots(self, i):
            return 0

        def free_pages(self, i):
            return 100

    router = SessionRouter(_Stub([0, 1, 2]))
    router.route("a")                            # -> replica 0
    assert router.request_handoff("a", 3) is None        # 3 % 3 == src
    router.draining.add(1)
    assert router.request_handoff("a", 1) is None        # dst draining
    assert router.request_handoff("a", 2) == 2
    assert router.handoff_decisions() == [("handoff", "a", 0, 2)]


# ======================================================================
# live-vs-twin scenario differentials
# ======================================================================
from repro.serving.fleet.harness import run_fleet_workload  # noqa: E402
from repro.serving.gateway.harness import run_gateway_workload  # noqa: E402


def _outcomes(m):
    """Per-session ordered (turn, outcome, tool_resumed) lists — the
    trace-determined surface both planes must agree on."""
    per = {}
    for t in sorted(m.turns, key=lambda t: (t.session_id, t.turn_index)):
        per.setdefault(t.session_id, []).append(
            (t.turn_index, t.completed, t.barged, t.tool_resumed))
    return per


def check_scenario_differential(tiny_model, kind, sessions, seed,
                                barge=0.0):
    twin_m, twin = _twin(tiny_model, kind, sessions, seed, barge=barge)
    # clamps and engine geometry must match the twin's ReplayConfig
    # defaults exactly (max_prompt/max_response 6, page_size 8) or the
    # two planes serve different traces. The clock scale must keep an
    # interpreted CPU round well under a token's audio in scaled time:
    # at 40x a round outlasts it, so a barge cut can land before a reply
    # the twin completes, and the outcome depends on the host's speed
    live_m, live = run_gateway_workload(
        kind=kind, sessions=sessions, barge_in=barge, seed=seed,
        scale=10.0, max_turns=2, max_prompt=6, max_response=6,
        rate_rps=4.0, timeout_s=180.0, slots=2, page_size=8,
        pages_per_seq=8, num_pages=128, audio_per_token_s=APT,
        frontier_cap_s=3.0, model=tiny_model)
    assert set(twin_m.summary()) == set(live_m.summary())
    assert _outcomes(twin_m) == _outcomes(live_m)
    assert twin_m.tool_pauses == live_m.tool_pauses
    if kind == "duplex":
        # frames are trace-determined (duplex never barges: every turn
        # emits its full clamped token count, each token one frame);
        # misses are timing and deliberately NOT compared
        assert sum(t.frames for t in twin_m.turns) \
            == sum(t.frames for t in live_m.turns) > 0
    if kind == "toolcall" and barge == 0.0:
        # with barge-in on, a cut reply legitimately cancels its tool
        # pause, so a nonzero count is only trace-guaranteed barge-free
        assert twin_m.tool_pauses > 0
        assert {(t.session_id, t.turn_index)
                for t in twin_m.turns if t.tool_resumed} \
            == {(t.session_id, t.turn_index)
                for t in live_m.turns if t.tool_resumed}


def check_handoff_differential(tiny_model, sessions, seed, barge=0.0):
    twin_m, twin = _fleet_twin(tiny_model, "handoff", sessions, seed,
                               barge=barge)
    live_m, live = run_fleet_workload(
        kind="handoff", sessions=sessions, barge_in=barge, seed=seed,
        scale=40.0, max_turns=2, max_prompt=6, max_response=6,
        timeout_s=180.0, replicas=REPLICAS, slots=2, num_pages=128,
        audio_per_token_s=0.25, model=tiny_model)
    assert set(twin_m.summary()) == set(live_m.summary())

    def per_session(gw):
        per = {}
        for _, sid, src, dst in gw.router.handoff_decisions():
            per.setdefault(sid, []).append((src, dst))
        return per

    assert per_session(twin) == per_session(live)
    assert sorted(twin.router.handoff_decisions()) \
        == sorted(live.router.handoff_decisions())
    if barge == 0.0:
        want = _expected_handoffs("handoff", sessions, seed)
        assert per_session(twin) == want
        for gw, m in ((twin, twin_m), (live, live_m)):
            assert not gw.migrator.plans and not gw.migrator.cancelled()
            assert m.handoffs == len(want)
            assert {t.session_id for t in m.turns if t.handoff} \
                == set(want)
    for gw in (twin, live):
        for e in gw.replicas:
            e.flush_transfers()
            e.check_invariants()
            assert e.pool.free_pages == e.num_pages


# one small example per scenario stays in the fast lane
def test_duplex_differential_smoke(tiny):
    check_scenario_differential(tiny, "duplex", 3, 0)


def test_toolcall_differential_smoke(tiny):
    check_scenario_differential(tiny, "toolcall", 3, 0)


def test_handoff_differential_smoke(tiny):
    check_handoff_differential(tiny, 6, 0)


# seeded soaks ride the slow marker
SOAKS = [(kind, sessions, seed, barge)
         for seed in range(3)
         for kind, sessions, barge in (("duplex", 4, 0.0),
                                       ("toolcall", 5, 0.0),
                                       ("toolcall", 4, 0.5))]


@pytest.mark.slow
@pytest.mark.parametrize("kind,sessions,seed,barge", SOAKS)
def test_scenario_differential_soak(tiny, kind, sessions, seed, barge):
    check_scenario_differential(tiny, kind, sessions, seed, barge)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("barge", [0.0, 0.5])
def test_handoff_differential_soak(tiny, seed, barge):
    check_handoff_differential(tiny, 6, seed, barge)
