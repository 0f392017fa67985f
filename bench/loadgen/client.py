"""In-process voice users against a ``RealtimeGateway``, timed by the
client on the host's monotonic clock.

The turn loop is a copy of ``_drive_session`` in
``src/repro/serving/gateway/client.py`` (commit 1a6d178), kept to its
``interactive`` path: speak (SpeechStart, UserAudio, SpeechEnd), send
the TurnRequest, play the AudioChunks into a playback estimate, barge
in at the script's cut (anchored at the first packet), listen to the
rest, think, speak again. Two changes:

- every time is read from ``time.perf_counter`` and every wait is an
  ``asyncio.sleep`` in real seconds, never from the gateway's clock
  (which also advances by modelled reload stalls);
- a fixed population of users runs inside a measured window: each
  user works through its sessions, hangs up when the next turn would
  overflow the context, and starts its next session at once; after the
  window closes no user sends another turn.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from repro.serving.gateway.events import (AudioChunk, BargeIn, Hangup,
                                          SessionClosed, SpeechEnd,
                                          SpeechStart, TurnDone,
                                          TurnRequest, UserAudio)


@dataclass
class TurnLog:
    user: int
    session_id: str
    turn: int                        # index within the session's turns
    ctx_before: int                  # committed tokens before the turn
    prompt: np.ndarray
    max_new_tokens: int
    t_speech_end: float = 0.0
    t_send: float = 0.0
    t_first: Optional[float] = None
    t_finish: Optional[float] = None   # delivered, or barged after packet
    tokens: List[int] = field(default_factory=list)
    play_starts: List[float] = field(default_factory=list)
    completed: bool = False          # TurnDone(aborted=False) received
    barged: bool = False
    played_at_barge: Optional[int] = None

    @property
    def fed(self) -> int:
        """Tokens of the reply the server committed to the context: all
        of a completed reply; of a cut one, all but the last token,
        which was emitted but never fed back."""
        if self.completed:
            return len(self.tokens)
        return max(0, len(self.tokens) - 1)

    @property
    def wasted(self) -> int:
        if self.played_at_barge is None:
            return 0
        return max(0, len(self.tokens) - self.played_at_barge)


@dataclass
class Window:
    """Open and close instants, host monotonic seconds."""
    t_open: Optional[float] = None
    t_close: Optional[float] = None

    def closed(self, now: float) -> bool:
        return self.t_close is not None and now >= self.t_close


@dataclass
class ClientLog:
    turns: List[TurnLog] = field(default_factory=list)
    speech_late_s: List[float] = field(default_factory=list)
    sessions: dict = field(default_factory=dict)   # sid -> history tokens
    exhausted_users: int = 0


def session_id(user: int, k: int) -> str:
    return f"u{user:03d}.{k}"


async def drive_user(gateway, pos: int, user, pop, *, max_context: int,
                     window: Window, log: ClientLog, vocab: int,
                     seed: int) -> None:
    """One user of the population: its sessions, one after another."""
    apt = pop.audio_per_token_s
    rng = np.random.default_rng([seed, 2, pos])
    await asyncio.sleep(user.phase * pop.ramp_s)
    for k, sess in enumerate(user.sessions):
        sid = session_id(pos, k)
        ctx = log.sessions.get(sid, {}).get("history_len", 0)
        handle = gateway.connect(sid)
        for ti, turn in enumerate(sess.turns):
            prompt = rng.integers(0, vocab, size=turn.prompt_len) \
                .astype(np.int32)
            n_tokens = turn.reply_tokens
            if ctx + turn.prompt_len + n_tokens > max_context:
                break                        # hang up: context is full
            t0 = time.perf_counter()
            if window.closed(t0):
                await _hangup(handle, sid)
                return
            await handle.send(SpeechStart(sid, expected_dur_s=turn.speech_s))
            await handle.send(UserAudio(sid, dur_s=turn.speech_s))
            await asyncio.sleep(turn.speech_s)
            now = time.perf_counter()
            log.speech_late_s.append(now - (t0 + turn.speech_s))
            if window.closed(now):
                await _hangup(handle, sid)
                return
            rec = TurnLog(user=pos, session_id=sid, turn=ti,
                          ctx_before=ctx, prompt=prompt,
                          max_new_tokens=n_tokens)
            rec.t_speech_end = now
            await handle.send(SpeechEnd(sid))
            await handle.send(TurnRequest(sid, prompt=prompt,
                                          max_new_tokens=n_tokens))
            rec.t_send = time.perf_counter()
            log.turns.append(rec)
            await _play_turn(handle, sid, turn, rec, apt)
            ctx += turn.prompt_len + rec.fed
            if not rec.barged:
                # listen to the rest of the reply, then think
                drain = max(0.0, (rec.play_starts[-1] + apt
                                  if rec.play_starts else 0.0)
                            - time.perf_counter())
                await asyncio.sleep(drain + sess.think_s)
        await _hangup(handle, sid)
    log.exhausted_users += 1


async def _play_turn(handle, sid: str, turn, rec: TurnLog,
                     apt: float) -> None:
    """Receive the reply: play each packet after the one before it,
    barge in at the cut, and return once the server closed the turn
    (and, after a barge, acknowledged it)."""
    cut_s: Optional[float] = None
    if turn.barge_in:
        frac = turn.barge_cut_s / max(1e-9, turn.reply_tokens * apt)
        cut_s = max(apt, min(frac, 0.9) * turn.reply_tokens * apt)
    play_end = time.perf_counter()
    deadline: Optional[float] = None
    done = False
    while True:
        timeout = None
        if deadline is not None and not rec.barged:
            timeout = max(0.0, deadline - time.perf_counter())
        try:
            if timeout is None:
                ev = await handle.recv()
            else:
                ev = await asyncio.wait_for(handle.recv(), timeout)
        except asyncio.TimeoutError:
            _barge(rec)
            await handle.send(BargeIn(sid, expected_dur_s=turn.speech_s))
            if done:
                return
            continue
        now = time.perf_counter()
        if isinstance(ev, AudioChunk):
            if rec.t_first is None:
                rec.t_first = now
                if cut_s is not None:
                    deadline = now + cut_s
            start = max(play_end, now)
            rec.play_starts.append(start)
            rec.tokens.append(int(ev.token))
            play_end = start + ev.dur_s
        elif isinstance(ev, TurnDone):
            done = True
            if not ev.aborted:
                rec.completed = True
                if rec.t_finish is None:
                    rec.t_finish = now
            if ev.aborted or rec.barged or deadline is None:
                return
            if now >= deadline:
                # the reply was complete before the cut: the user still
                # cuts the playback, the server has nothing to abort
                _barge(rec)
                await handle.send(BargeIn(sid,
                                          expected_dur_s=turn.speech_s))
                return


def _barge(rec: TurnLog) -> None:
    now = time.perf_counter()
    rec.barged = True
    rec.played_at_barge = sum(1 for s in rec.play_starts if s < now)
    if rec.t_finish is None and rec.t_first is not None:
        rec.t_finish = now


async def _hangup(handle, sid: str) -> None:
    await handle.send(Hangup(sid))
    while True:                          # drain until the close ack
        ev = await handle.recv()
        if isinstance(ev, SessionClosed):
            return
