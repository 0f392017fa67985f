"""Voice-session populations, drawn from a traffic file's parameters.

The per-session draws are a copy of the ``interactive`` kind of
``src/repro/serving/workload.py`` (``_lognormal`` and ``_make_turns``,
as of commit 1a6d178), in the same order, with its constants moved
into the traffic file. One change: reply tokens are reply audio over
the traffic's ``audio_per_token_s``, the value the gateway is given
too (the original generator assumed 0.08 s while the gateway streams
0.25 s a token).

A population is closed: ``users`` concurrent users, each working
through ``sessions_per_user`` sessions in turn. What a run's ``--seed``
changes is only the order: the set of user scripts (their sessions,
turns and lengths), of start phases and of history sizes is drawn from
the traffic's fixed ``population_seed``; the run's seed deals the
scripts and phases out to the users and draws the token ids. So every
seed offers the same work in another order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class TurnScript:
    prompt_len: int
    reply_tokens: int
    reply_audio_s: float
    speech_s: float
    barge_in: bool
    barge_cut_s: float               # seconds of reply heard before the cut


@dataclass
class SessionScript:
    turns: List[TurnScript]
    think_s: float


@dataclass
class UserScript:
    index: int                       # position in the drawn set
    sessions: List[SessionScript]
    history_tokens: int              # committed before the window opens
    phase: float                     # start delay as a share of the ramp


@dataclass
class Population:
    users: List[UserScript]
    audio_per_token_s: float
    mean_cycle_s: float              # mean cycle_s over every turn
    ramp_s: float = 0.0              # users start spread over this
    tokens: np.random.Generator = field(repr=False, default=None)


def lognormal(rng, median, sigma, lo, hi) -> float:
    v = rng.lognormal(math.log(median), sigma)
    return float(min(max(v, lo), hi))


def _dist(rng, d: dict) -> float:
    return lognormal(rng, d["median"], d["sigma"], d["lo"], d["hi"])


def draw_session(rng, t: dict) -> SessionScript:
    """One interactive session, drawn in the original generator's
    order: turn count, then per turn prompt, reply audio, barge-in,
    cut, speech; then the session's think time."""
    lo, hi = t["turns"]
    n_turns = int(rng.integers(lo, hi + 1))
    apt = t["audio_per_token_s"]
    turns = []
    for _ in range(n_turns):
        prompt = int(_dist(rng, t["prompt_tokens"]))
        audio = _dist(rng, t["reply_audio_s"])
        reply = max(t["min_reply_tokens"], int(audio / apt))
        barge = bool(rng.random() < t["barge_in_p"])
        c0, c1 = t["barge_cut"]
        cut = float(rng.uniform(c0, c1)) * audio if barge else 0.0
        speech = _dist(rng, t["speech_s"])
        turns.append(TurnScript(prompt, reply, audio, speech, barge, cut))
    think = _dist(rng, t["think_s"])
    return SessionScript(turns, think)


def heard_s(turn: TurnScript, apt: float) -> float:
    """Reply audio a user listens to: all of it, or up to the cut."""
    full = turn.reply_tokens * apt
    return min(full, max(apt, turn.barge_cut_s)) if turn.barge_in \
        else full


def cycle_s(turn: TurnScript, think_s: float, apt: float) -> float:
    """A turn's length at an instant server: speech, the reply heard,
    then thinking, which a barge-in skips (the user is already
    speaking again)."""
    return turn.speech_s + heard_s(turn, apt) \
        + (0.0 if turn.barge_in else think_s)


def history_sizes(t: dict, users: int, rng) -> List[int]:
    """Tokens committed per user before the window opens.

    ``turns`` mode: each user's first session follows 0 to k earlier
    turns of the same generator (prompt plus the reply heard).
    ``fixed`` mode: every user holds ``tokens`` tokens."""
    h = t["history"]
    if h["mode"] == "fixed":
        return [int(h["tokens"])] * users
    lo, hi = h["turns"]
    apt = t["audio_per_token_s"]
    out = []
    for _ in range(users):
        n = int(rng.integers(lo, hi + 1))
        tot = 0
        for _ in range(n):
            tr = draw_session(rng, {**t, "turns": [1, 1]}).turns[0]
            tot += tr.prompt_len + int(heard_s(tr, apt) / apt)
        out.append(tot)
    return out


def population(t: dict, seed: int, *, users: int) -> Population:
    """The traffic's fixed set of user scripts, dealt out by ``seed``."""
    rng = np.random.default_rng(t["population_seed"])
    spu = t["sessions_per_user"]
    scripts = [[draw_session(rng, t) for _ in range(spu)]
               for _ in range(users)]
    hist = history_sizes(t, users, rng)
    phases = [(i + 0.5) / users for i in range(users)]
    apt = t["audio_per_token_s"]
    cycles = [cycle_s(tr, s.think_s, apt)
              for ss in scripts for s in ss for tr in s.turns]
    deal = np.random.default_rng(seed)
    order = deal.permutation(users)
    phase_order = deal.permutation(users)
    out = [UserScript(index=int(order[u]), sessions=scripts[order[u]],
                      history_tokens=hist[order[u]],
                      phase=phases[phase_order[u]])
           for u in range(users)]
    return Population(out, apt, float(np.mean(cycles)), t["ramp_s"],
                      tokens=np.random.default_rng([seed, 1]))
