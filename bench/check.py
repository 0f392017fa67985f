"""Whether the served tokens are the model's: ``correct``.

Once the window has closed, a sample of sessions drawn from the seed is
taken among those with finished turns: the one with the most served
tokens, one whose turn followed a barge-in trim, one whose pages went
to the host and came back before a finished turn, one with a history
built before the window, and more drawn at random up to the traffic's
``check.sessions``. For each, the sequence the server saw is rebuilt
from what the client knows: the history, then per finished turn the
prompt it sent and the reply tokens that were fed back (all of a
completed reply, all but the last of a cut one).

Three numbers are compared, each with its limit:

- ``gap_max``: the widest gap, over every served token in the sample,
  by which the reference's logit of the served token lies below its
  best logit at that position. Greedy serving in bfloat16 picks a
  token whose float32 logit is near the best; a wrong page, position,
  head or weight does not. The limit is the traffic's
  ``check.gap_limit``, set from calibration runs (``PERF.md``).
- ``token_mismatch``: sessions whose rebuilt sequence differs from the
  engine's committed token ids (limit 0): the client received what the
  engine committed.
- ``tokens_compared``: served tokens compared, at least
  ``check.min_tokens``.

With ``control`` the float8 reference takes the program's place: at
each served position its first token is judged instead of the served
one, and ``gap_max`` is that token's gap. Such a run must read
``correct`` false.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from bench import reference


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Sample:
    sids: List[str]
    covers: Dict[str, str] = field(default_factory=dict)


@dataclass
class Verdict:
    correct: bool
    numbers: Dict[str, dict]
    covers: Dict[str, str] = field(default_factory=dict)


def finished_turns(run, sid: str) -> list:
    """The session's turns in order, up to its first unfinished one."""
    turns = sorted((t for t in run.client.turns if t.session_id == sid),
                   key=lambda t: t.t_send)
    out = []
    for t in turns:
        if t.t_first is None or not (t.completed or t.barged):
            break
        out.append(t)
    return out


def pick_sessions(run) -> Sample:
    rng = np.random.default_rng([run.seed, 4])
    want = run.cell.traffic["check"]["sessions"]
    sids = sorted({t.session_id for t in run.client.turns})
    done = {sid: finished_turns(run, sid) for sid in sids}
    cands = [sid for sid in sids if done[sid]]
    if not cands:
        return Sample([])
    chosen: Dict[str, str] = {}

    def take(label, pool):
        pool = [s for s in pool if s not in chosen]
        if pool:
            chosen[pool[int(rng.integers(len(pool)))]] = label

    longest = max(cands, key=lambda s: (sum(len(t.tokens)
                                            for t in done[s]), s))
    chosen[longest] = "longest"
    take("after barge-in", [s for s in cands
                            if any(a.barged for a in done[s][:-1])])
    reloaded = {}
    for t, sid in run.reloads:
        reloaded.setdefault(sid, t)
    take("reloaded", [s for s in cands if s in reloaded
                      and any(t.t_finish > reloaded[s] for t in done[s])])
    take("history", [s for s in cands
                     if run.client.sessions.get(s, {}).get("history_len")])
    rest = [s for s in cands if s not in chosen]
    for i in rng.permutation(len(rest))[:max(0, want - len(chosen))]:
        chosen[rest[int(i)]] = "random"
    return Sample(sorted(chosen), covers={v: k for k, v in chosen.items()})


def rebuild(run, sid: str):
    """(sequence, logit positions, served tokens, consistent) of one
    session, from the client's records."""
    hist = run.client.sessions.get(sid, {})
    seq = list(np.asarray(hist.get("history", []), np.int64))
    positions, served, ok = [], [], True
    for t in finished_turns(run, sid):
        ok &= len(seq) == t.ctx_before
        base = len(seq) + len(t.prompt) - 1
        seq += [int(x) for x in t.prompt]
        positions += [base + j for j in range(len(t.tokens))]
        served += t.tokens
        seq += t.tokens[:t.fed]
    return (np.asarray(seq, np.int32), np.asarray(positions, np.int64),
            np.asarray(served, np.int32), ok)


def compare(run, sample: Sample, engine_ids: Dict[str, list], params, *,
            control: bool = False) -> Verdict:
    c = run.cell.traffic["check"]
    seqs, poss, toks = [], [], []
    mismatch = 0
    for sid in sample.sids:
        seq, pos, served, ok = rebuild(run, sid)
        got = engine_ids.get(sid)
        if not ok or got is None or list(got[:len(seq)]) != list(seq):
            mismatch += 1
        seqs.append(seq)
        poss.append(pos)
        toks.append(served)
    n = int(sum(len(p) for p in poss))
    pad = run.engine_conf["max_context"]
    batch = reference.batch_for(run.conf, pad)
    gap = float("inf")
    if n:
        gaps = reference.served_gaps(run.cell.arch, run.conf, params, seqs,
                                     poss, toks, pad_to=pad, batch=batch)
        gap = float(max(g.max() for g in gaps if len(g)))
        allg = np.concatenate(gaps)
        _log(f"check gaps: n={len(allg)} nonzero={int((allg > 0).sum())} "
             f"top={np.sort(allg)[-5:].tolist()}")
        if control:
            # the control in the program's place: at every served
            # position, the token the float8 reference puts first is
            # judged instead of the served one
            _log(f"check program gap_max (control run, not judged): "
                 f"{gap!r}")
            cg = reference.served_gaps(run.cell.arch, run.conf, params,
                                       seqs, poss, toks, pad_to=pad,
                                       batch=batch, control=True)
            gap = float(max(g.max() for g in cg if len(g)))
            allc = np.concatenate(cg)
            _log(f"control gaps: nonzero={int((allc > 0).sum())} "
                 f"top={np.sort(allc)[-5:].tolist()}")
    numbers = {
        "gap_max": {"value": gap if n else None,
                    "limit": c["gap_limit"]},
        "token_mismatch": {"value": mismatch, "limit": 0},
        "tokens_compared": {"value": n, "limit": c["min_tokens"]},
    }
    correct = gap <= c["gap_limit"] and mismatch == 0 \
        and n >= c["min_tokens"]
    return Verdict(bool(correct), numbers, sample.covers)
