"""The population generator: seeded, and true to its parameters."""
import json
from pathlib import Path

import numpy as np

from bench.loadgen import generator

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _flat(pop):
    return [(u.index, u.history_tokens, u.phase,
             [[(t.prompt_len, t.reply_tokens, t.speech_s, t.barge_in,
                t.barge_cut_s) for t in s.turns] + [s.think_s]
              for s in u.sessions]) for u in pop.users]


def test_one_seed_gives_the_same_population_twice():
    t = _mix("voice")
    a = generator.population(t, 2**31 + 5, users=24)
    b = generator.population(t, 2**31 + 5, users=24)
    assert _flat(a) == _flat(b)
    assert a.mean_cycle_s == b.mean_cycle_s
    assert (a.tokens.integers(0, 1000, 50)
            == b.tokens.integers(0, 1000, 50)).all()


def test_seeds_deal_the_same_work_in_another_order():
    t = _mix("voice")
    a = generator.population(t, 1, users=24)
    b = generator.population(t, 2, users=24)
    assert _flat(a) != _flat(b)
    key = lambda f: sorted(map(repr, f))                 # noqa: E731
    assert key([x[1:2] + x[3:] for x in _flat(a)]) == \
        key([x[1:2] + x[3:] for x in _flat(b)])
    assert sorted(u.phase for u in a.users) == \
        sorted(u.phase for u in b.users)


def test_drawn_lengths_follow_the_stated_parameters():
    t = _mix("voice")
    rng = np.random.default_rng(0)
    sessions = [generator.draw_session(rng, t) for _ in range(3000)]
    turns = [x for s in sessions for x in s.turns]
    lo, hi = t["turns"]
    counts = [len(s.turns) for s in sessions]
    assert min(counts) == lo and max(counts) == hi
    prompts = np.array([x.prompt_len for x in turns])
    p = t["prompt_tokens"]
    assert prompts.min() >= p["lo"] and prompts.max() <= p["hi"]
    assert abs(np.median(prompts) / p["median"] - 1) < 0.05
    audio = np.array([x.reply_audio_s for x in turns])
    assert abs(np.median(audio) / t["reply_audio_s"]["median"] - 1) < 0.05
    sd = np.std(np.log(audio[(audio > 3) & (audio < 50)]))
    assert 0.45 < sd < 0.65
    replies = np.array([x.reply_tokens for x in turns])
    want = np.maximum(t["min_reply_tokens"],
                      (audio / t["audio_per_token_s"]).astype(int))
    assert (replies == want).all()
    barge = np.array([x.barge_in for x in turns])
    assert abs(barge.mean() - t["barge_in_p"]) < 0.03
    frac = np.array([x.barge_cut_s / x.reply_audio_s
                     for x in turns if x.barge_in])
    c0, c1 = t["barge_cut"]
    assert frac.min() >= c0 and frac.max() <= c1
    speech = np.array([x.speech_s for x in turns])
    assert abs(np.median(speech) / t["speech_s"]["median"] - 1) < 0.05
    think = np.array([s.think_s for s in sessions])
    assert abs(np.median(think) / t["think_s"]["median"] - 1) < 0.08


def test_fixed_history_gives_every_user_the_same_size():
    t = _mix("voice-history")
    for n in (5, 8, 20):
        pop = generator.population(t, 3, users=n)
        assert [u.history_tokens for u in pop.users] == \
            [t["history"]["tokens"]] * n
