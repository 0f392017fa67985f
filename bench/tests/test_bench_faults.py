"""A whole run on the CPU at a tiny size, the chip check skipped: sound,
it reads ``correct``; with the served path broken underneath, not."""
import numpy as np
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tinycell"))


def _go(root, seed, fault=None, trace=False):
    return run.execute(tiny.CELL, seed, 4.0, trace, root=root,
                       require_tpu=False, fault=fault)


def state_unchanged(gw):
    """The fused step hands back the page store it was given: no KV is
    ever written."""
    eng = gw.engine
    step = eng._fused_fn

    def stale(*args):
        logits, _, _ = step(*args)
        return logits, args[3], args[4]
    eng._fused_fn = stale


def token_altered(gw):
    """Every fifth launch commits, and sends, the token after the
    argmax in its first row instead of the argmax."""
    eng = gw.engine
    rows = eng._run_chunk_rows
    count = [0]

    def altered(feeds):
        logits, outs = rows(feeds)
        count[0] += 1
        if count[0] % 5 == 0:
            i = min(logits)
            lg = np.array(logits[i])
            lg[(int(np.argmax(lg)) + 1) % lg.shape[0]] = lg.max() + 1.0
            logits = dict(logits)
            logits[i] = lg
        return logits, outs
    eng._run_chunk_rows = altered


def test_a_sound_run_is_correct(root):
    out = _go(root, 2**31 + 101)
    assert out["correct"] is True
    assert out["check"]["tokens_compared"]["value"] >= 10
    assert out["check"]["token_mismatch"]["value"] == 0
    assert set(out["metrics"]) == {"ttfp_p50_s", "turns_per_s",
                                   "setup_s"}
    assert out["attempted"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_a_broken_path_is_not_correct(root, fault):
    out = _go(root, 2**31 + 102, fault=fault)
    assert out["correct"] is False
    assert out["check"]["gap_max"]["value"] > \
        out["check"]["gap_max"]["limit"]


def test_the_control_in_the_programs_place_is_not_correct(root):
    """The float8 reference's tokens, judged where the served ones were,
    fail the comparison the harness itself makes."""
    out = run.execute(tiny.CELL, 2**31 + 103, 4.0, False, root=root,
                      require_tpu=False, control=True)
    assert out["correct"] is False
    assert out["check"]["gap_max"]["value"] > \
        out["check"]["gap_max"]["limit"]
    assert out["check"]["token_mismatch"]["value"] == 0
