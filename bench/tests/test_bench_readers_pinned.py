"""The eight per-layer readers the benchmark already has, pinned: on the
committed recorded chip trace and a fixed set of client, admission,
launch and memory records, each reads what it read when it was written.
A change to how the trace is reduced (more host spans kept, gaps named
by the innermost span) must leave every one of them where it is."""
from pathlib import Path

import pytest

from bench import peaks, run, spec
from bench.loadgen.client import TurnLog, Window
from bench.trace import reduce as R

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "qwen2-1.5b.voice"

PINNED = {
    "gateway.admit_wait_p90_s": 0.030000000000001137,
    "sched.wasted_token_share": 0.24,
    "kv.admit_ms_per_turn": 0.14250000000082252,
    "step.padded_row_share": 0.9154411764705882,
    "step.mfu": 0.9326296847475047,
    "paged_attention_roofline": 2.2321637219486377,
    "device.idle_share_in_rounds": 0.13113716987356383,
    "device.resident_gb": 6.9,
}


def _turn(k):
    """The k-th of twelve turns, one a second from t = 100.5, every
    third cut by a barge-in after some of its packets played."""
    t = TurnLog(user=k % 4, session_id=f"u{k % 4:03d}.0", turn=k // 4,
                ctx_before=0, prompt=None, max_new_tokens=20)
    t.t_speech_end = 100.5 + k
    t.t_send = t.t_speech_end + 0.001
    t.t_first = t.t_send + 0.2 + 0.03 * k
    t.t_finish = t.t_first + 1.5
    t.tokens = list(range(8 + k))
    if k % 3 == 0:
        t.barged, t.played_at_barge = True, 3 + k % 5
    return t


@pytest.fixture(scope="module")
def recorded_run():
    cell = spec.resolve_cell(CELL, ROOT)
    r = run.BenchRun(cell=cell, seed=0, seconds=10.0, trace_on=True,
                     conf=cell.config, engine_conf=cell.config["engine"])
    r.window = Window(100.0, 110.0)
    r.grace_deadline = 125.0
    r.client.turns = [_turn(k) for k in range(12)]
    for t in r.client.turns:
        r.admits.setdefault(t.session_id, []).append(
            t.t_send + 0.01 * (1 + t.turn))
    r.submits = [(100.2 + k, 100.2 + k + 6e-5 * (1 + k % 3), 0, 0,
                  k % 5 != 4) for k in range(12)]
    # the six launches of the recorded slice: decode rows of 300-1,100
    # committed tokens, two rounds carrying a 32-token prefill chunk
    r.launches = []
    for i in range(6):
        rows = [(300 + 37 * j + 11 * i, 1) for j in range(20)]
        if i in (1, 4):
            rows.append((800 + i, 32))
        r.launches.append(run.Launch(105.0 + 0.06 * i, rows, 32,
                                     32 if i in (1, 4) else 1))
    r.trace_span = (105.0, 105.5)
    r.trace = R.Trace(R.load_events(DATA / f"{CELL}.trace.json.gz"))
    r.peak = peaks.peaks("TPU v5 lite")
    r.resident = [(99.0, 7.2e9), (104.0, 6.8627e9), (108.0, 6.9e9)]
    return r


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_reader_reads_what_it_read(recorded_run, name):
    got = spec.metric_reader(name, ROOT)(recorded_run)
    assert got == pytest.approx(PINNED[name], rel=1e-12)
