"""The reduction from trace events to the traced metrics."""
from pathlib import Path

import pytest

from bench.trace import reduce as R

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def ev(plane, line, name, ts, dur):
    return {"plane": plane, "line": line, "name": name, "ts": ts,
            "dur": dur}


def synthetic():
    """A 100 ns window: two rounds (10-40, 50-90); ops 12-30 and 55-85,
    a kernel op inside each; the step module around each."""
    return [
        ev("/host:CPU", "python", "bench.trace", 0, 100),
        ev("/host:CPU", "python", "bench.round", 10, 30),
        ev("/host:CPU", "python", "bench.launch", 11, 28),
        ev("/host:CPU", "python", "bench.round", 50, 40),
        ev("/host:CPU", "python", "bench.admit", 42, 6),
        ev(DEV, R.MODULES_LINE, "jit_paged_fused_step(1)", 12, 18),
        ev(DEV, R.MODULES_LINE, "jit_paged_fused_step(1)", 55, 30),
        ev(DEV, R.OPS_LINE, "%while.2 = (s32[]) while(%t)", 12, 18),
        ev(DEV, R.OPS_LINE, "%fusion.1 = bf16[4] fusion(%a)", 12, 8),
        ev(DEV, R.OPS_LINE, "%paged_attention.5 = bf16[2] custom-call("
                            "%fusion.1)", 20, 10),
        ev(DEV, R.OPS_LINE, "%fusion.7 = bf16[4] fusion(%b)", 55, 10),
        ev(DEV, R.OPS_LINE, "%paged_attention.5 = bf16[2] custom-call("
                            "%fusion.7)", 65, 20),
        ev(DEV, R.OPS_LINE, "%copy.3 = bf16[2] copy(%paged_attention.5)",
           85, 0),
    ]


def test_interval_arithmetic():
    assert R.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert R.intersect([(0, 3), (5, 7)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert R.length([(0, 3), (5, 7)]) == 5


def test_synthetic_trace():
    tr = R.Trace(synthetic())
    assert tr.devices == [DEV]
    assert tr.window_s() == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(48e-9)
    # rounds cover 10-40 and 50-90 (70 ns); busy inside them 18 + 30
    assert tr.busy_share_in("bench.round") == pytest.approx(48 / 70)
    assert tr.op_time("paged_attention") == pytest.approx(30e-9)
    # only the first step starts inside a bench.launch span
    assert tr.module_time_in("bench.launch") == pytest.approx(18e-9)
    top = tr.top_ops(3)
    # the while loop's own time is what its body ops leave: 18 - 8 - 10
    assert top == [["paged_attention", pytest.approx(30e-9)],
                   ["fusion", pytest.approx(18e-9)],
                   ["while", pytest.approx(0.0)]]
    gaps = tr.idle_gaps(3)
    # 30-55 (midpoint 42.5: inside bench.admit), 85-100, 0-12
    assert gaps[0] == ["bench.admit", pytest.approx(25e-9)]
    assert gaps[1] == ["host: outside bench spans", pytest.approx(15e-9)]
    assert gaps[2] == ["host: outside bench spans", pytest.approx(12e-9)]


def test_op_names_are_the_ops_own():
    assert R.op_name("%paged_attention.5 = bf16[2] custom-call(%x)") \
        == "paged_attention.5"
    assert R.op_name("%copy.3 = bf16[2] copy(%paged_attention.5)") \
        == "copy.3"
    assert R.op_name("plain") == "plain"


def test_round_trip_through_a_file(tmp_path):
    R.save_events(synthetic(), tmp_path / "t.json.gz")
    again = R.Trace(R.load_events(tmp_path / "t.json.gz"))
    assert again.busy_s() == pytest.approx(48e-9)


def test_recorded_chip_trace():
    """Six rounds of a traced ``qwen2-1.5b.voice`` run on one TPU v5
    lite, each reduction checked against a count on a 1 us grid."""
    import numpy as np
    events = R.load_events(DATA / "qwen2-1.5b.voice.trace.json.gz")
    tr = R.Trace(events)
    assert tr.devices == [DEV]
    n = int(np.ceil(tr.window[1] / 1e3)) + 1

    def grid(evs):
        g = np.zeros(n, bool)
        for e in evs:
            g[int(e["ts"] // 1e3):int((e["ts"] + e["dur"]) // 1e3)] = True
        return g

    ops = [e for e in events if e["line"] == R.OPS_LINE]
    busy = grid(ops)
    assert tr.busy_s() == pytest.approx(busy.sum() * 1e-6, rel=0.02)
    rounds = grid([e for e in events if e["name"] == "bench.round"])
    share = (busy & rounds).sum() / rounds.sum()
    assert tr.busy_share_in("bench.round") == pytest.approx(share,
                                                            abs=0.02)
    kernel = [e for e in ops
              if e["name"].startswith("%paged_attention.")]
    assert tr.op_time("paged_attention") == pytest.approx(
        sum(e["dur"] for e in kernel) * 1e-9)
    # every step execution starts inside its launch span, one each
    launches = tr.span_intervals("bench.launch")
    assert len(tr.modules_in("bench.launch")) == len(launches) == 6
    assert tr.module_time_in("bench.launch") <= tr.busy_s() * 1.001
    top = tr.top_ops(10)
    assert top[0][0] == "paged_attention"
    assert sum(s for _, s in top) <= tr.busy_s() * 1.001
    for name, s in tr.idle_gaps(10):
        assert name.startswith("bench.") or name.startswith("host")
        assert 0 < s < tr.window_s()
