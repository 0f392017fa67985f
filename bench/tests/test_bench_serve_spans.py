"""The program's own ``serve.*`` spans on a recorded chip slice: six
rounds of a traced ``qwen2-1.5b.voice`` run on one TPU v5 lite, with the
program's tracing on, cut from the profiler's trace (host spans and the
device's op and module lines, on one clock).

What it pins: the program's spans line up with the benchmark's wrappers
and with the device's work, and they account for the device's idle time
inside the rounds, counted on a 1 us grid as well as by the interval
reduction."""
from pathlib import Path

import numpy as np
import pytest

from bench.trace import reduce as R

DATA = Path(__file__).resolve().parent / "data"
CHILDREN = ("serve.xfer.drain", "serve.round.feeds", "serve.round.tables",
            "serve.round.launch", "serve.round.logits",
            "serve.round.sample")


@pytest.fixture(scope="module")
def events():
    return R.load_events(DATA / "qwen2-1.5b.voice.serve.trace.json.gz")


def spans(events, name):
    return [(e["ts"], e["ts"] + e["dur"]) for e in events
            if e["plane"].startswith("/host") and e["name"] == name]


def inside(inner, outer):
    return all(any(a <= x and y <= b for a, b in outer) for x, y in inner)


def test_spans_nest_as_the_program_opens_them(events):
    rounds = spans(events, "serve.round")
    assert len(rounds) == 6
    assert inside(rounds, spans(events, "bench.round"))
    for child in CHILDREN:
        assert inside(spans(events, child), rounds), child
    assert inside(spans(events, "serve.round.launch"),
                  spans(events, "bench.launch"))
    assert inside(spans(events, "serve.kv.settle"),
                  spans(events, "serve.admit"))
    assert inside(spans(events, "serve.admit"),
                  spans(events, "serve.schedule"))


def test_each_step_runs_from_its_own_launch(events):
    tables = spans(events, "serve.round.tables")
    launches = spans(events, "serve.round.launch")
    mods = [e["ts"] for e in events if e["line"] == R.MODULES_LINE]
    assert len(tables) == len(launches) == len(mods) == 6
    # the trace puts each step's start 0.5-0.7 ms before the host enters
    # the call that enqueues it: the device's clock leads the host's by
    # at least that much, so a gap's split between adjacent spans is
    # good to about a millisecond; it starts after the round's tables
    # began and before its launch returned
    for (a, _), (_, b) in zip(tables, launches):
        assert sum(1 for t in mods if a <= t <= b) == 1
    # the host waits for the step's result in logits, never in launch
    assert R.length(launches) < 0.02 * R.length(spans(events,
                                                      "serve.round"))


def test_the_rounds_idle_falls_in_their_steps(events):
    tr = R.Trace(events)
    (dev,) = tr.devices
    busy = tr.busy(dev)
    rounds = R.intersect(R.union(spans(events, "bench.round")),
                         [tr.window])

    def idle(ivs):
        ivs = R.intersect(R.union(ivs), rounds)
        return R.length(ivs) - R.length(R.intersect(busy, ivs))

    whole = idle(rounds)
    named = idle([iv for c in CHILDREN for iv in spans(events, c)])
    assert named >= 0.9 * whole > 0
    # the same counted on a 1 us grid
    n = int(np.ceil(tr.window[1] / 1e3)) + 1

    def grid(ivs):
        g = np.zeros(n, bool)
        for a, b in ivs:
            g[int(a // 1e3):int(b // 1e3)] = True
        return g

    ops = grid([(e["ts"], e["ts"] + e["dur"]) for e in events
                if e["line"] == R.OPS_LINE])
    in_rounds = grid(rounds)
    kids = grid([iv for c in CHILDREN for iv in spans(events, c)])
    assert whole * 1e-3 == pytest.approx((in_rounds & ~ops).sum(),
                                         rel=0.02)
    assert named * 1e-3 == pytest.approx((in_rounds & kids & ~ops).sum(),
                                         rel=0.02)
    # most of it waits on the step's logits
    logits = idle(spans(events, "serve.round.logits"))
    assert logits > 0.5 * whole
