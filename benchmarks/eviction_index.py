"""Table 1: heap-based eviction index vs tail scanning — REAL wall-clock
(host-side CPU work in both the paper and here)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import fmt, row
from repro.core.kv_manager import KVManager
from repro.core.monitor import RuntimeMonitor


class _Clock:
    t = 0.0

    def now(self):
        return self.t


def _setup(index_mode: str, n_sessions: int):
    clock = _Clock()
    mon = RuntimeMonitor(clock)
    kv = KVManager(capacity_blocks=n_sessions * 8, block_size=16,
                   bytes_per_token=1e5, monitor=mon, policy="next_use",
                   index_mode=index_mode, clock=clock)
    rng = np.random.default_rng(0)
    for i in range(n_sessions):
        sid = f"s{i}"
        mon.register(sid)
        v = mon.view(sid)
        v.playback.started = True
        v.playback.play_end = float(rng.uniform(0, 60))
        v.playback.appended_s = v.playback.play_end + 1
        v.reply_gap_ema = float(rng.uniform(0.5, 5))
        s = kv.session(sid)
        s.total_blocks = s.hbm_blocks = int(rng.integers(2, 9))
    return kv, clock


def run(quick=False):
    out = []
    n_sessions = 1000 if quick else 4000
    rounds = 400 if quick else 2000
    for mode in ("heap", "scan"):
        kv, clock = _setup(mode, n_sessions)
        overheads = []
        for i in range(rounds):
            clock.t += 0.01
            t0 = time.perf_counter()
            kv.evict(2, clock.t)
            overheads.append(time.perf_counter() - t0)
            # sessions come back (commit re-adds blocks + re-ranks)
            sid = f"s{i % n_sessions}"
            kv.commit_turn(sid, 6 * kv.block_size, clock.t)
        oh = np.array(overheads) * 1000.0
        out.append(row(
            f"eviction_index/{mode}/n{n_sessions}",
            float(oh.mean()) * 1000.0,
            f"avg_ms={fmt(float(oh.mean()))};"
            f"p90_ms={fmt(float(np.percentile(oh, 90)))}"))
    return out
