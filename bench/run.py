#!/usr/bin/env python3
"""LiveServe chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` is a model configuration under a traffic
mix. A run builds the configuration at its published widths with
weights drawn on the chip from ``--seed``, puts the live gateway
(``policy="liveserve"``, real-time clock) in front of the paged engine,
warms the cell's query buckets, builds every user's history through the
engine, starts the closed population of voice users, and measures for
``--seconds``. Every time an end-to-end metric reads is taken by the
client on the host's monotonic clock. Then it checks the served tokens
against the float32 reference and prints one JSON line.

``--trace 1`` also profiles a slice of the window and reports the
per-layer metrics instead of the end-to-end ones.

It exits non-zero, with no result line, unless JAX's first device is a
TPU and there are as many chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import check, spec  # noqa: E402
from bench.loadgen import generator  # noqa: E402
from bench.loadgen.client import (ClientLog, Window,  # noqa: E402
                                  drive_user, session_id)


class BenchError(Exception):
    """A run that cannot give a result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
@dataclass
class Launch:
    t: float
    rows: List[tuple]               # (q_start, q_len) per valid row
    B: int
    Q: int


@dataclass
class BenchRun:
    """Everything a metric reader may read, for one run."""
    cell: spec.Cell
    seed: int
    seconds: float
    trace_on: bool
    conf: dict = field(default_factory=dict)
    engine_conf: dict = field(default_factory=dict)
    setup: Dict[str, float] = field(default_factory=dict)
    window: Window = field(default_factory=Window)
    grace_deadline: float = 0.0
    client: ClientLog = field(default_factory=ClientLog)
    admits: Dict[str, List[float]] = field(default_factory=dict)
    submits: List[tuple] = field(default_factory=list)
    launches: List[Launch] = field(default_factory=list)
    reload_marks: Dict[str, int] = field(default_factory=dict)
    reloads: List[tuple] = field(default_factory=list)
    compiles_in_window: int = 0
    trace: object = None
    trace_span: tuple = (0.0, 0.0)
    peak: dict = field(default_factory=dict)
    peak_bytes: Optional[int] = None
    resident: List[tuple] = field(default_factory=list)  # (t, bytes)
    device: dict = field(default_factory=dict)
    users: int = 0
    mean_cycle_s: float = 0.0
    dump_trace: Optional[str] = None

    # ------------------------------------------------------ turn views
    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.window.t_open <= t \
            < self.window.t_close

    def attempted(self):
        return [t for t in self.client.turns if self.in_window(t.t_send)]

    def ttfps(self) -> List[float]:
        """TTFP of every attempted turn; one with no first packet by
        the grace deadline counts at the deadline."""
        out = []
        for t in self.attempted():
            first = t.t_first if (t.t_first is not None and t.t_first
                                  <= self.grace_deadline) \
                else self.grace_deadline
            out.append(first - t.t_speech_end)
        return out

    def failed(self) -> int:
        return sum(1 for t in self.attempted()
                   if t.t_first is None or t.t_first > self.grace_deadline)

    def finished_in_window(self) -> int:
        return sum(1 for t in self.client.turns
                   if self.in_window(t.t_finish))

    def window_launches(self) -> List[Launch]:
        return [x for x in self.launches if self.in_window(x.t)]

    def traced_launches(self) -> List[Launch]:
        a, b = self.trace_span
        return [x for x in self.launches if a <= x.t < b]


# ----------------------------------------------------------------------
def tpu_devices(need: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX's first device is {devs[0].platform} "
            f"({devs[0].device_kind}); this benchmark runs on the chip "
            "only")
    if len(devs) < need:
        raise BenchError(f"the cell needs {need} chips, JAX has "
                         f"{len(devs)}")
    return devs[:need]


def compile_cache() -> str:
    """JAX's persistent cache: the environment's directory, else one
    fixed directory inside the checkout. Every program is kept, so a
    second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_gateway(conf: dict, cfg, params):
    from repro.serving.gateway.harness import build_gateway as build
    e = conf["engine"]
    gw = build(policy=e["policy"], scale=1.0, slots=e["slots"],
               page_size=e["page_size"],
               pages_per_seq=e["max_context"] // e["page_size"],
               num_pages=e["num_pages"],
               audio_per_token_s=e["audio_per_token_s"],
               round_token_budget=e["round_token_budget"],
               prefill_chunk=e["prefill_chunk"],
               frontier_cap_s=e["frontier_cap_s"], model=(cfg, params),
               prefix_cache=e["prefix_cache"], kv_quant=e["kv_quant"],
               spec_decode=e["spec_decode"])
    eng = gw.engine
    got = dict(kv_policy=eng.kv.policy, preload=eng.preloader.enabled,
               async_transfers=eng.async_transfers)
    want = {k: e[k] for k in got}
    if got != want:
        raise BenchError(f"engine settings {got} differ from the "
                         f"configuration's {want}")
    return gw


# ----------------------------------------------------------------------
class Instruments:
    """Host-clock wrappers around the calls into each layer. With
    tracing on they also write ``bench.*`` spans into the profiler's
    trace."""

    def __init__(self, run: BenchRun, gw, traced: bool, dev):
        self.run, self.gw, self.eng = run, gw, gw.engine
        self.traced, self.dev = traced, dev
        self._idle = None

    def span(self, name: str):
        if not self.traced:
            return nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def install(self) -> None:
        import repro.serving.gateway.gateway as gwmod
        from repro.serving.paged_engine import _q_bucket
        run, gw, eng = self.run, self.gw, self.eng
        inst = self

        round_fn = eng.run_round

        def run_round(chunks):
            with inst.span("bench.round"):
                out = round_fn(chunks)
            if inst.traced:
                # what the chip holds between rounds: the step's result
                # has replaced its inputs by now
                stats = inst.dev.memory_stats()
                if stats and "bytes_in_use" in stats:
                    run.resident.append((time.perf_counter(),
                                         stats["bytes_in_use"]))
            return out
        eng.run_round = run_round

        rows_fn = eng._run_chunk_rows

        def run_chunk_rows(feeds):
            rows = [(eng.sessions[sid].kv_len, len(toks))
                    for sid, toks in feeds.values()]
            q = _q_bucket(max(n for _, n in rows))
            run.launches.append(Launch(time.perf_counter(), rows,
                                       eng.slots, q))
            with inst.span("bench.launch"):
                return rows_fn(feeds)
        eng._run_chunk_rows = run_chunk_rows

        submit_fn = eng.submit_turn

        def submit_turn(sid, prompt, max_new_tokens, **kw):
            n0 = len(eng.reload_wall_s)
            t0 = time.perf_counter()
            ok = False
            try:
                with inst.span("bench.admit"):
                    slot = submit_fn(sid, prompt, max_new_tokens, **kw)
                ok = True
                return slot
            finally:
                run.submits.append((t0, time.perf_counter(), n0,
                                    len(eng.reload_wall_s), ok))
        eng.submit_turn = submit_turn

        drain_fn = eng.drain_transfers

        def drain_transfers(max_chunks=None):
            with inst.span("bench.drain"):
                return drain_fn(max_chunks)
        eng.drain_transfers = drain_transfers

        control = gwmod.control_round

        def control_round(*a, **kw):
            with inst.span("bench.schedule"):
                return control(*a, **kw)
        gwmod.control_round = control_round
        self._restore = (gwmod, control)

        record = gw._record_admit

        def record_admit(sid, r):
            run.admits.setdefault(sid, []).append(time.perf_counter())
            return record(sid, r)
        gw._record_admit = record_admit

        rnd = gw._round

        def gw_round():
            busy = rnd()
            if not busy and inst.traced and inst._idle is None:
                inst._idle = inst.span("bench.idle")
                inst._idle.__enter__()
            return busy
        gw._round = gw_round

        drain = gw._drain

        def gw_drain():
            if inst._idle is not None:
                inst._idle.__exit__(None, None, None)
                inst._idle = None
            return drain()
        gw._drain = gw_drain

        reload_io = eng._io_reload_chunk

        def io_reload(sid, lis):
            run.reloads.append((time.perf_counter(), sid))
            return reload_io(sid, lis)
        eng.transfer.set_io(reload_chunk=io_reload,
                            offload_chunk=eng._io_offload_chunk)

    def uninstall(self) -> None:
        if self._idle is not None:
            self._idle.__exit__(None, None, None)
            self._idle = None
        gwmod, control = self._restore
        gwmod.control_round = control


# ----------------------------------------------------------------------
def build_histories(run: BenchRun, gw, pop) -> None:
    """Commit every first session's history through the engine before
    the window: one teacher-forced prefill per user (its earlier turns'
    prompts and replies as random tokens) and one decoded token, in
    chunks of the cell's prefill size, as many users at once as slots
    and half the pool allow."""
    from repro.core.session import Phase
    eng = gw.engine
    e = run.engine_conf
    rng = np.random.default_rng([run.seed, 3])
    vocab = run.conf["vocab_size"]
    todo = []
    for pos, u in enumerate(pop.users):
        if u.history_tokens > 0:
            toks = rng.integers(0, vocab, size=u.history_tokens) \
                .astype(np.int32)
            todo.append((session_id(pos, 0), toks))
    budget = e["num_pages"] * e["page_size"] // 2
    active: Dict[int, tuple] = {}
    while todo or active:
        held = sum(len(t) + 1 for _, t in active.values())
        while todo and eng.free_slot() is not None \
                and (not active or held + len(todo[0][1]) <= budget):
            sid, toks = todo.pop(0)
            slot = eng.submit_turn(sid, toks, 1)
            active[slot] = (sid, toks)
            held += len(toks) + 1
        chunks = {s: (e["prefill_chunk"]
                      if eng.slot_state[s].request.phase == Phase.PREFILL
                      else 1) for s in active}
        for slot, evs in eng.run_round(chunks).items():
            for kind, val in evs:
                sid, toks = active[slot]
                if kind == "token":
                    run.client.sessions[sid] = {
                        "history": np.append(toks, np.int32(val)),
                        "history_len": len(toks) + 1}
                elif kind == "finished":
                    del active[slot]
    eng.flush_transfers()


async def _serve(run: BenchRun, gw, pop, inst: Instruments) -> None:
    import jax
    t = run.cell.traffic
    window = run.window
    e = run.engine_conf
    gtask = asyncio.create_task(gw.run())
    users = [asyncio.create_task(drive_user(
        gw, pos, u, pop, max_context=e["max_context"], window=window,
        log=run.client, vocab=run.conf["vocab_size"], seed=run.seed))
        for pos, u in enumerate(pop.users)]
    tasks = users + [gtask]
    trace_dir = None
    try:
        await asyncio.sleep(t["ramp_s"])
        window.t_open = time.perf_counter()
        window.t_close = window.t_open + run.seconds
        run.setup["ramp"] = window.t_open - run.setup.pop("_ramp0")
        run.reload_marks["open"] = len(gw.engine.reload_wall_s)
        if run.trace_on:
            await asyncio.sleep(min(t["trace_after_s"],
                                    run.seconds / 4))
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
            span = inst.span("bench.trace")
            span.__enter__()
            t0 = time.perf_counter()
            await asyncio.sleep(min(t["trace_s"], run.seconds / 2))
            run.trace_span = (t0, time.perf_counter())
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        await asyncio.sleep(max(0.0, window.t_close - time.perf_counter()))
        run.reload_marks["close"] = len(gw.engine.reload_wall_s)
        run.grace_deadline = window.t_close + t["grace_s"]
        while time.perf_counter() < run.grace_deadline:
            if gtask.done():
                break
            if all(x.t_first is not None for x in run.attempted()):
                break
            await asyncio.sleep(0.05)
    finally:
        for x in tasks:
            x.cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
    for x, res in zip(tasks, results):
        if isinstance(res, BaseException) \
                and not isinstance(res, asyncio.CancelledError):
            raise BenchError(f"a serving task failed: {res!r}") from res
    if trace_dir is not None:
        from bench.trace.reduce import Trace, events_from_xplane
        pbs = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if not pbs:
            raise BenchError("the profiler wrote no trace")
        run.trace = Trace(events_from_xplane(str(pbs[-1])))
        if run.dump_trace:
            from bench.trace.reduce import describe_xplane
            with open(run.dump_trace + ".planes.json", "w") as f:
                json.dump(describe_xplane(str(pbs[-1])), f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)


def _count_compiles(run: BenchRun):
    from jax import monitoring

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration" \
                and run.window.t_open is not None \
                and run.window.t_close is not None \
                and run.in_window(time.perf_counter()):
            run.compiles_in_window += 1
    monitoring.register_event_duration_secs_listener(listener)


# ----------------------------------------------------------------------
def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, require_tpu: bool = True,
            users: Optional[int] = None, fault=None,
            control: bool = False, check_outputs: bool = True,
            dump_trace: Optional[str] = None) -> dict:
    """One run; returns the result object. ``users`` overrides the
    traffic's population (the knee sweep); ``fault`` is a function that
    breaks the served path underneath (the tests' fault checks);
    ``control`` puts the float8 control in the program's place in the
    comparison, which must then read ``correct`` false."""
    import jax
    from bench import peaks as peaks_mod
    cell = spec.resolve_cell(cell_name, root)
    devs = tpu_devices(cell.chips) if require_tpu else jax.devices()[:1]
    dev = devs[0]
    run = BenchRun(cell=cell, seed=seed, seconds=seconds,
                   trace_on=trace, conf=cell.config,
                   engine_conf=cell.config["engine"],
                   dump_trace=dump_trace)
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs)}
    if require_tpu:
        run.peak = peaks_mod.peaks(dev.device_kind)
        log(f"compile cache: {compile_cache()}")
    readers = spec.readers(cell.per_layer if trace else cell.end_to_end,
                           root)
    run.setup["imports"] = time.perf_counter() - T_START
    _count_compiles(run)

    from bench import weights
    t0 = time.perf_counter()
    cfg = cell.arch.program_config(run.conf)
    params = weights.make(cell.arch.shapes(run.conf),
                          run.conf["torch_dtype"], seed, dev)
    jax.block_until_ready(params)
    from repro.models import init_params
    if not weights.same_layout(params, lambda: init_params(
            cfg, jax.random.PRNGKey(0))):
        raise BenchError("the weight tree does not match the program's "
                         "layout")
    run.setup["init"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    gw = build_gateway(run.conf, cfg, params)
    eng = gw.engine
    if require_tpu and eng.interpret:
        raise BenchError("the engine runs its kernels interpreted")
    run.setup["compile"] = time.perf_counter() - t0
    inst = Instruments(run, gw, trace, dev)
    inst.install()
    if fault is not None:
        fault(gw)

    t0 = time.perf_counter()
    e = run.engine_conf
    n_users = users or cell.traffic["users"]
    pop = generator.population(cell.traffic, seed, users=n_users)
    run.users, run.mean_cycle_s = n_users, pop.mean_cycle_s
    build_histories(run, gw, pop)
    run.setup["history"] = time.perf_counter() - t0
    # warm again now that the page store is the step's own output, as it
    # is in the window: the first warm-up saw freshly made pages, which
    # JAX keys apart, so each query bucket would compile once more
    t0 = time.perf_counter()
    from repro.serving.gateway.harness import _warm_engine
    _warm_engine(eng, min(e["prefill_chunk"], e["round_token_budget"]))
    run.launches.clear()
    run.setup["rewarm"] = time.perf_counter() - t0
    run.setup["_ramp0"] = time.perf_counter()

    asyncio.run(_serve(run, gw, pop, inst))
    inst.uninstall()
    run.setup["total"] = run.window.t_open - T_START
    stats = dev.memory_stats() or {}
    run.peak_bytes = stats.get("peak_bytes_in_use")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = readers[m.name](run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    if not check_outputs:
        eng.k_pages = eng.v_pages = None
        del gw, eng, inst, params
        gc.collect()
        return {"attempted": len(run.attempted()), "failed": run.failed(),
                "metrics": metrics, "run": run}
    sample = check.pick_sessions(run)
    engine_ids = {sid: list(eng.sessions[sid].token_ids)
                  for sid in sample.sids if sid in eng.sessions}
    # the program's state goes before the reference runs
    eng.k_pages = eng.v_pages = None
    del gw, eng, inst
    gc.collect()
    verdict = check.compare(run, sample, engine_ids, params,
                            control=control)
    device = dict(run.device)
    if run.peak_bytes is not None:
        device["memory_peak_bytes"] = int(run.peak_bytes)
    out = {"correct": verdict.correct, "attempted": len(run.attempted()),
           "failed": run.failed(), "metrics": metrics, "device": device}
    if dump_trace and run.trace is not None:
        from bench.trace.reduce import save_events
        save_events(run.trace.events, Path(dump_trace))
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    out["check"] = verdict.numbers
    _report(run, out, verdict)
    return out


def _report(run: BenchRun, out: dict, verdict) -> None:
    s = run.setup
    log("setup_s parts: " + " ".join(
        f"{k}={v:.3f}" for k, v in s.items()))
    lat = run.client.speech_late_s
    if lat:
        log(f"client lateness s: p50={statistics.median(lat):.4f} "
            f"max={max(lat):.4f} n={len(lat)}")
    log(f"users={run.users} mean_cycle_s={run.mean_cycle_s:.3f} "
        f"attempted={out['attempted']} failed={out['failed']} "
        f"finished_in_window={run.finished_in_window()} "
        f"launches_in_window={len(run.window_launches())} "
        f"compiles_in_window={run.compiles_in_window} "
        f"exhausted_users={run.client.exhausted_users}")
    for k, v in out["metrics"].items():
        log(f"metric {k} = {v['value']!r} {v['unit']}")
    log(f"check sample: {verdict.covers}")
    for name, n in verdict.numbers.items():
        log(f"check {name}: {n['value']!r} limit {n['limit']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the traced slice's events (gzip "
                         "JSON) here")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control's tokens in place of "
                         "the served ones (must read correct false)")
    args = ap.parse_args(argv)
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace), dump_trace=args.dump_trace,
                      control=bool(args.control))
    except (BenchError, spec.SpecError, ImportError) as e:
        log(f"bench: FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
