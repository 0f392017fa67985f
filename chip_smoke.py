#!/usr/bin/env python3
"""Chip smoke: serve qwen2-1.5b at its published widths on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # replica fleet, one replica per chip

One process drives the served path through the entry points a user
calls: the registry config (28 layers, d_model 1536, 12 q / 2 kv heads,
vocab 151,936, bf16) with random weights from ``--seed``, created on
the chip, served by ``PagedRealtimeEngine`` with the Pallas kernels
compiled for the chip (``interpret=False``).

One chip, in order:

1. warm every Q bucket of the gateway engine (compile seconds), then
   time the warmed fused step per round;
2. the scripted multi-turn demo (``run_multiturn_demo``): evict ->
   offload -> speech-time reload -> barge-in -> resume, with zero
   re-prefill;
3. a few sessions through the asyncio gateway with the ``liveserve``
   policy: completed turns, TTFP P50/P90 as the gateway reports them;
4. the fused step's logits (last prompt token, then one paged decode
   step) against ``models.model.forward`` in float32 under
   ``default_matmul_precision("highest")`` on the same chip.

``--four-chips``: only the fleet path and its comparison — four
replicas, one per chip, serve the ``handoff`` workload so sessions
migrate across devices; the same sessions then run on one replica, and
every session's token stream must be identical (the fleet differential
contract).

It fails unless JAX's first device is a TPU, and it never carries on on
the CPU. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = "qwen2-1.5b"
PAGE = 16
POOL_PAGES = 4096          # ~1.9 GB of K+V at 28,672 B/token
# bf16 weights are shared with the reference, so what separates the two
# is bf16 rounding of activations and KV (2^-9 relative per operation),
# compounded over 28 layers: a few percent. A page, head or position
# bug scrambles the logits, which gives a relative error of order one.
LOGITS_REL_L2_BOUND = 0.1


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(need: int):
    """The JAX devices, or a failure naming what was found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's devices are {devs[0].platform} "
            f"({devs[0].device_kind}); this smoke runs on the chip only")
    if len(devs) < need:
        raise SmokeFailure(f"needs {need} TPU chips, found {len(devs)}")
    return devs


def device_of(x):
    (dev,) = x.devices()
    return dev


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def time_step(eng, q: int, reps: int = 10) -> float:
    """Mean host-clock seconds of one warmed fused round at width ``q``
    (an all-scratch batch), each ended by ``block_until_ready``."""
    import jax
    import jax.numpy as jnp
    B = eng.slots
    args = (eng.params, jnp.zeros((B, q), jnp.int32),
            jnp.zeros((B, q), jnp.int32), eng.k_pages, eng.v_pages,
            jnp.full((B, eng.pages_per_seq), eng.scratch_page, jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.full((B, q), eng.scratch_page, jnp.int32),
            jnp.tile(jnp.arange(q, dtype=jnp.int32) % eng.page_size,
                     (B, 1)))
    jax.block_until_ready(eng._fused_fn(*args)[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(eng._fused_fn(*args)[0])
    return (time.perf_counter() - t0) / reps


def session_streams(engines) -> dict:
    """Per-session token streams (every turn's emitted tokens), from
    whichever replica holds the session record at the end."""
    out = {}
    for e in engines:
        for sid, sess in e.sessions.items():
            assert sid not in out, f"{sid} recorded on two replicas"
            out[sid] = [list(t) for t in sess.history]
    return out


def expected_turns(kind: str, sessions: int, seed: int, barge: float,
                   max_turns: int) -> int:
    from repro.serving.workload import WorkloadConfig, generate
    wl = WorkloadConfig(kind=kind, num_sessions=sessions, seed=seed,
                        p_barge_in=barge, arrival="poisson", rate_rps=2.0)
    return sum(min(len(s.turns), max_turns) for s in generate(wl))


# ----------------------------------------------------------------------
def smoke_demo(model) -> None:
    from repro.serving.paged_engine import run_multiturn_demo
    log("== scripted multi-turn demo ==")
    out = run_multiturn_demo(model=model, page_size=PAGE, log=log)
    check(out["interpret"] is False, "demo engine runs interpreted")
    turns = [t for ts in out["turns"].values() for t in ts]
    check(all(t["re_prefill_tokens"] == 0 for t in turns),
          "a turn re-prefilled committed context")
    check(out["offload_events"] > 0, "no page was offloaded")
    check(out["preload"]["admitted"] >= 1, "no speech-time preload")
    check(any(t["aborted"] for t in turns), "no barge-in happened")
    check(any(t["reload_stall_s"] > 0 for t in turns),
          "no turn reloaded offloaded pages")
    log(f"demo: turns={len(turns)} re_prefill=0 "
        f"offloads={out['offload_events']} "
        f"preload_admitted={out['preload']['admitted']}")


def smoke_gateway(model, seed: int, dev) -> object:
    from repro.serving.gateway import run_gateway_workload
    from repro.serving.gateway.harness import build_gateway, \
        pages_for_context
    sessions, max_turns, max_prompt, max_response = 4, 2, 256, 32
    barge = 0.15
    chunk = 32
    log("== live gateway (liveserve) ==")
    t0 = time.perf_counter()
    gw = build_gateway(
        policy="liveserve", scale=1.0, slots=8, page_size=PAGE,
        pages_per_seq=pages_for_context(
            max_turns * (max_prompt + max_response), PAGE),
        num_pages=POOL_PAGES, round_token_budget=2 * chunk,
        prefill_chunk=chunk, frontier_cap_s=3.0, model=model, seed=seed)
    compile_s = time.perf_counter() - t0
    eng = gw.engine
    check(eng.interpret is False, "gateway engine runs interpreted")
    check(device_of(eng.k_pages) == dev, "pages are off the chip")
    log(f"engine: interpret={eng.interpret} slots={eng.slots} "
        f"page={eng.page_size} pages_per_seq={eng.pages_per_seq} "
        f"pool={eng.num_pages} pages")
    log(f"compile_s (pool + every Q bucket up to {chunk}): "
        f"{compile_s:.3f}")
    for q in (1, chunk):
        log(f"step_ms Q={q} (slots={eng.slots}, warmed): "
            f"{time_step(eng, q) * 1e3:.3f}")
    t0 = time.perf_counter()
    m, gw = run_gateway_workload(
        policy="liveserve", kind="interactive", sessions=sessions,
        barge_in=barge, seed=seed, scale=1.0, max_turns=max_turns,
        max_prompt=max_prompt, max_response=max_response, gateway=gw,
        timeout_s=600.0)
    wall = time.perf_counter() - t0
    s = m.summary()
    want = expected_turns("interactive", sessions, seed, barge, max_turns)
    done = [t for t in m.turns if t.completed or t.barged]
    check(len(done) == want, f"{len(done)} of {want} turns finished")
    check(any(t.barged for t in m.turns), "no barge-in happened")
    prompts = [t.prompt_tokens for t in m.turns]
    log(f"gateway: turns={len(done)}/{want} "
        f"barged={sum(t.barged for t in m.turns)} "
        f"prompt_tokens={min(prompts)}..{max(prompts)} "
        f"rounds={gw.rounds} wall_s={wall:.3f}")
    log(f"ttfp_s p50={s['p50_ttfp']:.4f} p90={s['p90_ttfp']:.4f} "
        f"completed_rps={s['completed_rps']:.4f}")
    return gw


def logits_pair(eng, prompt) -> tuple:
    """Fused-step logits of the last prompt token (chunked prefill over
    pages) and of the one decode step after it, plus the token fed."""
    taps = []
    eng.logit_tap = lambda sid, lg: taps.append(lg)
    slot = eng.submit_turn("ref", prompt, max_new_tokens=2)
    first = None
    while first is None:
        ev = eng.run_round({slot: 32})[slot]
        first = next((t for kind, t in ev if kind == "token"), None)
    eng.run_round({slot: 1})
    eng.logit_tap = None
    return taps[-2], taps[-1], first


def smoke_logits(cfg, params, prompt, pre, dec, tok) -> None:
    """Compare the served logits with the float32 reference (run once
    the serving engines are gone, so their pools are free)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import forward
    log("== logits vs float32 reference ==")
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")

    @jax.jit
    def ref(p, t):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return forward(cfg32, p32, t)[0][0, -2:]

    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref(params, jnp.asarray(
            np.append(prompt, tok)[None])))
    for name, got, w in (("prefill", pre, want[0]), ("decode", dec,
                                                      want[1])):
        err = float(np.linalg.norm(got - w) / np.linalg.norm(w))
        log(f"logits {name}: rel_l2={err:.6f} bound="
            f"{LOGITS_REL_L2_BOUND} argmax_equal="
            f"{int(np.argmax(got)) == int(np.argmax(w))}")
        check(np.isfinite(got).all(), f"{name} logits not finite")
        check(err <= LOGITS_REL_L2_BOUND,
              f"{name} logits rel L2 {err} > {LOGITS_REL_L2_BOUND}")


def one_chip(args) -> list:
    devs = tpu_devices(1)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.gateway.harness import published_model
    log(f"compile cache: {enable_compile_cache() or 'from environment'}")
    dev = devs[0]
    t0 = time.perf_counter()
    model = published_model(MODEL, args.seed)
    cfg, params = model
    import jax
    jax.block_until_ready(params)
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"params={sum(x.size for x in jax.tree.leaves(params))} "
        f"init_s={time.perf_counter() - t0:.3f}")
    check(all(device_of(x) == dev for x in jax.tree.leaves(params)),
          "params are off the chip")
    gw = smoke_gateway(model, args.seed, dev)
    smoke_demo(model)
    import numpy as np
    prompt = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, size=80).astype(np.int32)
    taps = logits_pair(gw.engine, prompt)
    del gw
    smoke_logits(cfg, params, prompt, *taps)
    log(f"peak_bytes_in_use: {peak_bytes(dev)}")
    return devs[:1]


def four_chips(args) -> list:
    devs = tpu_devices(4)[:4]
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.fleet import run_fleet_workload
    from repro.serving.fleet.harness import build_fleet_gateway
    from repro.serving.gateway import run_gateway_workload
    from repro.serving.gateway.harness import pages_for_context, \
        published_model
    log(f"compile cache: {enable_compile_cache() or 'from environment'}")
    import jax
    model = published_model(MODEL, args.seed, devs[0])
    cfg = model[0]
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    run = dict(kind="handoff", sessions=4, barge_in=0.0, seed=args.seed,
               scale=4.0, max_turns=2, max_prompt=64, max_response=16,
               timeout_s=600.0)
    geom = dict(slots=8, page_size=PAGE, num_pages=POOL_PAGES,
                pages_per_seq=pages_for_context(2 * (64 + 16), PAGE))
    log("== fleet: 4 replicas, one per chip ==")
    t0 = time.perf_counter()
    gw = build_fleet_gateway(replicas=4, model=model, devices=devs,
                             seed=args.seed, scale=run["scale"], **geom)
    log(f"compile_s (4 replicas, every Q bucket): "
        f"{time.perf_counter() - t0:.3f}")
    for i, e in enumerate(gw.replicas):
        check(e.interpret is False, f"replica {i} runs interpreted")
        on = {device_of(x) for x in jax.tree.leaves(e.params)}
        on |= {device_of(e.k_pages), device_of(e.v_pages)}
        check(on == {devs[i]}, f"replica {i} arrays on {on}, not "
                               f"{devs[i]}")
    t0 = time.perf_counter()
    m, gw = run_fleet_workload(gateway=gw, **run)
    wall = time.perf_counter() - t0
    moves = [(p.src, p.dst) for p in gw.migrator.completed()]
    check(any(devs[s] != devs[d] for s, d in moves),
          f"no migration crossed devices ({moves})")
    fleet = session_streams(gw.replicas)
    log(f"fleet: turns={len(m.turns)} migrations={moves} "
        f"handoffs={m.handoffs} wall_s={wall:.3f}")
    log(f"fleet ttfp_s p50={m.summary()['p50_ttfp']:.4f} "
        f"p90={m.summary()['p90_ttfp']:.4f}")
    del gw
    log("== the same sessions on one replica ==")
    m1, gw1 = run_gateway_workload(
        policy="liveserve", model=model, **run, **geom)
    single = session_streams([gw1.engine])
    check(fleet == single, "fleet token streams differ from the "
                           "one-replica run")
    log(f"token streams identical: {len(fleet)} sessions, "
        f"{sum(len(t) for ts in fleet.values() for t in ts)} tokens")
    for d in devs:
        log(f"peak_bytes_in_use {d}: {peak_bytes(d)}")
    return devs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet and its "
                         "one-replica comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(HERE, "src"))
    t0 = time.perf_counter()
    try:
        devs = four_chips(args) if args.four_chips else one_chip(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"total_s: {time.perf_counter() - t0:.3f}")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
