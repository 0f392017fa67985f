"""Serving launcher — runs the realtime interaction pipeline.

Simulated pipeline (paper-scale policies on the virtual clock):

  PYTHONPATH=src python -m repro.launch.serve --model qwen3-omni-like \
      --workload interactive --concurrency 12 --barge-in 0.5 \
      --system liveserve

Live gateway (event-driven front-end over the real paged JAX data
plane, scaled wall clock, CPU-runnable — DESIGN.md §4):

  PYTHONPATH=src python -m repro.launch.serve --engine live \
      --workload interactive --sessions 8 --barge-in 0.3 \
      --system liveserve --clock-scale 4

Real engine demo (scripted multi-turn conversation, no gateway):

  PYTHONPATH=src python -m repro.launch.serve --engine real

``--model qwen2-1.5b`` makes live/real serve that registry config at its
published widths and dtype (random weights from ``--seed``, created on
the device) with 16-token pages; without it they serve the reduced
CPU-runnable config. On a TPU the Pallas kernels compile for the chip;
elsewhere they run in interpret mode.

walks evict/offload/preload-reload/barge-in through
PagedRealtimeEngine and reports per-turn TTFT, reload stall, and
re-prefill tokens. Workload/system flags only apply to --engine
sim|live; passing them with --engine real is an error, not a silent
no-op.
"""
from __future__ import annotations

import argparse
import json

from repro.launch.compile_cache import enable_compile_cache

# flags meaningful only for the sim / live engines; --engine real must
# reject them explicitly instead of silently ignoring them
_WORKLOAD_FLAGS = ("workload", "system", "sessions", "concurrency",
                   "barge_in", "kv_gb")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="sim",
                    choices=["sim", "real", "live"],
                    help="sim: event-driven simulator; live: asyncio "
                         "gateway over the paged JAX data plane "
                         "(DESIGN.md §4); real: scripted paged-engine "
                         "demo (DESIGN.md §3)")
    ap.add_argument("--model", default=None,
                    help="sim: a pipeline cost model (qwen3-omni-like | "
                         "ming-omni-like); live/real: a registry config "
                         "served at its published widths (e.g. "
                         "qwen2-1.5b). Default: qwen3-omni-like for sim, "
                         "the reduced CPU-runnable config for live/real")
    ap.add_argument("--workload", default=None,
                    choices=["sharegpt", "interactive", "mixed",
                             "duplex", "toolcall", "handoff"],
                    help="duplex: full-duplex periodic-frame sessions "
                         "(per-token deadlines, deadline_miss_rate); "
                         "toolcall: agentic tool-call pauses (hot-KV "
                         "idle + resume without re-prefill); handoff: "
                         "mid-conversation transfer to another model "
                         "config (use with --replicas >= 2). These "
                         "three need --engine live")
    ap.add_argument("--system", default=None,
                    choices=["liveserve", "vllm-omni", "vllm-omni-wo"])
    ap.add_argument("--sessions", type=int, default=None)
    ap.add_argument("--concurrency", type=int, default=None)
    ap.add_argument("--barge-in", type=float, default=None)
    ap.add_argument("--kv-gb", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    # --engine real | live (the paged data plane)
    ap.add_argument("--fused-step", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="real/live engines: run each round's whole "
                         "token budget (prefill chunks + decode) as one "
                         "jitted launch (DESIGN.md §11). "
                         "--no-fused-step serves on the per-token "
                         "differential-control plane")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="real/live engines: shard the paged KV plane "
                         "over a ('data','model') mesh, e.g. 1x8 "
                         "(DESIGN.md §9). On CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    # --engine live only
    ap.add_argument("--prefix-cache", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="live engine: share committed KV pages across "
                         "sessions with identical prompt prefixes — "
                         "refcounted copy-on-write pages behind a radix "
                         "prefix index (DESIGN.md §13). Off by default "
                         "(the bit-exact no-sharing control)")
    ap.add_argument("--prompt-families", type=int, default=None,
                    help="live engine: assign sessions round-robin to K "
                         "shared-system-prompt families (workload knob "
                         "that makes --prefix-cache hits observable)")
    ap.add_argument("--family-prefix-len", type=int, default=None,
                    help="live engine: shared prefix tokens per family "
                         "(with --prompt-families)")
    ap.add_argument("--clock-scale", type=float, default=None,
                    help="live engine: wall-clock speedup factor")
    ap.add_argument("--slots", type=int, default=None,
                    help="live engine: decode batch rows")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="live engine: KV pool size in pages")
    ap.add_argument("--preload-chunks", type=int, default=None,
                    help="live engine: transfer chunks each round may "
                         "drain between decode sub-batches — the async "
                         "chunked KV transfer budget that lands "
                         "speech-time preloads off the turn critical "
                         "path (DESIGN.md §10)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["fp32", "int8"],
                    help="live engine: KV wire format on the offload "
                         "path (DESIGN.md §14). int8 block-quantizes "
                         "host copies (~4x less modeled PCIe per page, "
                         "tolerance-gated quality); fp32 is the "
                         "bit-exact default")
    ap.add_argument("--spec-decode", type=int, default=None,
                    metavar="K",
                    help="live engine: draft up to K tokens per decode "
                         "slot per round and verify them in the same "
                         "fused launch (self-speculative prompt-lookup "
                         "drafts; DESIGN.md §16). Lossless: accepted "
                         "streams are bit-exact vs K=0. Needs "
                         "--fused-step; composes with --mesh, "
                         "--replicas, --prefix-cache, --kv-quant")
    ap.add_argument("--autotune", default=None, metavar="CACHE.json",
                    help="live engine: consult (and require) a kernel "
                         "autotune cache JSON at jit time — build one "
                         "with benchmarks/autotune_bench.py "
                         "(DESIGN.md §16)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="live engine: N data-parallel engine replicas "
                         "behind one gateway, with live cross-replica "
                         "KV migration (DESIGN.md §12). Composes with "
                         "--mesh: every replica shards its page store "
                         "over the same mesh")
    args = ap.parse_args()
    enable_compile_cache()

    if args.engine != "live":
        live_only = [f"--{f.replace('_', '-')}" for f in
                     ("clock_scale", "slots", "kv_pages",
                      "preload_chunks", "replicas", "prefix_cache",
                      "prompt_families", "family_prefix_len",
                      "kv_quant", "spec_decode", "autotune")
                     if getattr(args, f) is not None]
        if live_only:
            ap.error(f"{', '.join(live_only)} only apply to "
                     f"--engine live")
    if args.engine == "sim" and args.mesh is not None:
        ap.error("--mesh shards the real paged data plane; the simulator "
                 "models costs, not placement (use --engine real|live)")
    if args.engine == "sim" and not args.fused_step:
        ap.error("--no-fused-step selects the paged data plane's "
                 "per-token control; the simulator has no data plane "
                 "(use --engine real|live)")
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_serving_mesh
        try:
            mesh = make_serving_mesh(args.mesh)
        except ValueError as e:
            ap.error(str(e))
    if args.engine == "real":
        given = [f"--{f.replace('_', '-')}" for f in _WORKLOAD_FLAGS
                 if getattr(args, f) is not None]
        if given:
            ap.error(
                f"--engine real runs a fixed scripted demo and does not "
                f"take {', '.join(given)}; use --engine live (real data "
                f"plane under load) or --engine sim (paper-scale "
                f"simulation)")
    from repro.serving.costmodel import PIPELINES
    model, page_size = None, 8
    if args.engine == "sim":
        if args.model is not None and args.model not in PIPELINES:
            ap.error(f"--engine sim models {'|'.join(PIPELINES)}")
    elif args.model is not None:
        from repro.configs import list_configs
        if args.model not in list_configs():
            ap.error(f"--model {args.model!r} is not a registry config "
                     f"({', '.join(list_configs())})")
        from repro.serving.gateway.harness import published_model
        model, page_size = published_model(args.model, args.seed), 16

    if args.engine == "real":
        from repro.serving.paged_engine import run_multiturn_demo
        out = run_multiturn_demo(
            seed=args.seed, mesh=mesh, fused_step=args.fused_step,
            model=model, page_size=page_size,
            log=(lambda *_a, **_k: None) if args.json else print)
        if args.json:
            print(json.dumps(out, indent=1, default=str))
        return

    # shared workload defaults for sim and live
    workload = args.workload or "interactive"
    if args.engine != "live" \
            and workload in ("duplex", "toolcall", "handoff"):
        ap.error(f"--workload {workload} drives gateway-level "
                 f"interaction events (frame deadlines, tool pauses, "
                 f"handoffs); use --engine live")
    system = args.system or "liveserve"
    sessions = args.sessions if args.sessions is not None else 32
    barge_in = args.barge_in if args.barge_in is not None else 0.0

    if args.engine == "live":
        from repro.serving.gateway.harness import pages_for_context
        bad = [n for n, v in (("--kv-gb", args.kv_gb),
                              ("--concurrency", args.concurrency))
               if v is not None]
        if bad:
            ap.error(f"--engine live is open-loop on a page pool; "
                     f"{', '.join(bad)} do not apply (use --kv-pages "
                     f"for pool size)")
        policies = {"liveserve": "liveserve", "vllm-omni": "fcfs"}
        if system not in policies:
            ap.error(f"--engine live supports --system "
                     f"{'|'.join(policies)} (the paged data plane needs "
                     f"an offload tier; 'vllm-omni-wo' discards KV — "
                     f"use --engine sim for that baseline)")
        replicas = args.replicas if args.replicas is not None else 1
        if replicas < 1:
            ap.error("--replicas must be >= 1")
        spec_decode = args.spec_decode if args.spec_decode is not None \
            else 0
        if spec_decode < 0:
            ap.error("--spec-decode must be >= 0")
        if spec_decode > 0 and not args.fused_step:
            ap.error("--spec-decode verifies drafts in one fused launch "
                     "and cannot run on the per-token control plane; "
                     "drop --no-fused-step (DESIGN.md §16)")
        run_kw = dict(
            policy=policies[system], kind=workload, sessions=sessions,
            barge_in=barge_in, seed=args.seed,
            scale=(args.clock_scale
                   if args.clock_scale is not None else 4.0),
            slots=args.slots if args.slots is not None else 8,
            num_pages=(args.kv_pages if args.kv_pages is not None
                       or model is None else 4096),
            mesh=mesh, model=model, page_size=page_size,
            preload_chunks=(args.preload_chunks
                            if args.preload_chunks is not None else 1),
            fused_step=args.fused_step,
            prefix_cache=bool(args.prefix_cache),
            kv_quant=args.kv_quant or "fp32",
            spec_decode=spec_decode,
            autotune=args.autotune,
            prompt_families=(args.prompt_families
                             if args.prompt_families is not None else 0),
            family_prefix_len=(args.family_prefix_len
                               if args.family_prefix_len is not None
                               else 0),
            # the family prefix rides on top of the per-turn prompt
            # draw, so grow each session's context window to fit it
            # (64 tokens by default)
            pages_per_seq=pages_for_context(
                64 + (args.family_prefix_len or 0), page_size),
            frontier_cap_s=3.0 if system == "liveserve" else None)
        if replicas > 1:
            from repro.serving.fleet import run_fleet_workload
            import jax
            # one replica per device where the host has enough of them
            # (a mesh places its own pages)
            devices = (jax.devices()[:replicas]
                       if mesh is None and len(jax.devices()) >= replicas
                       else None)
            m, gw = run_fleet_workload(replicas=replicas, devices=devices,
                                       **run_kw)
            engines = list(gw.replicas)
        else:
            from repro.serving.gateway import run_gateway_workload
            m, gw = run_gateway_workload(**run_kw)
            engines = [gw.engine]
        s = m.summary()
        s["rounds"] = gw.rounds
        s["max_over_frontier_s"] = gw.max_over_frontier_s
        off = sum(e.transfer.stats.reload_pages_off_path
                  for e in engines)
        on = sum(e.transfer.stats.reload_pages_on_path for e in engines)
        s["transfer_overlap_frac"] = off / (off + on) if off + on else 0.0
        if replicas > 1:
            done = gw.migrator.completed()
            for i in range(replicas):
                mig_in = sum(1 for p in done if p.dst == i)
                mig_out = sum(1 for p in done if p.src == i)
                s[f"replica{i}"] = (
                    f"routed={gw.router.routed[i]} "
                    f"migrated_in={mig_in} migrated_out={mig_out} "
                    f"peak_occupancy={m.replica_occupancy[i]:.3f}"
                    + (" [drained]" if i in gw.router.draining else ""))
    else:
        from repro.serving.simulator import run_sim
        from repro.serving.workload import WorkloadConfig

        systems = {
            "liveserve": dict(policy="liveserve"),
            "vllm-omni": dict(policy="fcfs", kv_policy="lru",
                              preload=False),
            "vllm-omni-wo": dict(policy="fcfs", kv_policy="none",
                                 preload=False),
        }
        pipe = PIPELINES[args.model or "qwen3-omni-like"](
            kv_capacity_gb=args.kv_gb if args.kv_gb is not None else 4.0)
        wl = WorkloadConfig(
            kind=workload, num_sessions=sessions,
            concurrency=(args.concurrency
                         if args.concurrency is not None else 8),
            seed=args.seed, p_barge_in=barge_in)
        m = run_sim(pipe, wl, until=3600.0, **systems[system])
        s = m.summary()

    if args.json:
        print(json.dumps(s, indent=1))
    else:
        for k, v in s.items():
            print(f"{k:20s} {v:.4f}" if isinstance(v, float)
                  else f"{k:20s} {v}")


if __name__ == "__main__":
    main()
