"""One-call end-to-end gateway runner (DESIGN.md §4).

Shared by ``launch/serve.py --engine live``, ``chip_smoke.py``,
``benchmarks/gateway_bench.py``, the examples, and the integration
tests: build a model (the laptop-scale ``tiny_model`` by default, or a
registry config at its published widths via ``published_model``) + a
``PagedRealtimeEngine`` on a ``ScaledWallClock``,
put a ``RealtimeGateway`` with the requested policy in front of it, and
replay a ``serving/workload.py`` trace through in-process clients.
Returns the same ``Metrics`` object the simulator produces, so
sim-vs-real comparisons are a dict-diff away.
"""
from __future__ import annotations

import asyncio
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import SchedulerConfig
from repro.serving.gateway.client import LoadGenConfig, run_load
from repro.serving.gateway.clock import ScaledWallClock
from repro.serving.gateway.gateway import GatewayConfig, RealtimeGateway
from repro.serving.metrics import Metrics
from repro.serving.workload import WorkloadConfig


def tiny_model(seed: int = 0, vocab: int = 331) -> Tuple[object, dict]:
    """The CPU-runnable reduced config the live data plane serves."""
    from repro.configs import get_config, reduced
    from repro.models import init_params
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                  vocab=vocab)
    return cfg, init_params(cfg, jax.random.PRNGKey(seed))


def published_model(name: str, seed: int = 0,
                    device=None) -> Tuple[object, dict]:
    """A registry config at its published widths and dtype, with random
    weights drawn from ``seed`` by a jitted ``init_params`` — so they are
    created on ``device`` (default: JAX's default device), never staged
    through the host."""
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config(name)
    init = jax.jit(functools.partial(init_params, cfg),
                   out_shardings=(None if device is None
                                  else SingleDeviceSharding(device)))
    return cfg, init(jax.random.PRNGKey(seed))


def pages_for_context(tokens: int, page_size: int) -> int:
    """Block-table width that holds ``tokens`` of committed context."""
    return -(-tokens // page_size)


def _warm_engine(eng, prefill_chunk: int = 1) -> None:
    """Compile the fixed-shape paged step before the clock starts: a
    padded all-scratch round exercises the exact signature every serving
    round uses, so multi-second jit time never lands in TTFP. On the
    fused plane this warms every query-axis bucket up to the gateway's
    prefill chunk (the fused step compiles one executable per power-of-
    two bucket — DESIGN.md §11)."""
    from repro.serving.paged_engine import _q_bucket
    B = eng.slots
    scratch = np.full((B,), eng.scratch_page, np.int32)
    if not eng.fused_step:
        out = eng._step_fn(
            eng.params, jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), eng.k_pages, eng.v_pages,
            jnp.full((B, eng.pages_per_seq), eng.scratch_page, jnp.int32),
            jnp.ones((B,), jnp.int32), jnp.asarray(scratch),
            jnp.zeros((B,), jnp.int32))
        jax.block_until_ready(out[0])        # scratch-page writes only
        return
    # a spec engine runs _spec_fn on EVERY fused round, so that is the
    # executable to warm; drafts also raise the largest decode row to
    # 1 + spec_decode tokens, so warm that bucket too
    fn = eng._spec_fn if getattr(eng, "_spec_fn", None) is not None \
        else eng._fused_fn
    top = max(prefill_chunk, 1 + getattr(eng, "spec_decode", 0))
    q = 1
    while True:
        out = fn(
            eng.params, jnp.zeros((B, q), jnp.int32),
            jnp.zeros((B, q), jnp.int32), eng.k_pages, eng.v_pages,
            jnp.full((B, eng.pages_per_seq), eng.scratch_page, jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.full((B, q), eng.scratch_page, jnp.int32),
            jnp.tile(jnp.arange(q, dtype=jnp.int32) % eng.page_size,
                     (B, 1)))
        jax.block_until_ready(out[0])        # scratch-page writes only
        if q >= _q_bucket(top):
            break
        q *= 2


def build_gateway(*, policy: str = "liveserve", scale: float = 8.0,
                  slots: int = 8, page_size: int = 8,
                  pages_per_seq: int = 8, num_pages: Optional[int] = None,
                  audio_per_token_s: float = 0.25,
                  round_token_budget: int = 16, prefill_chunk: int = 16,
                  frontier_cap_s: Optional[float] = None,
                  sched_cfg: Optional[SchedulerConfig] = None,
                  model: Optional[tuple] = None,
                  mesh=None, seed: int = 0,
                  preload_chunks: int = 1,
                  fused_step: bool = True,
                  prefix_cache: bool = False,
                  kv_quant: str = "fp32",
                  spec_decode: int = 0,
                  proposer=None,
                  autotune: Optional[str] = None) -> RealtimeGateway:
    """``mesh``: a ('data','model') jax mesh shards the engine's page
    store over 'model' (DESIGN.md §9) — on a laptop run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a
    virtual host-platform mesh; everything above the engine is
    mesh-agnostic. ``preload_chunks``: transfer chunks each round may
    drain between decode sub-batches (the serve flag of the same name;
    DESIGN.md §10). ``fused_step=False`` serves on the per-token
    differential-control plane (one launch per token — DESIGN.md §11).
    ``spec_decode=K`` drafts up to K tokens per decode slot per round
    and verifies them in the same fused launch (DESIGN.md §16);
    ``autotune`` names a kernel-config cache JSON to consult at jit
    time (``repro.kernels.autotune``)."""
    from repro.serving.paged_engine import PagedRealtimeEngine
    if autotune:
        from repro.kernels import autotune as at
        at.enable(autotune)
    cfg, params = model if model is not None else tiny_model(seed)
    clock = ScaledWallClock(scale)
    eng = PagedRealtimeEngine(cfg, params, slots=slots,
                              page_size=page_size,
                              pages_per_seq=pages_per_seq,
                              num_pages=num_pages, clock=clock,
                              mesh=mesh,
                              transfer_chunks_per_round=preload_chunks,
                              fused_step=fused_step,
                              prefix_cache=prefix_cache,
                              kv_quant=kv_quant,
                              spec_decode=spec_decode,
                              proposer=proposer)
    _warm_engine(eng, min(prefill_chunk, round_token_budget))
    gw = RealtimeGateway(eng, cfg=GatewayConfig(
        policy=policy, audio_per_token_s=audio_per_token_s,
        round_token_budget=round_token_budget,
        prefill_chunk=prefill_chunk, frontier_cap_s=frontier_cap_s,
        sched=sched_cfg))
    return gw


def run_gateway_workload(*, policy: str = "liveserve",
                         kind: str = "interactive", sessions: int = 8,
                         barge_in: float = 0.0, seed: int = 0,
                         arrival: str = "poisson", rate_rps: float = 2.0,
                         scale: float = 8.0, max_turns: int = 2,
                         max_prompt: int = 16, max_response: int = 12,
                         speech_scale: float = 1.0,
                         prompt_families: int = 0,
                         family_prefix_len: int = 0,
                         gateway: Optional[RealtimeGateway] = None,
                         timeout_s: Optional[float] = None,
                         **gw_kw) -> Tuple[Metrics, RealtimeGateway]:
    """Replay an open-loop workload through a gateway; returns
    (metrics, gateway). Pass ``gateway`` to use a pre-built (and
    pre-compiled, but not yet run) stack; otherwise ``gw_kw`` goes to
    ``build_gateway``. A gateway serves exactly one workload — its
    session registry and metrics are single-run state.
    """
    if gateway is not None:
        assert not gw_kw, "gateway already built; engine kwargs ignored"
        assert gateway.cfg.policy == policy, \
            f"gateway was built for {gateway.cfg.policy!r}, not {policy!r}"
        assert not gateway._stopping and not gateway._sessions, \
            "a RealtimeGateway serves one workload; build a fresh one"
        gw = gateway
    else:
        gw = build_gateway(policy=policy, scale=scale, seed=seed,
                           **gw_kw)
    wl = WorkloadConfig(kind=kind, num_sessions=sessions, seed=seed,
                        p_barge_in=barge_in, arrival=arrival,
                        rate_rps=rate_rps,
                        prompt_families=prompt_families,
                        family_prefix_len=family_prefix_len)
    lcfg = LoadGenConfig(workload=wl, vocab=gw.engine.cfg.vocab_size,
                         max_prompt=max_prompt, max_response=max_response,
                         max_turns=max_turns,
                         audio_per_token_s=gw.cfg.audio_per_token_s,
                         speech_scale=speech_scale, seed=seed)

    async def main():
        gw.clock.restart()
        serve = asyncio.create_task(gw.run())
        load = asyncio.create_task(run_load(gw, lcfg))
        try:
            done, _ = await asyncio.wait(
                {serve, load}, timeout=timeout_s,
                return_when=asyncio.FIRST_COMPLETED)
            if serve in done and load not in done:
                # the serve loop died under live clients: surface its
                # error instead of letting every client block forever
                serve.result()
                raise RuntimeError("gateway serve loop exited early")
            if load not in done:
                raise asyncio.TimeoutError(
                    f"load generator exceeded {timeout_s}s")
            load.result()                # propagate client errors
        except BaseException:
            gw.stop(force=True)
            load.cancel()
            await asyncio.gather(serve, load, return_exceptions=True)
            raise
        gw.stop()
        await serve                      # surface late serve errors

    asyncio.run(main())
    return gw.metrics(), gw
