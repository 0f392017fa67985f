"""Tensor-sharded paged data plane (DESIGN.md §9).

Kernel-level parity: ``sharded_paged_attention`` / ``sharded_flash_
prefill`` / the shard_map'd ``paged_decode_step`` against the unsharded
kernels and ``kernels/ref.py`` across dtypes, page sizes, ragged
``seq_lens``, and head counts that do / do not divide the 'model' axis
(exercising every layout kind: heads, slots, and the replication
fallback).

Engine-level: the mesh-sharded ``PagedRealtimeEngine`` is **token-
exact** with the single-device engine on the same multi-turn trace —
prefill, decode, physical evict/offload/reload, barge-in — and under
the deterministic ``ReplayGateway`` the full scheduling-visible record
(TTFP rounds, completion order, barges) is identical.

The in-process tests need >1 jax device: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``multidevice`` job does). On a single-device host a subprocess smoke
keeps kernel parity covered in tier-1.
"""
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention

NDEV = len(jax.devices())
multidev = pytest.mark.skipif(
    NDEV < 2,
    reason="needs >1 device; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


def _mesh(m):
    return jax.make_mesh((1, m), ("data", "model"))


def _layout(num_kv_heads, page, m):
    from repro.distributed.paged import PagedKVLayout
    return PagedKVLayout(SimpleNamespace(num_kv_heads=num_kv_heads),
                         _mesh(m), page)


def _paged_case(key, B, Hq, Hkv, D, page, pps, dtype):
    num_pages = B * pps + 3
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (num_pages, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (num_pages, page, Hkv, D), dtype)
    bt = jax.random.permutation(
        ks[3], num_pages)[:B * pps].reshape(B, pps).astype(jnp.int32)
    # ragged lengths incl. a partially-filled last page and a 1-token row
    sl = jnp.array([(i * 7) % (page * pps) + 1 for i in range(B)],
                   jnp.int32)
    return q, kp, vp, bt, sl


# ======================================================================
# kernel: position remap + stats (single device — always runs)
# ======================================================================
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_stats_merge_matches_ref(dtype):
    """The shard-side contract without a mesh: slicing each page's slots
    into S stripes, computing per-stripe (o, m, l) with the position
    remap, and flash-merging reproduces the full softmax exactly."""
    q, kp, vp, bt, sl = _paged_case(jax.random.PRNGKey(0), 3, 4, 2, 16,
                                    8, 4, dtype)
    want = ref.paged_attention_ref(q, kp, vp, bt, sl)
    for S in (2, 4):
        psl = kp.shape[1] // S
        outs = []
        for s in range(S):
            o, m, l = paged_attention(
                q, kp[:, s * psl:(s + 1) * psl],
                vp[:, s * psl:(s + 1) * psl], bt, sl - s * psl,
                pos_stride=kp.shape[1], return_stats=True, interpret=True)
            outs.append((o.astype(jnp.float32), m, l))
        m_star = jnp.max(jnp.stack([m for _, m, _ in outs]), axis=0)
        ws = [l * jnp.exp(m - m_star) for _, m, l in outs]
        den = jnp.maximum(sum(ws), 1e-30)
        got = sum(o * w[..., None] for (o, _, _), w in zip(outs, ws)) \
            / den[..., None]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=_tol(dtype), atol=_tol(dtype))


def test_paged_attention_default_unchanged():
    """No stats, no remap: byte-compatible with the pre-sharding API."""
    q, kp, vp, bt, sl = _paged_case(jax.random.PRNGKey(1), 2, 8, 2, 32,
                                    8, 5, jnp.float32)
    out = paged_attention(q, kp, vp, bt, sl, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ======================================================================
# shard_map kernel parity (multi-device)
# ======================================================================
@multidev
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,D,page,pps,m,kind",
    [
        (2, 8, 4, 16, 8, 4, 2, "heads"),     # Hkv % M == 0
        (3, 8, 2, 16, 8, 4, 4, "slots"),     # heads don't divide, page does
        (2, 4, 2, 32, 8, 5, 8, "slots"),
        (2, 6, 3, 16, 5, 4, 2, "replicated"),  # neither divides
        (1, 4, 1, 16, 16, 3, 8, "slots"),    # MQA, 1-token rows
    ])
def test_sharded_paged_attention_parity(B, Hq, Hkv, D, page, pps, m,
                                        kind, dtype):
    if m > NDEV:
        pytest.skip(f"mesh model={m} > {NDEV} devices")
    from repro.distributed.paged import sharded_paged_attention
    layout = _layout(Hkv, page, m)
    assert layout.kind == kind, layout
    q, kp, vp, bt, sl = _paged_case(jax.random.PRNGKey(2), B, Hq, Hkv, D,
                                    page, pps, dtype)
    got = sharded_paged_attention(layout, q, kp, vp, bt, sl,
                                  interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype))


@multidev
@pytest.mark.parametrize("Hq,Hkv,m", [(8, 2, 2), (4, 1, 2), (6, 3, 2)])
def test_sharded_flash_prefill_parity(Hq, Hkv, m):
    if m > NDEV:
        pytest.skip(f"mesh model={m} > {NDEV} devices")
    from repro.distributed.paged import sharded_flash_prefill
    layout = _layout(Hkv, 8, m)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, Hq, 32, 16))
    k = jax.random.normal(ks[1], (2, Hkv, 96, 16))
    v = jax.random.normal(ks[2], (2, Hkv, 96, 16))
    got = sharded_flash_prefill(layout, q, k, v, q_offset=64, block_q=16,
                                block_kv=16, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, q_offset=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ======================================================================
# shard_map'd decode step vs the single-device step (multi-device)
# ======================================================================
@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config, reduced
    from repro.models import init_params
    cfg = reduced(get_config("qwen2-1.5b"), layers=2, d_model=64,
                  vocab=331)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _step_case(cfg, B, page, pps, key):
    hd = cfg.resolved_head_dim
    num_pages = B * pps
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(
        ks[0], (cfg.num_layers, num_pages + 1, page, cfg.num_kv_heads * hd))
    vp = jax.random.normal(ks[1], kp.shape)
    perm = np.asarray(jax.random.permutation(ks[2], num_pages))
    bt = perm[:B * pps].reshape(B, pps).astype(np.int32)
    written = np.array([(i * 11) % (page * (pps - 1)) for i in range(B)],
                       np.int32)
    tokens = np.asarray(
        jax.random.randint(ks[3], (B,), 0, cfg.vocab_size), np.int32)
    wp = np.array([bt[i, written[i] // page] for i in range(B)], np.int32)
    ws = written % page
    return (tokens, written, kp, vp, bt.astype(np.int32), written + 1,
            wp, ws)


@multidev
@pytest.mark.parametrize("page,m", [(8, 2), (8, 4), (8, 8), (6, 4)])
def test_sharded_decode_step_matches_unsharded(tiny, page, m):
    """The shard_map'd step — page writes included — against the plain
    jitted step on identical inputs. (8, 2) runs the heads layout,
    (8, 4/8) slots, (6, 4) the replication fallback."""
    if m > NDEV:
        pytest.skip(f"mesh model={m} > {NDEV} devices")
    import functools
    from repro.distributed.paged import PagedKVLayout, make_sharded_step
    from repro.serving.paged_engine import paged_decode_step
    cfg, params = tiny
    layout = PagedKVLayout(cfg, _mesh(m), page)
    tokens, written, kp, vp, bt, sl, wp, ws = _step_case(
        cfg, 3, page, 4, jax.random.PRNGKey(4))
    plain = jax.jit(functools.partial(paged_decode_step, cfg,
                                      interpret=True))
    lg0, k0, v0 = plain(params, tokens, written, kp, vp, bt, sl, wp, ws)
    sharded = make_sharded_step(cfg, layout, interpret=True)
    kp_s = jax.device_put(kp, layout.page_sharding())
    vp_s = jax.device_put(vp, layout.page_sharding())
    lg1, k1, v1 = sharded(params, jnp.asarray(tokens),
                          jnp.asarray(written), kp_s, vp_s,
                          jnp.asarray(bt), jnp.asarray(sl),
                          jnp.asarray(wp), jnp.asarray(ws))
    np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k0), np.asarray(k1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                               rtol=2e-5, atol=2e-5)


# ======================================================================
# engine differential (multi-device): the acceptance criterion
# ======================================================================
def _drive_trace(eng, cfg):
    """Prefill + decode + physical evict/reload + barge-in, multi-turn."""
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, cfg.vocab_size, size=10)
    p2 = rng.integers(0, cfg.vocab_size, size=6)
    pb = rng.integers(0, cfg.vocab_size, size=8)
    eng.add_session("a", p1, max_new_tokens=6)
    eng.run_to_completion()
    now = eng.clock.now()
    assert eng.kv.evict(2, now) == 2          # physical offload via hook
    eng.add_session("b", pb, max_new_tokens=4)  # clobber freed pages
    eng.run_to_completion()
    eng.start_turn("a", p2, max_new_tokens=8)   # reload path
    for _ in range(3):
        eng.step()
    eng.barge_in("a")
    eng.start_turn("a", rng.integers(0, cfg.vocab_size, size=4),
                   max_new_tokens=5)
    eng.run_to_completion()
    eng.check_invariants()
    return {sid: s.history for sid, s in eng.sessions.items()}


@multidev
@pytest.mark.parametrize("shape", [(1, 2), (1, 4), (1, 8), (2, 2)])
def test_sharded_engine_token_exact_full_trace(tiny, shape):
    if shape[0] * shape[1] > NDEV:
        pytest.skip(f"mesh {shape} > {NDEV} devices")
    from repro.serving.paged_engine import PagedRealtimeEngine
    cfg, params = tiny
    kw = dict(slots=2, page_size=8, pages_per_seq=16, num_pages=6)
    want = _drive_trace(PagedRealtimeEngine(cfg, params, **kw), cfg)
    mesh = jax.make_mesh(shape, ("data", "model"))
    eng = PagedRealtimeEngine(cfg, params, mesh=mesh, **kw)
    got = _drive_trace(eng, cfg)
    assert got == want
    # reloaded pages really round-tripped through DRAM on the sharded
    # store (the offload/evict happened physically, not just in books)
    assert eng.kv.reloaded_blocks >= 2
    assert eng.offload_events


@multidev
def test_sharded_replay_differential_matches_unsharded(tiny):
    """The full deterministic replay (scheduler + frontier cap + barge
    storms) on a sharded engine produces the identical scheduling-
    visible record as the single-device engine."""
    from repro.serving.gateway.replay import ReplayConfig, run_replay
    from repro.serving.paged_engine import PagedRealtimeEngine
    from repro.serving.workload import WorkloadConfig
    cfg, params = tiny
    wl = WorkloadConfig(kind="interactive", num_sessions=4, seed=5,
                        p_barge_in=0.5, arrival="poisson", rate_rps=4.0)
    mesh = jax.make_mesh((1, min(8, NDEV)), ("data", "model"))

    def run(use_mesh):
        def factory(clock):
            return PagedRealtimeEngine(
                cfg, params, slots=2, page_size=8, pages_per_seq=8,
                clock=clock, mesh=mesh if use_mesh else None)
        m, gw = run_replay(factory, wl, ReplayConfig(), seed=5)
        return [(t.session_id, t.turn_index, t.ttfp, t.finish_time,
                 t.completed, t.barged, t.talker_generated)
                for t in m.turns], gw

    plain, _ = run(False)
    sharded, gw = run(True)
    assert sharded == plain
    assert gw.max_over_frontier_s <= ReplayConfig().audio_per_token_s + 1e-6


@multidev
def test_live_gateway_on_sharded_engine(tiny):
    """The asyncio gateway end to end (warm-up compile included) over a
    mesh-sharded engine: sessions complete, barges ack, pages free."""
    from repro.serving.gateway import run_gateway_workload
    from repro.serving.gateway.harness import build_gateway
    mesh = jax.make_mesh((1, min(8, NDEV)), ("data", "model"))
    gw = build_gateway(policy="liveserve", scale=16.0, model=tiny,
                       slots=4, page_size=8, pages_per_seq=8, mesh=mesh,
                       frontier_cap_s=3.0)
    assert gw.engine.layout is not None
    m, gw = run_gateway_workload(
        policy="liveserve", sessions=4, barge_in=0.5, seed=1,
        max_prompt=8, max_response=8, max_turns=2, speech_scale=0.5,
        gateway=gw, timeout_s=300)
    eng = gw.engine
    assert m.completed_sessions == 4
    assert all(t.completed or t.barged for t in m.turns)
    assert all(s is None for s in eng.slot_state.values())
    assert eng.pool.free_pages == eng.num_pages
    eng.check_invariants()


# ======================================================================
# single-device tier-1 smoke: kernel parity in an 8-device subprocess
# ======================================================================
def test_sharded_kernels_subprocess_smoke():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from types import SimpleNamespace
        from repro.distributed.paged import (PagedKVLayout,
                                             sharded_paged_attention)
        from repro.kernels import ref
        assert len(jax.devices()) == 8
        for Hkv, page, m, kind in ((4, 8, 2, "heads"), (2, 8, 8, "slots"),
                                   (3, 5, 4, "replicated")):
            layout = PagedKVLayout(SimpleNamespace(num_kv_heads=Hkv),
                                   jax.make_mesh((1, m),
                                                 ("data", "model")), page)
            assert layout.kind == kind, (layout.kind, kind)
            B, Hq, D, pps = 2, 2 * Hkv, 16, 3
            P = B * pps + 2
            ks = jax.random.split(jax.random.PRNGKey(0), 4)
            q = jax.random.normal(ks[0], (B, Hq, D))
            kp = jax.random.normal(ks[1], (P, page, Hkv, D))
            vp = jax.random.normal(ks[2], (P, page, Hkv, D))
            bt = jax.random.permutation(ks[3], P)[:B * pps] \\
                .reshape(B, pps).astype(jnp.int32)
            sl = jnp.array([1, page * pps - 2], jnp.int32)
            got = sharded_paged_attention(layout, q, kp, vp, bt, sl,
                                          interpret=True)
            want = ref.paged_attention_ref(q, kp, vp, bt, sl)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout
