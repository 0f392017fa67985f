"""The dropless held-expert layer on the served path (DESIGN.md §17).

A tiny Qwen3-MoE share — 2 layers, d 64, 4 q / 2 kv heads, a router 16
experts wide, 4 experts held, top-4 — with random weights from the
benchmark's maker, served by the paged engine and compared with the
plain reference of ``bench/arch/Qwen3MoeForCausalLM.py``:

- prefill in chunks, then decode through ``paged_fused_step``, agree
  with the reference's logits;
- the four shares' partial layer outputs add up to the uncut 16-expert
  reference layer;
- dropless: a token's logits are bitwise the same alone and in a launch
  with other rows and padded rows;
- the engine's routing counters match a hand count, and padded rows
  add nothing to them;
- the ``moe_experts`` kernel matches a plain grouped matmul with empty
  groups, one-row groups, a group over two tiles and padded rows;
- a share's router is made in the served dtype, training's in f32.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec, weights  # noqa: E402
from bench.arch import common  # noqa: E402
from repro.core.session import Phase  # noqa: E402
from repro.kernels import moe_experts as K  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.moe import moe_held, moe_init  # noqa: E402
from repro.serving.paged_engine import PagedRealtimeEngine  # noqa: E402

# f32 program against the f32 reference: the products are the same, in
# another order (and the attention kernel's online softmax), so logits
# agree to rounding; the float8 control lies far outside
LOGIT_TOL = 2e-3


def tiny_conf(**kw) -> dict:
    conf = json.loads(
        (ROOT / "bench/configs/qwen3-30b-a3b-ep8.json").read_text())
    conf.update(name="tiny-moe", num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                moe_intermediate_size=32, router_width=16,
                num_experts_published=16, num_experts=4,
                num_experts_per_tok=4, expert_offset=4, vocab_size=331,
                torch_dtype="float32")
    conf.update(kw)
    return conf


def _model(conf, seed=2**31 + 17):
    arch = spec.architecture(conf)
    w = weights.make(arch.shapes(conf), conf["torch_dtype"], seed,
                     jax.devices()[0])
    return arch, arch.program_config(conf), w


def _engine(cfg, w, slots=4):
    return PagedRealtimeEngine(cfg, w, slots=slots, page_size=8,
                               pages_per_seq=8, num_pages=64)


def _serve(eng, prompts, chunk, decode):
    """Serve ``prompts`` (sid -> tokens) in lockstep: prefill chunks of
    ``chunk``, then ``decode`` tokens each. Returns sid -> [(position,
    logits)] from the engine's tap."""
    seen = {sid: [] for sid in prompts}
    taps = []
    eng.logit_tap = lambda sid, lg: taps.append((sid, np.array(lg)))
    slots = {sid: eng.submit_turn(sid, p, max_new_tokens=decode + 1)
             for sid, p in prompts.items()}
    for _ in range(64):
        live = {s: sid for sid, s in slots.items()
                if eng.slot_state[s] is not None}
        if not live:
            break
        grants = {s: chunk if eng.slot_state[s].request.phase
                  == Phase.PREFILL else 1 for s in live}
        taps.clear()
        eng.run_round(grants)
        for sid, lg in taps:
            seen[sid].append((eng.sessions[sid].kv_len - 1, lg))
    return seen


def test_prefill_then_decode_matches_the_reference():
    conf = tiny_conf()
    arch, cfg, w = _model(conf)
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 331, 13), "b": rng.integers(0, 331, 7)}
    seen = _serve(_engine(cfg, w), prompts, chunk=4, decode=6)
    for sid, rows in seen.items():
        assert len(rows) >= 6
        pos = [p for p, _ in rows]
        toks = np.zeros((1, max(pos) + 1), np.int32)
        # the sequence the engine committed: prompt, then its argmaxes
        seq = list(prompts[sid]) + [int(np.argmax(lg)) for p, lg in rows
                                    if p >= len(prompts[sid]) - 1]
        toks[0] = np.asarray(seq[:toks.shape[1]], np.int32)
        x = arch.hidden(conf, w, toks)
        want = np.asarray(arch.logits(conf, w, x[0][np.asarray(pos)]))
        got = np.stack([lg for _, lg in rows])
        np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL)
        x8 = arch.hidden(conf, w, toks, lower=True)
        low = np.asarray(arch.logits(conf, w, x8[0][np.asarray(pos)],
                                     lower=True))
        assert np.abs(low - want).max() > 20 * LOGIT_TOL


def test_the_four_shares_sum_to_the_uncut_layer():
    full = tiny_conf(num_experts=16, expert_offset=0)
    arch, _, w = _model(full)
    lw = jax.tree.map(lambda a: a[1], w["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    want = arch.moe(full, x, lw, lower=False) - x
    h = L.rms_norm(x, lw["ln2"], full["rms_norm_eps"])
    valid = jnp.ones((2, 9), bool)
    parts, held = [], 0
    for off in range(0, 16, 4):
        share = tiny_conf(num_experts=4, expert_offset=off)
        cfg = arch.program_config(share)
        m = dict(lw["moe"])
        for k in ("w_gate", "w_up", "w_down"):
            m[k] = m[k][off:off + 4]
        y, counts = moe_held(m, h, cfg, valid, interpret=True)
        parts.append(np.asarray(y))
        assert int(counts[0]) == 2 * 9 * 4
        held += int(counts[1])
    assert held == 2 * 9 * 4          # every routed pair held by one share
    np.testing.assert_allclose(sum(parts), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_a_tokens_logits_do_not_depend_on_its_launch():
    """bf16, as served: "a" alone in four slots, then beside "b" and
    "c" (one padded row), every launch of the same width."""
    conf = tiny_conf(torch_dtype="bfloat16")
    _, cfg, w = _model(conf)
    rng = np.random.default_rng(1)
    pa = rng.integers(0, 331, 12)
    alone = _serve(_engine(cfg, w), {"a": pa}, chunk=4, decode=5)
    crowd = _serve(_engine(cfg, w),
                   {"b": rng.integers(0, 331, 12), "a": pa,
                    "c": rng.integers(0, 331, 12)}, chunk=4, decode=5)
    assert [p for p, _ in alone["a"]] == [p for p, _ in crowd["a"]]
    for (_, x), (_, y) in zip(alone["a"], crowd["a"]):
        assert np.array_equal(x, y)


def _hand_count(arch, conf, w, tokens):
    """(held pairs, held experts touched) of ``tokens`` [1, S] summed
    over layers, from the reference's own routing."""
    L_, eps = conf["num_hidden_layers"], conf["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[1])[None]
    x = w["embed"][tokens].astype(jnp.float32)
    pairs = touched = 0
    for l in range(L_):
        lw = jax.tree.map(lambda a: a[l], w["layers"])
        x = common.attention(conf, x, lw, pos, lower=False, qk_norm=True)
        h = common.rms(x, lw["ln2"].astype(jnp.float32), eps)
        gate = np.asarray(arch.routing(
            conf, h, lw["moe"]["router"].astype(jnp.float32), lower=False))
        pairs += int((gate > 0).sum())
        touched += int((gate > 0).any(axis=(0, 1)).sum())
        x = arch.moe(conf, x, lw, lower=False)
    return pairs, touched


@pytest.mark.parametrize("slots", [1, 4])
def test_the_routing_counters_match_a_hand_count(slots):
    """One launch of a 7-token prompt: 7 x top-4 x 2 layers routed
    pairs whatever the padded rows, and the held pairs and experts the
    reference routes."""
    conf = tiny_conf()
    arch, cfg, w = _model(conf)
    prompt = np.random.default_rng(2).integers(0, 331, 7)
    eng = _engine(cfg, w, slots=slots)
    s = eng.submit_turn("a", prompt, max_new_tokens=3)
    eng.run_round({s: 7})
    assert eng.moe_routed_pairs == 7 * 4 * 2
    pairs, touched = _hand_count(arch, conf, w,
                                 jnp.asarray(prompt[None], jnp.int32))
    assert (eng.moe_held_pairs, eng.moe_experts_touched) == (pairs,
                                                            touched)
    assert 0 < pairs < 7 * 4 * 2


def test_the_kernel_matches_a_plain_grouped_matmul():
    """Held experts 2..5 of 8, top-2, tiles of 8 rows: local expert 0
    gets no pair, 1 exactly one, 2 more than a tile; tokens 18 and 19
    are padding."""
    T, d, f, held, tm = 20, 32, 48, 4, 8
    sel = np.zeros((T, 2), np.int32)
    sel[:, 0] = 4                                   # local 2: 18 rows
    sel[:, 1] = np.where(np.arange(T) % 2, 0, 7)    # never held
    sel[3, 1] = 3                                   # local 1: one row
    sel[5, 1] = 5                                   # local 3
    sel[6, 1] = 5
    valid = np.arange(T) < 18
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (held, d, f)) / 6
    wu = jax.random.normal(ks[2], (held, d, f)) / 6
    wd = jax.random.normal(ks[3], (held, f, d)) / 7
    wt = jax.random.uniform(ks[4], (T, 2))
    p = K.plan(jnp.asarray(sel), wt, jnp.asarray(valid), offset=2,
               held=held, tm=tm)
    assert p.counts.tolist() == [36, 21, 3]
    assert p.live.tolist() == [1] * 5 + [0] * 4     # 1 + 3 + 1 tiles
    got = np.asarray(K.moe_experts(x, p, wg, wu, wd, interpret=True))
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for j in range(2):
            e = int(sel[t, j]) - 2
            if valid[t] and 0 <= e < held:
                g = x[t] @ wg[e]
                want[t] += float(wt[t, j]) * np.asarray(
                    (jax.nn.silu(g) * (x[t] @ wu[e])) @ wd[e])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(K.moe_experts_reference(x, p, wg, wu, wd)),
        rtol=1e-6, atol=1e-6)
    assert not got[18:].any()


def test_the_three_planes_serve_the_same_tokens():
    """The per-token plane (``paged_decode_step``), the fused plane and
    the speculative verify variant commit the same tokens; the first
    two route the same pairs."""
    conf = tiny_conf(torch_dtype="bfloat16")
    _, cfg, w = _model(conf)
    rng = np.random.default_rng(4)
    prompts = {"a": rng.integers(0, 331, 9), "b": rng.integers(0, 331, 5)}
    out, counts = {}, {}
    for plane, kw in (("tokenwise", dict(fused_step=False)),
                      ("fused", {}), ("spec", dict(spec_decode=2))):
        eng = PagedRealtimeEngine(cfg, w, slots=3, page_size=8,
                                  pages_per_seq=8, num_pages=64, **kw)
        for sid, p in prompts.items():
            eng.submit_turn(sid, p, max_new_tokens=6)
        eng.run_to_completion()
        out[plane] = {sid: eng.sessions[sid].token_ids
                      for sid in prompts}
        counts[plane] = (eng.moe_routed_pairs, eng.moe_held_pairs,
                         eng.moe_experts_touched)
    assert out["tokenwise"] == out["fused"] == out["spec"]
    n = sum(len(t) for t in out["fused"].values())
    assert counts["fused"][0] == n * 4 * 2
    assert counts["tokenwise"][:2] == counts["fused"][:2]


def test_the_sharded_planes_refuse_an_moe_config():
    from jax.sharding import Mesh

    from repro.distributed.paged import PagedKVLayout
    _, cfg, _ = _model(tiny_conf())
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with pytest.raises(NotImplementedError, match="MoE"):
        PagedKVLayout(cfg, mesh, 8)


@pytest.mark.parametrize("held_count, want", [(4, jnp.bfloat16),
                                              (0, jnp.float32)])
def test_the_router_dtype_follows_the_share(held_count, want):
    import dataclasses
    cfg = spec.architecture(tiny_conf()).program_config(tiny_conf())
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, held_count=held_count,
        held_offset=cfg.moe.held_offset if held_count else 0))
    p = jax.eval_shape(lambda k: moe_init(k, cfg, jnp.bfloat16),
                       jax.random.PRNGKey(0))
    assert p["router"].dtype == want
    assert p["w_gate"].dtype == jnp.bfloat16
    assert p["w_gate"].shape[0] == cfg.moe.held
