"""Kernel autotune harness (DESIGN.md §16, ISSUE 10).

- cache round-trip (enable → sweep → save → reload → lookup serves the
  same entry) and the invalidation rules: a format-version bump
  discards the file, the backend key component misses across backends,
  unswept shapes miss to the static defaults;
- tiling exactness: any legal ``kv_block``/``head_block`` is
  output-identical (head_block splits bit-exactly by per-head softmax
  independence; kv_block re-tiles the flash accumulation within
  tolerance of the oracle);
- the sweep is gated by the arithmetic-intensity model and reproducibly
  selects the non-default kv_block=32 for the page=32 decode shape
  (the probe ``benchmarks/autotune_bench.py`` reports);
- ``_resolve`` consults the cache only for unset knobs and only while
  enabled — disabled serving keeps the static defaults (bit-exact
  spec_decode=0 control stays untouched).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ref
from repro.kernels.paged_attention import (_default_kv_block, _resolve,
                                           paged_attention,
                                           paged_prefill_attention)


@pytest.fixture(autouse=True)
def _clean_state():
    """Autotune state is process-global; never leak it across tests."""
    autotune.disable()
    yield
    autotune.disable()


def _decode_case(key, B=3, Hq=4, Hkv=2, D=16, page=32, pps=3):
    ks = jax.random.split(key, 4)
    num_pages = B * pps + 2
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, Hkv, D), jnp.float32)
    bt = jax.random.permutation(
        ks[3], num_pages)[:B * pps].reshape(B, pps).astype(jnp.int32)
    sl = jnp.array([page * pps - 5, 7, 1], jnp.int32)[:B]
    return q, kp, vp, bt, sl


# ======================================================================
# keys, defaults, resolve fallbacks
# ======================================================================
def test_shape_key_is_canonical():
    assert autotune.shape_key(b=1, a=2) == autotune.shape_key(a=2, b=1)
    assert autotune.shape_key(B=4, page=32) == "B=4,page=32"


def test_cache_key_carries_backend():
    k = autotune.cache_key("paged_attention", "B=1", backend="tpu")
    assert k == "paged_attention|B=1|tpu"
    assert autotune.cache_key("paged_attention", "B=1") \
        == f"paged_attention|B=1|{jax.default_backend()}"


def test_default_kv_block_heuristic():
    # whole-page tiles up to 16 slots and for non-16-divisible pages;
    # 16-slot lane sub-tiles otherwise
    assert [_default_kv_block(p) for p in (4, 8, 16, 20, 24, 32, 64)] \
        == [4, 8, 16, 20, 24, 16, 16]


def test_resolve_disabled_uses_static_defaults():
    assert not autotune.enabled()
    dims = dict(B=2, Hq=4, Hkv=2, D=16, page=32, pps=4)
    assert _resolve("paged_attention", None, None, page=32, Hkv=2,
                    dims=dims) == (16, 2)
    # explicit knobs always win
    assert _resolve("paged_attention", 32, 1, page=32, Hkv=2,
                    dims=dims) == (32, 1)
    with pytest.raises(AssertionError):
        _resolve("paged_attention", 7, None, page=32, Hkv=2, dims=dims)


def test_resolve_consults_cache_only_for_unset_knobs(tmp_path):
    autotune.enable(str(tmp_path / "cache.json"))
    dims = dict(B=2, Hq=4, Hkv=2, D=16, page=32, pps=4)
    skey = autotune.shape_key(**dims)
    autotune._STATE["cache"][autotune.cache_key("paged_attention", skey)] \
        = {"kv_block": 8, "head_block": 1}
    assert _resolve("paged_attention", None, None, page=32, Hkv=2,
                    dims=dims) == (8, 1)
    # a set knob is never overridden; the other still fills from cache
    assert _resolve("paged_attention", 32, None, page=32, Hkv=2,
                    dims=dims) == (32, 1)
    # unswept shape: miss, static defaults
    other = dict(dims, B=3)
    assert _resolve("paged_attention", None, None, page=32, Hkv=2,
                    dims=other) == (16, 2)
    s = autotune.stats()
    assert s["hits"] >= 2 and s["misses"] >= 1


# ======================================================================
# cache round-trip + invalidation
# ======================================================================
def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "cache.json")
    assert autotune.enable(path) == 0
    entry = autotune.sweep("paged_attention", B=2, Hq=2, Hkv=1, D=8,
                           page=8, pps=2, reps=1)
    skey = autotune.shape_key(B=2, Hq=2, Hkv=1, D=8, page=8, pps=2)
    assert autotune.lookup("paged_attention", skey) == entry
    assert autotune.save() == path
    autotune.disable()
    assert autotune.lookup("paged_attention", skey) is None
    assert autotune.enable(path) == 1
    got = autotune.lookup("paged_attention", skey)
    assert got == entry
    assert {"kv_block", "head_block", "measured_us", "default_us",
            "model_us", "reps"} <= set(got)


def test_version_bump_discards_cache(tmp_path):
    path = str(tmp_path / "stale.json")
    with open(path, "w") as f:
        json.dump({"__meta__": {"version": autotune.FORMAT_VERSION + 1},
                   "paged_attention|B=1|cpu": {"kv_block": 8,
                                               "head_block": 1}}, f)
    assert autotune.enable(path) == 0
    # a versionless (pre-harness) file is equally stale
    with open(path, "w") as f:
        json.dump({"paged_attention|B=1|cpu": {"kv_block": 8}}, f)
    assert autotune.enable(path) == 0


def test_backend_component_invalidates_across_backends(tmp_path):
    autotune.enable(str(tmp_path / "cache.json"))
    skey = "B=1"
    autotune._STATE["cache"][autotune.cache_key(
        "paged_attention", skey, backend="some-other-backend")] \
        = {"kv_block": 8, "head_block": 1}
    assert autotune.lookup("paged_attention", skey) is None


# ======================================================================
# tiling exactness
# ======================================================================
def test_kv_block_tilings_match_oracle():
    q, kp, vp, bt, sl = _decode_case(jax.random.PRNGKey(0))
    want = np.asarray(ref.paged_attention_ref(q, kp, vp, bt, sl))
    for kv_block in (8, 16, 32):
        got = paged_attention(q, kp, vp, bt, sl, interpret=True,
                              kv_block=kv_block)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"kv_block={kv_block}")


def test_head_block_split_is_bit_exact():
    """Each KV head's softmax never mixes with another's, so the
    head-split launch must reproduce the whole launch bit for bit."""
    q, kp, vp, bt, sl = _decode_case(jax.random.PRNGKey(1))
    whole = paged_attention(q, kp, vp, bt, sl, interpret=True,
                            kv_block=16, head_block=2)
    split = paged_attention(q, kp, vp, bt, sl, interpret=True,
                            kv_block=16, head_block=1)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(split))


def test_prefill_kernel_tilings_match_oracle():
    key = jax.random.PRNGKey(2)
    B, Q, Hq, Hkv, D, page, pps = 2, 4, 4, 2, 16, 32, 3
    ks = jax.random.split(key, 4)
    num_pages = B * pps + 2
    q = jax.random.normal(ks[0], (B, Q, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, Hkv, D), jnp.float32)
    bt = jax.random.permutation(
        ks[3], num_pages)[:B * pps].reshape(B, pps).astype(jnp.int32)
    qs = jnp.array([11, 3], jnp.int32)
    ql = jnp.array([4, 2], jnp.int32)
    want = np.asarray(ref.paged_prefill_attention_ref(
        q, kp, vp, bt, qs, ql), np.float32)
    for kv_block, head_block in ((8, 2), (32, 2), (16, 1)):
        got = paged_prefill_attention(q, kp, vp, bt, qs, ql,
                                      interpret=True, kv_block=kv_block,
                                      head_block=head_block)
        for b in range(B):          # padding rows are unspecified
            n = int(ql[b])
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[b, :n], want[b, :n],
                rtol=2e-5, atol=2e-5,
                err_msg=f"kv_block={kv_block},head_block={head_block}")


# ======================================================================
# the sweep
# ======================================================================
def test_sweep_selects_nondefault_for_page32(tmp_path):
    """Pinned-config regression for the reproducibility probe: on the
    interpret path, one grid step per whole page=32 measurably beats
    the 16-slot default tile, and the sweep must keep finding it (the
    benchmark showed a ~4x margin; acceptance-criterion shape)."""
    autotune.enable(str(tmp_path / "cache.json"))
    entry = autotune.sweep("paged_attention", B=4, Hq=4, Hkv=2, D=16,
                           page=32, pps=4, reps=5)
    assert entry["kv_block"] == 32, entry
    assert entry["measured_us"] < entry["default_us"]


def test_sweep_roofline_gate_blocks_measured_winner(tmp_path):
    """With a gate ratio below 1 every non-default candidate is modeled
    ineligible — the sweep must keep the static default no matter what
    wall-clock says."""
    autotune.enable(str(tmp_path / "cache.json"))
    entry = autotune.sweep("paged_attention", B=4, Hq=4, Hkv=2, D=16,
                           page=32, pps=4, reps=1, gate_ratio=1e-9)
    assert entry["kv_block"] == _default_kv_block(32)
    assert entry["head_block"] == 2


def test_modeled_cost_orders_step_and_launch_overheads():
    kw = dict(B=4, Hkv=2, D=16, page=32, pps=4)
    # smaller tiles -> more grid steps -> strictly costlier model
    assert autotune.modeled_cost_us(kv_block=8, head_block=2, **kw) \
        > autotune.modeled_cost_us(kv_block=16, head_block=2, **kw) \
        > autotune.modeled_cost_us(kv_block=32, head_block=2, **kw)
    # head splitting doubles launch dispatches
    assert autotune.modeled_cost_us(kv_block=32, head_block=1, **kw) \
        > autotune.modeled_cost_us(kv_block=32, head_block=2, **kw)


def test_candidate_space_covers_default_and_whole_page():
    cfgs = autotune.candidate_configs(32, 2)
    kvs = {c["kv_block"] for c in cfgs}
    assert {16, 32} <= kvs          # static default + whole page
    assert all(32 % kb == 0 for kb in kvs)
    assert {c["head_block"] for c in cfgs} == {1, 2}
    assert {c["head_block"] for c in autotune.candidate_configs(8, 1)} \
        == {1}


def test_tuned_lookup_feeds_the_kernel(tmp_path):
    """End-to-end: enable a cache holding a non-default tiling for the
    exact call shape, call the kernel with knobs unset, and the tuned
    config must be consulted (hit counter) while staying correct."""
    q, kp, vp, bt, sl = _decode_case(jax.random.PRNGKey(3))
    B, Hq, D = q.shape
    _, page, Hkv, _ = kp.shape
    dims = dict(B=B, Hq=Hq, Hkv=Hkv, D=D, page=page, pps=bt.shape[1])
    autotune.enable(str(tmp_path / "cache.json"))
    autotune._STATE["cache"][autotune.cache_key(
        "paged_attention", autotune.shape_key(**dims))] \
        = {"kv_block": 32, "head_block": 1}
    hits0 = autotune.stats()["hits"]
    got = paged_attention(q, kp, vp, bt, sl, interpret=True)
    assert autotune.stats()["hits"] > hits0
    want = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
