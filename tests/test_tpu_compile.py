"""The served path compiles for a TPU v5e that is described, not attached.

Interpret mode runs a Pallas kernel in Python and checks none of what
Mosaic checks (tile alignment, VMEM, layouts), so a kernel can pass
every CPU test and be refused by the chip's compiler. These tests
compile the paged attention kernels at qwen2-1.5b's and qwen3-4b's
attention widths in bf16, at the benchmark's served shapes among
others, the ``moe_experts`` kernel at Qwen3-30B-A3B's expert widths
(16 held experts), and the fused serving step at qwen2-1.5b's and at
that MoE share's published widths with two layers, for a described
``v5e:2x2`` device — no chip time needed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention, \
    paged_prefill_attention

HBM_BYTES = 16 * 2**30            # one v5e chip
B, PAGES = 8, 4096                # gateway slots; ~2 GB pool at 1.5B
PPS = 36                          # 2 turns x (256 + 32) tokens / 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                            # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compilation cache
    off around these compiles: an entry written here could not be read
    back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def qwen():
    return get_config("qwen2-1.5b")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """(model, slots, pages_per_seq, page, Q, stats): gateway-sized
    qwen2-1.5b rows at pages of 16 and 32, then both benchmark
    configurations at their served shapes (slots 32 and 16, 2,048-token
    context in 16-token pages, decode and a 32-token chunk)."""
    for page in (16, 32):
        for Q in (1, 16):
            for stats in (False, True):
                yield pytest.param(
                    "qwen2-1.5b", B, PPS, page, Q, stats,
                    id=f"{page}-{Q}-{'stats' if stats else 'plain'}")
    for model, slots in (("qwen2-1.5b", 32), ("qwen3-4b", 16)):
        for Q in (1, 32):
            for stats in (False, True):
                yield pytest.param(
                    model, slots, 128, 16, Q, stats,
                    id=f"{model}-B{slots}-pps128-Q{Q}-"
                       f"{'stats' if stats else 'plain'}")


@pytest.mark.parametrize("model,slots,pps,page,Q,stats", _kernel_cases())
def test_paged_kernels_compile_for_v5e(one_chip, model, slots, pps, page,
                                       Q, stats):
    """Q=1 is the decode entry point, Q>1 a fused prefill chunk;
    ``stats`` is the variant the slot-sharded plane merges. qwen3-4b's
    8 KV heads make a block of 8 pages 256 KB of K and as much of V."""
    cfg = get_config(model)
    hd, Hkv = cfg.resolved_head_dim, cfg.num_kv_heads
    kv = _sds((PAGES + 1, page, Hkv * hd), jnp.bfloat16, one_chip)
    bt = _sds((slots, pps), jnp.int32, one_chip)
    rows = _sds((slots,), jnp.int32, one_chip)
    if Q == 1:
        fn = functools.partial(paged_attention, return_stats=stats)
        args = (_sds((slots, cfg.num_heads, hd), jnp.bfloat16, one_chip),
                kv, kv, bt, rows)
    else:
        fn = functools.partial(paged_prefill_attention, return_stats=stats)
        args = (_sds((slots, Q, cfg.num_heads, hd), jnp.bfloat16,
                     one_chip), kv, kv, bt, rows, rows)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [16, 512])
def test_moe_experts_compiles_for_v5e(one_chip, tokens):
    """The held experts' FFN at the served grids: 16 slots of one
    token (decode) and of a 32-token chunk, 16 of 128 experts held,
    top-8, d 2,048, expert width 768, bf16."""
    from repro.kernels.moe_experts import moe_experts, plan
    d, f, held, k = 2048, 768, 16, 8

    def layer(x, sel, w, valid, wg, wu, wd):
        p = plan(sel, w, valid, offset=0, held=held)
        return moe_experts(x, p, wg, wu, wd), p.counts

    pairs = _sds((tokens, k), jnp.int32, one_chip)
    compiled = jax.jit(layer).lower(
        _sds((tokens, d), jnp.bfloat16, one_chip), pairs,
        _sds((tokens, k), jnp.float32, one_chip),
        _sds((tokens,), jnp.bool_, one_chip),
        _sds((held, d, f), jnp.bfloat16, one_chip),
        _sds((held, d, f), jnp.bfloat16, one_chip),
        _sds((held, f, d), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _moe_share():
    """qwen3-30b-a3b as the benchmark serves it: 16 of 128 experts."""
    import dataclasses
    cfg = get_config("qwen3-30b-a3b")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, held_count=16))


def test_fused_step_compiles_for_v5e(one_chip, qwen):
    """The whole fused round at published widths (two layers), with the
    kernel compiled for the chip inside it, fits one chip's HBM."""
    _compile_fused_step(one_chip, qwen.replace(num_layers=2))


@pytest.mark.parametrize("Q", [1, 32])
def test_moe_fused_step_compiles_for_v5e(one_chip, Q):
    """The same for the MoE share (two layers), with the held-expert
    layer's kernel inside, at the benchmark's served shapes (16 slots,
    1,024 pages, 2,048-token context) in a decode and a 32-token chunk
    round."""
    _compile_fused_step(one_chip, _moe_share().replace(num_layers=2), Q=Q,
                        B=16, pps=128, num_pages=1024)


def _compile_fused_step(one_chip, cfg, Q=16, B=B, pps=PPS, num_pages=PAGES):
    from repro.models import init_params
    from repro.serving.paged_engine import paged_fused_step
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.random.PRNGKey(0)))
    page = 16
    pages = _sds((cfg.num_layers, num_pages + 1, page,
                  cfg.num_kv_heads * cfg.resolved_head_dim),
                 jnp.dtype(cfg.dtype), one_chip)
    tok = _sds((B, Q), jnp.int32, one_chip)
    rows = _sds((B,), jnp.int32, one_chip)
    step = jax.jit(functools.partial(paged_fused_step, cfg,
                                     interpret=False))
    compiled = step.lower(params, tok, tok, pages, pages,
                          _sds((B, pps), jnp.int32, one_chip), rows, rows,
                          tok, tok).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
