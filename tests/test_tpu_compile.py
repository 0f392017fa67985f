"""The served path compiles for a TPU v5e that is described, not attached.

Interpret mode runs a Pallas kernel in Python and checks none of what
Mosaic checks (tile alignment, VMEM, layouts), so a kernel can pass
every CPU test and be refused by the chip's compiler. These tests
compile the paged attention kernels at qwen2-1.5b's attention widths in
bf16, and the fused serving step at its published widths with two
layers, for a described ``v5e:2x2`` device — no chip time needed.

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


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "stats"])
@pytest.mark.parametrize("Q", [1, 16])
@pytest.mark.parametrize("page", [16, 32])
def test_paged_kernels_compile_for_v5e(one_chip, qwen, page, Q, stats):
    """Q=1 is the decode entry point, Q=16 a fused prefill chunk;
    ``stats`` is the variant the slot-sharded plane merges."""
    hd, Hkv = qwen.resolved_head_dim, qwen.num_kv_heads
    kv = _sds((PAGES + 1, page, Hkv * hd), jnp.bfloat16, one_chip)
    bt = _sds((B, PPS), jnp.int32, one_chip)
    rows = _sds((B,), jnp.int32, one_chip)
    if Q == 1:
        fn = functools.partial(paged_attention, return_stats=stats)
        args = (_sds((B, qwen.num_heads, hd), jnp.bfloat16, one_chip),
                kv, kv, bt, rows)
    else:
        fn = functools.partial(paged_prefill_attention, return_stats=stats)
        args = (_sds((B, Q, qwen.num_heads, hd), jnp.bfloat16, one_chip),
                kv, kv, bt, rows, rows)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_for_v5e(one_chip, qwen):
    """The whole fused round at published widths (two layers), with the
    kernel compiled for the chip inside it, fits one chip's HBM."""
    from repro.models import init_params
    from repro.serving.paged_engine import paged_fused_step
    cfg = qwen.replace(num_layers=2)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.random.PRNGKey(0)))
    Q, page = 16, 16
    pages = _sds((cfg.num_layers, PAGES + 1, page,
                  cfg.num_kv_heads * cfg.resolved_head_dim),
                 jnp.dtype(cfg.dtype), one_chip)
    tok = _sds((B, Q), jnp.int32, one_chip)
    rows = _sds((B,), jnp.int32, one_chip)
    step = jax.jit(functools.partial(paged_fused_step, cfg,
                                     interpret=False))
    compiled = step.lower(params, tok, tok, pages, pages,
                          _sds((B, PPS), jnp.int32, one_chip), rows, rows,
                          tok, tok).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
