"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + prefill/decode on CPU; asserts shapes and no NaNs.

Also checks prefill+decode consistency against teacher-forcing forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs, reduced
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, prefill)

ARCHS = [
    "whisper-tiny", "h2o-danube-1.8b", "qwen3-4b", "nemotron-4-340b",
    "qwen2-1.5b", "recurrentgemma-9b", "mamba2-1.3b", "deepseek-v2-236b",
    "phi3.5-moe-42b-a6.6b", "paligemma-3b", "qwen3-30b-a3b",
]

B, S = 2, 24


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens,
             "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.frontend == "vision":
        batch["patches"] = jax.random.normal(
            ks[1], (B, cfg.frontend_len, cfg.d_model), jnp.float32)
        batch["prefix_len"] = jnp.full((B,), cfg.frontend_len, jnp.int32)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.encoder.num_frames, cfg.d_model), jnp.float32)
    return batch


def test_all_archs_registered():
    assert sorted(ARCHS) == list_configs()


@pytest.mark.parametrize("arch", ARCHS)
def test_config_param_count_positive(arch):
    cfg = get_config(arch)
    n = cfg.num_params()
    na = cfg.num_active_params()
    assert n > 0 and 0 < na <= n
    # sanity: the headline sizes are roughly right (within 2x)
    expected = {"nemotron-4-340b": 340e9, "deepseek-v2-236b": 236e9,
                "phi3.5-moe-42b-a6.6b": 42e9, "qwen3-4b": 4e9,
                "qwen2-1.5b": 1.5e9, "h2o-danube-1.8b": 1.8e9,
                "mamba2-1.3b": 1.3e9, "recurrentgemma-9b": 9e9,
                "paligemma-3b": 2.6e9, "qwen3-30b-a3b": 30.5e9}
    if arch in expected:
        assert 0.5 < n / expected[arch] < 2.0, (arch, n)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = forward(cfg, params, batch["tokens"],
                          frontend_embeds=batch.get("patches"),
                          enc_frames=batch.get("frames"),
                          prefix_len=batch.get("prefix_len"))
    S_total = S + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    assert logits.shape == (B, S_total, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    loss, metrics = loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss))
    assert float(metrics["ce"]) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    g = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
    leaves = jax.tree.leaves(g)
    assert leaves
    for leaf in leaves:
        assert np.isfinite(np.asarray(leaf, dtype=np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    """Greedy continuation from prefill must match teacher-forcing logits."""
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    tokens = batch["tokens"]
    enc_frames = batch.get("frames")
    fe = batch.get("patches")
    capacity = S + 8
    cache = init_cache(cfg, B, capacity,
                       enc_frames=cfg.encoder.num_frames
                       if cfg.family == "encdec" else 0)
    # prefill on the first S-1 tokens, then decode token S-1
    last, cache = prefill(cfg, params, tokens[:, :S - 1], cache,
                          frontend_embeds=fe,
                          prefix_len=batch.get("prefix_len"),
                          enc_frames=enc_frames)
    dec_logits, cache = decode_step(cfg, params, tokens[:, S - 1], cache)
    full, _ = forward(cfg, params, tokens, frontend_embeds=fe,
                      enc_frames=enc_frames,
                      prefix_len=batch.get("prefix_len"))
    # last prefill logits == forward at index S-2; decode == forward at S-1
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(full[:, -2]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)
    expect = S + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    assert int(cache["len"][0]) == expect


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "recurrentgemma-9b"])
def test_ring_buffer_windowed_decode(arch):
    """Decode past the window: ring wraps, mask stays exact."""
    cfg = reduced(get_config(arch))
    win = (cfg.sliding_window if cfg.sliding_window
           else cfg.rglru.local_window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    T = win + 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                                cfg.vocab_size)
    cache = init_cache(cfg, B, T)
    assert cache["kv_pos"].shape[1] == win  # ring capped at window
    last, cache = prefill(cfg, params, tokens[:, :4], cache)
    outs = []
    for t in range(4, T):
        lg, cache = decode_step(cfg, params, tokens[:, t], cache)
        outs.append(lg)
    full, _ = forward(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(outs[-1]),
                               np.asarray(full[:, -1]), rtol=5e-4, atol=5e-4)
