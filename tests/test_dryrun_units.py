"""Unit tests for the dry-run machinery (no device-count forcing here —
these test the pure helpers; full lowering is exercised by
``python -m repro.launch.dryrun`` and its committed JSON artifacts)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_configs
from repro.launch import specs as SP

# NOTE: repro.launch.dryrun sets XLA_FLAGS at import; importing it in this
# process is safe only because jax is already initialized (the flag then
# has no effect on the live backend). We only use its pure helpers.
from repro.launch.dryrun import (calibration_depths,
                                 collective_bytes_from_hlo,
                                 reduced_depth_cfg)


def test_collective_parser_counts_result_bytes():
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(bf16[16,32]{1,0} %x), dimensions={1}
  %ar = (f32[8,8]{1,0}, f32[4]{0}) all-reduce(...), to_apply=%add
  %a2a = f32[2,64]{1,0} all-to-all(f32[2,64]{1,0} %y), dimensions={0}
  %cp = u32[10]{0} collective-permute(u32[10]{0} %z)
  %ags = bf16[4,4]{1,0} all-gather-start(bf16[4,2]{1,0} %w)
  %agd = bf16[4,4]{1,0} all-gather-done(bf16[4,4]{1,0} %ags)
  %dot = f32[128,128]{1,0} dot(f32[128,64]{1,0}, f32[64,128]{1,0})
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 16 * 512 * 2 + 4 * 4 * 2  # incl -start
    assert out["all-reduce"] == 8 * 8 * 4 + 4 * 4          # tuple summed
    assert out["all-to-all"] == 2 * 64 * 4
    assert out["collective-permute"] == 10 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_skip_rules_match_assignment():
    """long_500k runs ONLY for SSM / hybrid / SWA archs (8 skips)."""
    skips = [a for a in list_configs()
             if SP.cell_supported(get_config(a), "long_500k")]
    assert sorted(skips) == sorted([
        "whisper-tiny", "qwen3-4b", "nemotron-4-340b", "qwen2-1.5b",
        "deepseek-v2-236b", "phi3.5-moe-42b-a6.6b", "paligemma-3b",
        "qwen3-30b-a3b"])
    for a in ("h2o-danube-1.8b", "recurrentgemma-9b", "mamba2-1.3b"):
        assert SP.cell_supported(get_config(a), "long_500k") is None
    for a in list_configs():
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            assert SP.cell_supported(get_config(a), shape) is None


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-v2-236b",
                                  "whisper-tiny", "paligemma-3b"])
def test_input_specs_shapes(arch):
    cfg = get_config(arch)
    cell = SP.SHAPES["train_4k"]
    b = SP.batch_specs(cfg, cell)
    assert b["tokens"].shape[0] == 256
    total = b["tokens"].shape[1] + (cfg.frontend_len
                                    if cfg.frontend == "vision" else 0)
    assert total == 4096
    tokens, cache, extras = SP.prefill_specs(cfg, SP.SHAPES["prefill_32k"])
    assert tokens.shape[0] == 32
    td, cd = SP.decode_specs(cfg, SP.SHAPES["decode_32k"])
    assert td.shape == (128,)
    assert int(cd["len"].shape[0]) == 128


def test_ring_capacity_capped_at_window():
    cfg = get_config("h2o-danube-1.8b")
    _, cache = SP.decode_specs(cfg, SP.SHAPES["long_500k"])
    assert cache["kv_pos"].shape[1] == cfg.sliding_window  # 4096, not 524288
    cfgm = get_config("mamba2-1.3b")
    _, cm = SP.decode_specs(cfgm, SP.SHAPES["long_500k"])
    assert "kv_pos" not in cm                              # O(1) state


def test_model_flops_conventions():
    cfg = get_config("qwen3-4b")
    t = SP.model_flops(cfg, SP.SHAPES["train_4k"])
    p = SP.model_flops(cfg, SP.SHAPES["prefill_32k"])
    d = SP.model_flops(cfg, SP.SHAPES["decode_32k"])
    n = cfg.num_active_params()
    assert t == 6.0 * n * 256 * 4096
    assert p == 2.0 * n * 32 * 32768
    assert d == 2.0 * n * 128
    moe = get_config("deepseek-v2-236b")
    assert moe.num_active_params() < 0.2 * moe.num_params()


def test_reduced_depth_cfg_keeps_family():
    for a in list_configs():
        cfg = get_config(a)
        lo, hi = calibration_depths(cfg)
        c0 = reduced_depth_cfg(cfg, lo)
        assert c0.family == cfg.family and c0.num_layers == lo
        assert c0.d_model == cfg.d_model      # only depth changes
        if cfg.encoder:
            assert c0.encoder.num_layers == lo


def test_dryrun_artifacts_green():
    """The committed dry-run results: every cell ok or an assignment SKIP,
    and every OK cell fits the 16 GB v5e chip."""
    d = "experiments/dryrun"
    if not os.path.isdir(d):
        pytest.skip("dry-run not yet executed")
    cells = {}
    for fn in os.listdir(d):
        with open(os.path.join(d, fn)) as f:
            r = json.load(f)
        if r["mesh"] not in ("pod256", "pod512"):
            continue                           # perf-iteration tags
        cells[(r["arch"], r["shape"], r["mesh"])] = r
    assert len(cells) == 80
    for key, r in cells.items():
        assert r["status"] in ("ok", "skip"), (key, r.get("error"))
        if r["status"] == "ok":
            peak = r["memory_analysis"].get("peak_memory_in_bytes", 0)
            assert peak <= 16.5e9, (key, peak)  # fits the 16 GB v5e chip
