"""The reference and the FLOP count, pinned from before they moved into
architecture files: a seeded tiny Qwen2 and Qwen3 (each cell's
configuration with every size cut down) give the float32 and float8
logits at a few positions, and both published configurations the FLOPs
of a fixed set of rows, that the dense-only harness gave. The same
products in the same order give the same bits."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import spec, weights

ROOT = Path(__file__).resolve().parents[2]
PINS = json.loads((Path(__file__).resolve().parent / "data"
                   / "reference_pins.json").read_text())
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=192,
            vocab_size=331)
CONFIGS = {"qwen2": "qwen2-1.5b", "qwen3": "qwen3-4b"}


def _conf(config: str) -> dict:
    return json.loads((ROOT / f"bench/configs/{config}.json").read_text())


@pytest.mark.parametrize("model", sorted(CONFIGS))
@pytest.mark.parametrize("precision", ["float32", "float8"])
def test_reference_logits_are_the_pinned_ones(model, precision):
    import jax
    conf = _conf(CONFIGS[model])
    conf.update(TINY)
    arch = spec.architecture(conf)
    w = weights.make(arch.shapes(conf), conf["torch_dtype"], 2**31 + 16,
                     jax.devices()[0])
    toks = np.random.default_rng(16).integers(0, 331, (2, 24)) \
        .astype(np.int32)
    lower = precision == "float8"
    x = arch.hidden(conf, w, toks, lower=lower)
    rows = x[1][np.asarray(PINS["positions"])]
    got = np.asarray(arch.logits(conf, w, rows, lower=lower))
    want = np.asarray(PINS[f"{model}.{precision}"], np.float32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("config", sorted(CONFIGS.values()))
def test_step_flops_are_the_pinned_ones(config):
    conf = _conf(config)
    rows = [tuple(r) for r in PINS["rows"]]
    assert spec.architecture(conf).step_flops(conf, rows) == \
        PINS[f"{config}.step_flops"]
