"""The check's control: the float8 reference put in the program's place
fails the comparison, at a size a test run holds.

For each cell, its configuration with every size cut down (the same
mechanisms: QKV bias or QK-norm, GQA) and random weights from the
benchmark's own maker. At every position of a few random sequences the
token the float32 reference puts first reads a gap of 0; the token the
float8 control puts first reads a widest gap above the cell's
``check.gap_limit``."""
import numpy as np
import pytest

from bench import reference, spec, weights

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def small(conf: dict) -> dict:
    c = dict(conf)
    c.update(num_hidden_layers=6, hidden_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, intermediate_size=768,
             vocab_size=4096)
    return c


@pytest.mark.parametrize("cell", CELLS)
def test_the_float8_control_fails_the_check(cell):
    import jax
    c = spec.resolve_cell(cell)
    conf = small(c.config)
    w = weights.make(c.arch.shapes(conf), conf["torch_dtype"], 7,
                     jax.devices()[0])
    rng = np.random.default_rng(7)
    S, n = 256, 4
    seqs = [rng.integers(0, conf["vocab_size"], S).astype(np.int32)
            for _ in range(n)]
    pos = [np.arange(S) for _ in range(n)]
    x = c.arch.hidden(conf, w, np.stack(seqs))
    best = [np.asarray(jax.numpy.argmax(c.arch.logits(conf, w, x[i]), -1))
            for i in range(n)]
    sound = reference.served_gaps(c.arch, conf, w, seqs, pos, best,
                                  pad_to=S, batch=2)
    assert max(g.max() for g in sound) == 0.0
    ctl = reference.served_gaps(c.arch, conf, w, seqs, pos, best,
                                pad_to=S, batch=2, control=True)
    widest = max(g.max() for g in ctl)
    assert widest > c.traffic["check"]["gap_limit"], widest
    # most positions still agree: the control is close, not broken
    assert np.mean(np.concatenate(ctl) == 0.0) > 0.5
