"""Random weights for a configuration file, made on the device from
the seed in one jitted call, in the dtype they are served in.

The tree has the layout the serving program reads (``embed``,
``final_norm`` and layer-stacked ``layers``); the benchmark makes it,
so the reference (``reference.py``) reads weights the program did not
make. Scales, per weight: matrices N(0, 1/fan_in), the tied embedding
N(0, 16/hidden) so that logits have a standard deviation near 4, norm
scales 1 + N(0, 0.01) (the program stores the offset from 1, the
reference adds the 1 back), QKV biases N(0, 0.01).
"""
from __future__ import annotations

import math

import numpy as np

NORM_SD = 0.1
BIAS_SD = 0.1


def jax_key(seed: int):
    """A PRNG key for any whole-number seed (seeds may exceed 32
    bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def shapes(conf: dict) -> dict:
    """Leaf name -> (shape, std) for the serving program's dense tree."""
    L = conf["num_hidden_layers"]
    d = conf["hidden_size"]
    hq = conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    hd = conf["head_dim"]
    ff = conf["intermediate_size"]
    V = conf["vocab_size"]
    out = {
        "embed": ((V, d), 4.0 / math.sqrt(d)),
        "final_norm": ((d,), NORM_SD),
        "layers/ln1": ((L, d), NORM_SD),
        "layers/ln2": ((L, d), NORM_SD),
        "layers/attn/wq": ((L, d, hq, hd), 1 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, hkv, hd), 1 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, hkv, hd), 1 / math.sqrt(d)),
        "layers/attn/wo": ((L, hq, hd, d), 1 / math.sqrt(hq * hd)),
        "layers/mlp/w_gate": ((L, d, ff), 1 / math.sqrt(d)),
        "layers/mlp/w_up": ((L, d, ff), 1 / math.sqrt(d)),
        "layers/mlp/w_down": ((L, ff, d), 1 / math.sqrt(ff)),
    }
    if conf["attention_bias"]:
        out["layers/attn/bq"] = ((L, hq, hd), BIAS_SD)
        out["layers/attn/bk"] = ((L, hkv, hd), BIAS_SD)
        out["layers/attn/bv"] = ((L, hkv, hd), BIAS_SD)
    if conf["qk_norm"]:
        out["layers/attn/q_norm"] = ((L, hd), NORM_SD)
        out["layers/attn/k_norm"] = ((L, hd), NORM_SD)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make(conf: dict, seed: int, device=None):
    """The weight tree on ``device`` (default: JAX's first device)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    dtype = jnp.dtype(conf["torch_dtype"])
    spec = shapes(conf)
    names = sorted(spec)

    def init(key):
        keys = jax.random.split(key, len(names))
        return _nest({n: (jax.random.normal(k, spec[n][0], jnp.float32)
                          * spec[n][1]).astype(dtype)
                      for n, k in zip(names, keys)})

    dev = device or jax.devices()[0]
    fn = jax.jit(init, out_shardings=SingleDeviceSharding(dev))
    return fn(jax_key(seed))


def same_layout(ours, program_init) -> bool:
    """Whether the tree matches what the program's own initialiser
    would make (shapes and dtypes only; nothing is computed)."""
    import jax
    a = jax.tree.map(lambda x: (x.shape, x.dtype), ours)
    b = jax.tree.map(lambda x: (x.shape, x.dtype),
                     jax.eval_shape(program_init))
    return a == b

