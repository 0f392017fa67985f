"""Random weights for a configuration, made on the device from the seed
in one jitted call, in the dtype they are served in.

The tree's leaves, their shapes and scales come from the
configuration's architecture file (``shapes`` in
``bench/arch/<architecture>.py``), in the layout the serving program
reads; the benchmark makes it, so the reference reads weights the
program did not make.
"""
from __future__ import annotations

import numpy as np


def jax_key(seed: int):
    """A PRNG key for any whole-number seed (seeds may exceed 32
    bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make(leaves: dict, dtype: str, seed: int, device=None):
    """The weight tree of ``leaves`` (path -> (shape, std), an
    architecture's ``shapes``) in ``dtype`` on ``device`` (default:
    JAX's first device)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    dtype = jnp.dtype(dtype)
    names = sorted(leaves)

    def init(key):
        keys = jax.random.split(key, len(names))
        return _nest({n: (jax.random.normal(k, leaves[n][0], jnp.float32)
                          * leaves[n][1]).astype(dtype)
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

