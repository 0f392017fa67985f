"""Gaps of served tokens below the plain reference's best logit.

The reference itself is per architecture: ``hidden`` and ``logits`` of
``bench/arch/<architecture>.py``, a float32 model written from the
published description that imports nothing of the serving program and
reads the weights the benchmark made. This module runs it over the
sequences the server saw, in batches padded to one length so that one
program serves every batch, and reads at each served position how far
the served token's logit lies below the best. ``control`` judges the
float8 reference's first token (``lower=True``) in the served one's
place.
"""
from __future__ import annotations

import functools
from typing import List

import numpy as np


@functools.lru_cache(maxsize=None)
def _gap_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(logits, picked):
        """How far below the row's best logit the picked token lies."""
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
        return best - got

    return gaps


def served_gaps(arch, conf: dict, w, seqs: List[np.ndarray],
                positions: List[np.ndarray], tokens: List[np.ndarray], *,
                pad_to: int, batch: int, control: bool = False,
                rows: int = 512) -> List[np.ndarray]:
    """For each sequence, the gap of each served token: the reference's
    best logit at that position minus its logit of the token. ``arch``
    is the configuration's architecture module.

    With ``control`` the token at each position is the one the float8
    reference puts first instead of the served one (the served tokens
    then only fix the sequence)."""
    import jax.numpy as jnp
    gaps = _gap_program()
    out: List[np.ndarray] = []
    for b0 in range(0, len(seqs), batch):
        group = list(range(b0, min(len(seqs), b0 + batch)))
        toks = np.zeros((batch, pad_to), np.int32)    # one shape
        for r, i in enumerate(group):
            toks[r, :len(seqs[i])] = seqs[i]
        x = arch.hidden(conf, w, toks)
        x8 = arch.hidden(conf, w, toks, lower=True) if control else None
        for r, i in enumerate(group):
            pos = np.asarray(positions[i], np.int64)
            res = np.empty(len(pos), np.float64)
            for c0 in range(0, len(pos), rows):
                sel = np.zeros(rows, np.int64)
                n = min(rows, len(pos) - c0)
                sel[:n] = pos[c0:c0 + n]
                lg = arch.logits(conf, w, x[r][jnp.asarray(sel)])
                if control:
                    lg8 = arch.logits(conf, w, x8[r][jnp.asarray(sel)],
                                      lower=True)
                    pick = jnp.argmax(lg8, axis=-1).astype(jnp.int32)
                else:
                    pick = np.zeros(rows, np.int32)
                    pick[:n] = np.asarray(tokens[i])[c0:c0 + n]
                    pick = jnp.asarray(pick)
                res[c0:c0 + n] = np.asarray(gaps(lg, pick))[:n]
            out.append(res)
    return out


def batch_for(conf: dict, pad_to: int, budget_bytes: float = 2e9) -> int:
    """Sequences per reference batch, so the attention scores of one
    layer stay within ``budget_bytes``."""
    per = conf["num_attention_heads"] * pad_to * pad_to * 4 * 3
    return max(1, int(budget_bytes // per))
