"""Kernel autotune sweep (DESIGN.md §16): tune the paged-attention
tiling knobs per shape, round-trip the JSON cache, and report what was
picked. Interpret-mode timings on CPU rank *relative* candidate cost
(block count dominates there as launch overhead does on TPU); the
roofline gate keeps a noisy timing from promoting a config the
arithmetic-intensity model prices absurdly.

The page=32 decode shape with a 16-page table is the reproducibility
probe: the block rule (``kv_block_tokens``) caps a block at 128 tokens,
four blocks a row there, while fewer, larger blocks measurably win in
interpret mode — so a correct sweep reproducibly
selects a non-default block larger than the rule's (pinned by
tests/test_autotune.py).
"""
from __future__ import annotations

import os

from benchmarks.common import fmt, row

CACHE = "experiments/autotune_cache.json"

# (kind, dims) swept per run; quick keeps the two decode shapes
SHAPES = [
    ("paged_attention",
     dict(B=4, Hq=4, Hkv=2, D=16, page=16, pps=4)),
    ("paged_attention",
     dict(B=4, Hq=4, Hkv=2, D=16, page=32, pps=16)),  # non-default probe
    ("paged_prefill_attention",
     dict(B=4, Hq=4, Hkv=2, D=16, page=16, pps=4, Q=4)),
]


def run(quick=False):
    from repro.kernels import autotune

    out = []
    shapes = SHAPES[:2] if quick else SHAPES
    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    autotune.enable(CACHE)
    try:
        for kind, dims in shapes:
            entry = autotune.sweep(kind, reps=2 if quick else 3, **dims)
            skey = autotune.shape_key(**dims)
            out.append(row(
                f"autotune/{kind}/page{dims['page']}",
                entry["measured_us"],
                f"kv_block={entry['kv_block']}"
                f";head_block={entry['head_block']}"
                f";default_us={fmt(entry['default_us'], 1)}"
                f";speedup={fmt(entry['default_us'] / entry['measured_us'])}"
                f";model_us={fmt(entry['model_us'], 1)}"))
            # the cache must actually serve the entry it just stored
            assert autotune.lookup(kind, skey) == entry
        path = autotune.save()
        n = autotune.enable(path)                    # round-trip reload
        out.append(row("autotune/cache", 0.0,
                       f"entries={n};path={path}"))
        # the cache persists across runs by design (>= this sweep);
        # every shape swept just now must be served back verbatim
        assert n >= len(shapes), (n, len(shapes))
        for kind, dims in shapes:
            assert autotune.lookup(kind, autotune.shape_key(**dims)) \
                is not None, (kind, dims)
    finally:
        autotune.disable()
    return out
