"""Kernel autotune harness (DESIGN.md §16).

Sweeps the paged-attention tiling knobs (``kv_block`` tokens of one
block of whole pages, ``head_block`` KV heads per launch) per kernel
shape, checks every candidate against the pure-jnp oracle, gates the
pick with a roofline-style arithmetic-intensity model, and caches the
winner in a JSON store keyed by (kernel, shape, backend). The cache is
consulted at jit time by ``paged_attention``/``paged_prefill_attention``
— but ONLY after ``enable(path)`` loads it into this module's
process-global state; with autotune disabled (the default) every lookup
is a no-op and the kernels run their block rule, so serving stays
bit-exact unless explicitly opted in (the ``async_transfers=False``
pattern).
Timings taken on the CPU interpret path rank only the interpreter's
costs; a pick is the chip's only if the sweep ran on the chip.

Cache format (invalidation rules):
  {"__meta__": {"version": 1},
   "<kind>|<shape_key>|<backend>": {"kv_block": int, "head_block": int,
                                    "measured_us": float,
                                    "default_us": float,
                                    "model_us": float, "reps": int}}
A version bump discards the whole file at load; the backend component
(``jax.default_backend()``) invalidates across CPU/TPU/GPU moves; any
shape not swept simply misses and falls back to the static defaults.
"""
from __future__ import annotations

import functools
import json
import os
import statistics
import time
from typing import Dict, Optional

import jax
import numpy as np

FORMAT_VERSION = 1

# modeled machine constants (roofline-style). Only candidate *ratios*
# matter — the gate compares configs of one shape against each other —
# so TPU-ish absolutes are fine even when sweeping the CPU interpret
# path.
C_LAUNCH_US = 5.0        # fixed pallas_call dispatch cost
C_STEP_US = 0.4          # per-block overhead (DMA starts, control)
HBM_GB_S = 800.0         # KV stream bandwidth
PEAK_GFLOPS = 50_000.0   # MXU peak

_STATE: Dict[str, object] = {"path": None, "cache": {}, "hits": 0,
                             "misses": 0}


# ------------------------------------------------------------------ keys
def shape_key(**dims) -> str:
    """Canonical shape key: sorted ``k=v`` pairs."""
    return ",".join(f"{k}={dims[k]}" for k in sorted(dims))


def cache_key(kind: str, skey: str, backend: Optional[str] = None) -> str:
    return "|".join((kind, skey, backend or jax.default_backend()))


# ----------------------------------------------------------------- state
def enable(path: str) -> int:
    """Load (or start) the cache at ``path`` and turn lookups on.
    Returns the number of tuned entries loaded."""
    _STATE["path"] = path
    _STATE["cache"] = {}
    if os.path.exists(path):
        with open(path) as f:
            raw = json.load(f)
        meta = raw.pop("__meta__", {})
        if meta.get("version") == FORMAT_VERSION:
            _STATE["cache"] = raw
    return len(_STATE["cache"])


def disable() -> None:
    _STATE["path"] = None
    _STATE["cache"] = {}


def enabled() -> bool:
    return _STATE["path"] is not None


def lookup(kind: str, skey: str) -> Optional[dict]:
    """Tuned config for (kind, shape, current backend) — None unless
    ``enable()`` ran and the shape was swept."""
    if not enabled():
        return None
    ent = _STATE["cache"].get(cache_key(kind, skey))
    if ent is None:
        _STATE["misses"] += 1
    else:
        _STATE["hits"] += 1
    return ent


def stats() -> dict:
    return {"entries": len(_STATE["cache"]), "hits": _STATE["hits"],
            "misses": _STATE["misses"]}


def save(path: Optional[str] = None) -> str:
    path = path or _STATE["path"]
    assert path, "autotune.save() needs enable(path) or an explicit path"
    out = {"__meta__": {"version": FORMAT_VERSION}}
    out.update(_STATE["cache"])
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


# ------------------------------------------------------------ the model
def candidate_configs(page: int, Hkv: int, *, D: int, pps: int,
                      itemsize: int = 4) -> list:
    """The sweep space for one shape: every block of a power-of-two
    number of whole pages up to the block table, plus the whole table
    and the block rule's pick, crossed with head_block in {1, Hkv}."""
    from repro.kernels.paged_attention import kv_block_tokens
    kvs = sorted({page << k for k in range(pps.bit_length())}
                 | {page * pps, kv_block_tokens(page, Hkv * D, itemsize,
                                                pps)})
    heads = sorted({Hkv} | ({1} if Hkv > 1 else set()))
    return [{"kv_block": kb, "head_block": hb}
            for kb in kvs for hb in heads]


def modeled_cost_us(*, B: int, Hkv: int, D: int, page: int, pps: int,
                    Q: int = 1, G: int = 1, kv_block: int,
                    head_block: int) -> float:
    """Arithmetic-intensity cost of one call under a candidate tiling:
    launch dispatches + per-block overheads (every block of every row,
    as for rows at the context cap) + the KV byte stream over HBM
    bandwidth + the attention flops at peak. Shared with
    ``benchmarks/roofline_report.py``'s framing: the bytes/flops terms
    are tiling-invariant, so the model ranks tilings purely by launch
    and block overhead — exactly the knobs the sweep moves."""
    launches = Hkv // head_block
    total_steps = launches * B * -(-pps * page // kv_block)
    kv_bytes = 2 * B * pps * page * Hkv * D * 4
    flops = 4 * B * Hkv * G * Q * pps * page * D
    return (launches * C_LAUNCH_US + total_steps * C_STEP_US
            + kv_bytes / (HBM_GB_S * 1e3)
            + flops / (PEAK_GFLOPS * 1e3))


# ------------------------------------------------------------- the sweep
def _sweep_inputs(kind: str, *, B, Hq, Hkv, D, page, pps, Q, seed):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    num_pages = B * pps + 1
    k_pages = jnp.asarray(rng.standard_normal(
        (num_pages, page, Hkv, D)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal(
        (num_pages, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(B * pps)[:B * pps]
                     .reshape(B, pps), jnp.int32)
    seq = jnp.full((B,), page * pps - 3, jnp.int32)
    if kind == "paged_attention":
        q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
        return (q, k_pages, v_pages, bt, seq)
    q = jnp.asarray(rng.standard_normal((B, Q, Hq, D)), jnp.float32)
    q_lens = jnp.full((B,), Q, jnp.int32)
    q_start = seq - Q
    return (q, k_pages, v_pages, bt, q_start, q_lens)


def sweep(kind: str, *, B: int, Hq: int, Hkv: int, D: int, page: int,
          pps: int, Q: int = 1, reps: int = 3, interpret: bool = True,
          seed: int = 0, gate_ratio: float = 4.0) -> dict:
    """Sweep one shape: time every correctness-checked candidate, gate
    by the arithmetic-intensity model (a candidate the model prices
    worse than ``gate_ratio``× the default is never eligible, however
    it happens to time on this host), pick the fastest measured, and
    keep the default on a measured tie-or-worse. Stores and returns the
    winning entry."""
    from repro.kernels import ref
    from repro.kernels import paged_attention as pk
    assert kind in ("paged_attention", "paged_prefill_attention"), kind
    fns = {"paged_attention": pk.paged_attention,
           "paged_prefill_attention": pk.paged_prefill_attention}
    dims = dict(B=B, Hq=Hq, Hkv=Hkv, D=D, page=page, pps=pps)
    if kind == "paged_prefill_attention":
        dims["Q"] = Q
    args = _sweep_inputs(kind, B=B, Hq=Hq, Hkv=Hkv, D=D, page=page,
                         pps=pps, Q=Q, seed=seed)
    oracle = {"paged_attention": ref.paged_attention_ref,
              "paged_prefill_attention": ref.paged_prefill_attention_ref}
    want = np.asarray(oracle[kind](*args))
    G = Hq // Hkv

    def timed(cfg) -> Optional[float]:
        fn = jax.jit(functools.partial(
            fns[kind], interpret=interpret, **cfg))
        out = np.asarray(jax.block_until_ready(fn(*args)))   # compile
        if kind == "paged_prefill_attention":
            # padding rows are unspecified — compare valid tokens only
            out = out[:, :Q]
            ok = np.allclose(out, want[:, :Q], rtol=1e-4, atol=1e-4)
        else:
            ok = np.allclose(out, want, rtol=1e-4, atol=1e-4)
        if not ok:
            return None
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            walls.append(time.perf_counter() - t0)
        return statistics.median(walls) * 1e6

    default = {"kv_block": pk.kv_block_tokens(page, Hkv * D, 4, pps),
               "head_block": Hkv}
    default_us = timed(default)
    assert default_us is not None, "default config failed correctness"
    default_model = modeled_cost_us(B=B, Hkv=Hkv, D=D, page=page,
                                    pps=pps, Q=Q, G=G, **default)
    best, best_us, best_model = dict(default), default_us, default_model
    for cfg in candidate_configs(page, Hkv, D=D, pps=pps):
        if cfg == default:
            continue
        model_us = modeled_cost_us(B=B, Hkv=Hkv, D=D, page=page,
                                   pps=pps, Q=Q, G=G, **cfg)
        if model_us > gate_ratio * default_model:
            continue                     # roofline gate: never eligible
        us = timed(cfg)
        if us is None:
            continue                     # failed the oracle check
        if us < best_us:
            best, best_us, best_model = cfg, us, model_us
    entry = {**best, "measured_us": round(best_us, 3),
             "default_us": round(default_us, 3),
             "model_us": round(best_model, 3), "reps": reps}
    _STATE["cache"][cache_key(kind, shape_key(**dims))] = entry
    return entry
