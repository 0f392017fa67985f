"""Operations and bytes that the served step needs, from shapes alone.

A launch is described by its valid rows: ``(q_start, q_len)`` per row
that carries tokens, where the row's ``q_len`` tokens sit at positions
``q_start .. q_start + q_len - 1`` and each attends causally over every
position up to its own. Padding rows and padding tokens count nothing:
these are the operations and bytes the algorithm needs, not what a
padded grid performs.
"""
from __future__ import annotations

from typing import Iterable, Tuple

Rows = Iterable[Tuple[int, int]]


def layer_weight_params(conf: dict) -> int:
    """Multiply-accumulate weights of one decoder layer."""
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def attended(rows: Rows) -> int:
    """Sum over valid query tokens of the positions each attends."""
    total = 0
    for start, n in rows:
        # positions start+1 .. start+n attended by the n tokens
        total += n * start + n * (n + 1) // 2
    return total


def step_flops(conf: dict, rows: Rows) -> float:
    """Model FLOPs of one fused step: projections and MLP of every
    valid token, attention over what each attends, and the output head
    for each valid row (the step computes one logit row per row)."""
    rows = list(rows)
    L = conf["num_hidden_layers"]
    tokens = sum(n for _, n in rows)
    hq, hd = conf["num_attention_heads"], conf["head_dim"]
    mm = 2.0 * L * layer_weight_params(conf) * tokens
    att = 4.0 * L * hq * hd * attended(rows)
    head = 2.0 * conf["hidden_size"] * conf["vocab_size"] * len(rows)
    return mm + att + head


def attention_cost(conf: dict, rows: Rows, itemsize: int = 2) \
        -> Tuple[float, float]:
    """(FLOPs, bytes) the paged attention kernel needs for one step,
    over every layer: Q.K and P.V for each valid token over what it
    attends; each valid row's K and V up to its last position read once
    per KV head, its queries read and its outputs written once."""
    rows = list(rows)
    L = conf["num_hidden_layers"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    flops = 4.0 * L * hq * hd * attended(rows)
    kv = sum(start + n for start, n in rows) * 2 * hkv * hd
    qo = sum(n for _, n in rows) * 2 * hq * hd
    return flops, float(L * (kv + qo) * itemsize)


def least_time(flops: float, nbytes: float, peak: dict) \
        -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
