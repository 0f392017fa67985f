"""What the architecture files under ``bench/arch/`` share: the plain
float32 pieces of a decoder, the dense Qwen block built from them, the
jitted programs that run a reference layer by layer, and the count of
operations and bytes a served step needs.

The reference pieces follow the published description (Hugging Face
``Qwen2DecoderLayer`` / ``Qwen3DecoderLayer``): RMSNorm, Q/K/V
projections (with bias where ``attention_bias``), RMSNorm over each
head's dimension of Q and K where the architecture has QK-norm, rotary
embedding by ``rotate_half``, causal grouped-query attention, output
projection, residual; RMSNorm, SiLU-gated MLP, residual. They import
nothing of the serving program and read the weight tree the benchmark
made (``weights.py``), in the program's layout, where a norm scale is
stored as its offset from 1. Every product runs at
``Precision.HIGHEST``, so a TPU computes it in float32. Sequences are
padded to one length so that one program serves every batch; causal
attention keeps the padding out of every real position.

``lower=True`` is the control: every operand of every projection, MLP
and output-head product (weights and activations) is rounded to float8
e4m3 with a scale per output channel or per token, the step below the
configurations' bfloat16.

Weight scales of the dense block (``dense_shapes``): matrices
N(0, 1/fan_in), the embedding N(0, 16/hidden) so that a tied head gives
logits with a standard deviation near 4, norm scales 1 + N(0, 0.01)
(the program stores the offset from 1, the reference adds the 1 back),
QKV biases N(0, 0.01).

The counts describe a launch by its valid rows: ``(q_start, q_len)``
per row that carries tokens, where the row's ``q_len`` tokens sit at
positions ``q_start .. q_start + q_len - 1`` and each attends causally
over every position up to its own. Padding rows and padding tokens
count nothing: these are the operations and bytes the algorithm needs,
not what a padded grid performs.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

Rows = Iterable[Tuple[int, int]]

F8_MAX = 448.0
NORM_SD = 0.1
BIAS_SD = 0.1
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------- reference
def f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale


def mm(x, w, lower):
    """x [..., k] @ w [k, n] in float32 (or float8 operands)."""
    if lower:
        x, w = f8(x, -1), f8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rms(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + offset)


def rope(x, pos, theta):
    """x [B, S, H, D]; Hugging Face's rotate_half form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = pos[..., None].astype(jnp.float32) * inv            # [B, S, D/2]
    emb = jnp.concatenate([f, f], axis=-1)[:, :, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _f32(a):
    return a.astype(jnp.float32)


def attention(conf: dict, x, lw: dict, pos, *, lower: bool,
              qk_norm: bool):
    """The attention half of a decoder layer, residual included:
    x [B, S, d] float32 -> [B, S, d]."""
    B, S, d = x.shape
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    eps = conf["rms_norm_eps"]
    a = lw["attn"]
    h = rms(x, _f32(lw["ln1"]), eps)
    q = mm(h, _f32(a["wq"]).reshape(d, hq * hd), lower)
    k = mm(h, _f32(a["wk"]).reshape(d, hkv * hd), lower)
    v = mm(h, _f32(a["wv"]).reshape(d, hkv * hd), lower)
    if conf["attention_bias"]:
        q = q + _f32(a["bq"]).reshape(-1)
        k = k + _f32(a["bk"]).reshape(-1)
        v = v + _f32(a["bv"]).reshape(-1)
    q = q.reshape(B, S, hq, hd)
    k = k.reshape(B, S, hkv, hd)
    v = v.reshape(B, S, hkv, hd)
    if qk_norm:
        q = rms(q, _f32(a["q_norm"]), eps)
        k = rms(k, _f32(a["k_norm"]), eps)
    q = rope(q, pos, conf["rope_theta"])
    k = rope(k, pos, conf["rope_theta"])
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return x + mm(o.reshape(B, S, hq * hd),
                  _f32(a["wo"]).reshape(hq * hd, d), lower)


def swiglu(conf: dict, x, lw: dict, *, lower: bool):
    """The SiLU-gated MLP half of a dense layer, residual included."""
    m = lw["mlp"]
    h = rms(x, _f32(lw["ln2"]), conf["rms_norm_eps"])
    g = mm(h, _f32(m["w_gate"]), lower)
    u = mm(h, _f32(m["w_up"]), lower)
    return x + mm(jax.nn.silu(g) * u, _f32(m["w_down"]), lower)


def dense_layer(conf: dict, x, lw: dict, pos, *, lower: bool,
                qk_norm: bool):
    """One dense Qwen decoder layer: x [B, S, d] float32 -> [B, S, d]."""
    x = attention(conf, x, lw, pos, lower=lower, qk_norm=qk_norm)
    return swiglu(conf, x, lw, lower=lower)


def head(conf: dict, x, final_norm, weight, *, lower: bool):
    """Final RMSNorm and the output head ``weight`` [d, V] (float32)."""
    xn = rms(x, _f32(final_norm), conf["rms_norm_eps"])
    return mm(xn, weight, lower)


def tied_head(conf: dict, x, final_norm, table, *, lower: bool):
    """The output head of a tied model: the embedding table [V, d]."""
    return head(conf, x, final_norm, _f32(table).T, lower=lower)


# ----------------------------------------------- programs of a reference
def _conf_key(conf: dict) -> str:
    return json.dumps(conf, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _program(fn, conf_key: str, lower: bool):
    return jax.jit(functools.partial(fn, json.loads(conf_key),
                                     lower=lower))


def program(fn, conf: dict, *, lower: bool):
    """``fn(conf, *arrays, lower=lower)`` jitted, once per function,
    configuration and precision."""
    return _program(fn, _conf_key(conf), lower)


@functools.lru_cache(maxsize=None)
def _layer_program(layer, conf_key: str, lower: bool):
    conf = json.loads(conf_key)

    @jax.jit
    def one_layer(x, layers, l, pos):
        lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, l, keepdims=False), layers)
        return layer(conf, x, lw, pos, lower=lower)

    return one_layer


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def hidden(layer, conf: dict, w, tokens, *, lower: bool):
    """Final hidden states [B, S, d] (float32) of token rows [B, S]:
    the ``embed`` table, then ``layer(conf, x, lw, pos, lower=)`` over
    each slice of the layer-stacked ``layers``, one jitted program for
    every layer."""
    one_layer = _layer_program(layer, _conf_key(conf), lower)
    tok = jnp.asarray(tokens, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(tok.shape[1], dtype=jnp.int32),
                           tok.shape)
    x = _embed(w["embed"], tok)
    for l in range(conf["num_hidden_layers"]):
        x = one_layer(x, w["layers"], jnp.int32(l), pos)
    return x


# --------------------------------------------------- the dense Qwen tree
def dense_shapes(conf: dict, *, qk_norm: bool) -> dict:
    """Leaf path -> (shape, std) of the serving program's dense tree,
    without an output head of its own."""
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
    if qk_norm:
        out["layers/attn/q_norm"] = ((L, hd), NORM_SD)
        out["layers/attn/k_norm"] = ((L, hd), NORM_SD)
    return out


def dense_program_config(conf: dict, *, qk_norm: bool):
    """The serving program's ``ModelConfig`` of a dense Qwen file. A
    file whose ``qk_norm`` says otherwise than the architecture is
    refused."""
    if conf.get("qk_norm", qk_norm) != qk_norm:
        raise ValueError(f"{conf['architecture']} has qk_norm={qk_norm}; "
                         f"the configuration says {conf['qk_norm']}")
    from repro.configs.registry import ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], mlp_kind="swiglu",
        qk_norm=qk_norm, qkv_bias=conf["attention_bias"],
        rope_theta=conf["rope_theta"], rms_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        param_dtype=conf["torch_dtype"], dtype=conf["torch_dtype"],
        source=conf["source"])


# ------------------------------------------------ operations and bytes
def attended(rows: Rows) -> int:
    """Sum over valid query tokens of the positions each attends."""
    total = 0
    for start, n in rows:
        # positions start+1 .. start+n attended by the n tokens
        total += n * start + n * (n + 1) // 2
    return total


def dense_layer_params(conf: dict) -> int:
    """Multiply-accumulate weights of one dense decoder layer."""
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def dense_step_flops(conf: dict, rows: Rows) -> float:
    """Model FLOPs of one fused step of a dense model: projections and
    MLP of every valid token, attention over what each attends, and the
    output head for each valid row (the step computes one logit row per
    row)."""
    rows = list(rows)
    L = conf["num_hidden_layers"]
    tokens = sum(n for _, n in rows)
    hq, hd = conf["num_attention_heads"], conf["head_dim"]
    mm_flops = 2.0 * L * dense_layer_params(conf) * tokens
    att = 4.0 * L * hq * hd * attended(rows)
    out = 2.0 * conf["hidden_size"] * conf["vocab_size"] * len(rows)
    return mm_flops + att + out


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
