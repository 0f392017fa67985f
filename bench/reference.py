"""The plain reference: a Qwen2/Qwen3 decoder in float32, layer by
layer, written from the published description and importing nothing of
the serving program.

Per layer (Hugging Face ``Qwen2DecoderLayer`` / ``Qwen3DecoderLayer``):
RMSNorm, Q/K/V projections (with bias for Qwen2), RMSNorm over each
head's dimension of Q and K (Qwen3), rotary embedding by
``rotate_half``, causal grouped-query attention, output projection,
residual; RMSNorm, SiLU-gated MLP, residual. Then a final RMSNorm and
the tied embedding as the output head.

It reads the weight tree the benchmark made (``weights.py``): the
program's layout, where a norm scale is stored as its offset from 1.
Every product runs at ``Precision.HIGHEST``, so a TPU computes it in
float32. Sequences are padded to one length so that one program serves
every batch; causal attention keeps the padding out of every real
position.

``lower=True`` is the control: every operand of every projection,
MLP and output-head product (weights and activations) is rounded to
float8 e4m3 with a scale per output channel or per token, the step
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import List

import numpy as np

F8_MAX = 448.0


def _f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale


def _mm(x, w, lower):
    """x [..., k] @ w [k, n] in float32 (or float8 operands)."""
    import jax
    import jax.numpy as jnp
    if lower:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, offset, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + offset)


def _rope(x, pos, theta):
    """x [B, S, H, D]; Hugging Face's rotate_half form."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = pos[..., None].astype(jnp.float32) * inv            # [B, S, D/2]
    emb = jnp.concatenate([f, f], axis=-1)[:, :, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def layer(conf: dict, x, lw: dict, pos, *, lower: bool = False):
    """One decoder layer: x [B, S, d] float32 -> [B, S, d]."""
    import jax
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    B, S, d = x.shape
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    eps = conf["rms_norm_eps"]
    a = lw["attn"]
    h = _rms(x, f32(lw["ln1"]), eps)
    q = _mm(h, f32(a["wq"]).reshape(d, hq * hd), lower)
    k = _mm(h, f32(a["wk"]).reshape(d, hkv * hd), lower)
    v = _mm(h, f32(a["wv"]).reshape(d, hkv * hd), lower)
    if conf["attention_bias"]:
        q = q + f32(a["bq"]).reshape(-1)
        k = k + f32(a["bk"]).reshape(-1)
        v = v + f32(a["bv"]).reshape(-1)
    q = q.reshape(B, S, hq, hd)
    k = k.reshape(B, S, hkv, hd)
    v = v.reshape(B, S, hkv, hd)
    if conf["qk_norm"]:
        q = _rms(q, f32(a["q_norm"]), eps)
        k = _rms(k, f32(a["k_norm"]), eps)
    q = _rope(q, pos, conf["rope_theta"])
    k = _rope(k, pos, conf["rope_theta"])
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(B, S, hq * hd),
                f32(a["wo"]).reshape(hq * hd, d), lower)
    m = lw["mlp"]
    h = _rms(x, f32(lw["ln2"]), eps)
    g = _mm(h, f32(m["w_gate"]), lower)
    u = _mm(h, f32(m["w_up"]), lower)
    return x + _mm(jax.nn.silu(g) * u, f32(m["w_down"]), lower)


@functools.lru_cache(maxsize=None)
def _programs(conf_key: tuple, lower: bool):
    """Jitted (embed, layer l, head) programs for one configuration."""
    import jax
    import jax.numpy as jnp
    conf = dict(conf_key)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def one_layer(x, layers, l, pos):
        lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, l, keepdims=False), layers)
        return layer(conf, x, lw, pos, lower=lower)

    @jax.jit
    def head(x, final_norm, table):
        xn = _rms(x, final_norm.astype(jnp.float32),
                  conf["rms_norm_eps"])
        return _mm(xn, table.astype(jnp.float32).T, lower)

    return embed, one_layer, head


def _key(conf: dict) -> tuple:
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "attention_bias",
            "qk_norm", "rope_theta", "rms_norm_eps", "vocab_size")
    return tuple((k, conf[k]) for k in keep)


def hidden(conf: dict, w, tokens: np.ndarray, *, lower: bool = False):
    """Final hidden states [B, S, d] (float32) of token rows [B, S]."""
    import jax.numpy as jnp
    embed, one_layer, _ = _programs(_key(conf), lower)
    tok = jnp.asarray(tokens, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(tok.shape[1], dtype=jnp.int32),
                           tok.shape)
    x = embed(w["embed"], tok)
    for l in range(conf["num_hidden_layers"]):
        x = one_layer(x, w["layers"], jnp.int32(l), pos)
    return x


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


def served_gaps(conf: dict, w, seqs: List[np.ndarray],
                positions: List[np.ndarray], tokens: List[np.ndarray], *,
                pad_to: int, batch: int, control: bool = False,
                rows: int = 512) -> List[np.ndarray]:
    """For each sequence, the gap of each served token: the reference's
    best logit at that position minus its logit of the token.

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
        x = hidden(conf, w, toks)
        x8 = hidden(conf, w, toks, lower=True) if control else None
        _, _, head = _programs(_key(conf), False)
        _, _, head8 = _programs(_key(conf), True)
        for r, i in enumerate(group):
            pos = np.asarray(positions[i], np.int64)
            res = np.empty(len(pos), np.float64)
            for c0 in range(0, len(pos), rows):
                sel = np.zeros(rows, np.int64)
                n = min(rows, len(pos) - c0)
                sel[:n] = pos[c0:c0 + n]
                lg = head(x[r][jnp.asarray(sel)], w["final_norm"],
                          w["embed"])
                if control:
                    lg8 = head8(x8[r][jnp.asarray(sel)], w["final_norm"],
                                w["embed"])
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
