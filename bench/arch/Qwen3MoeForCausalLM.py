"""Qwen3-MoE (huggingface.co/Qwen/Qwen3-30B-A3B) as one chip's share of
an expert-parallel deployment: the Qwen3 attention half of ``common``
(QK-norm, GQA), then Hugging Face's ``Qwen3MoeSparseMoeBlock`` on the
experts this chip holds, and an output head of its own (``unembed``).

The configuration's ``num_experts`` is how many experts a layer holds
here, from ``expert_offset``; ``router_width`` is the router's published
width. Per layer, for each token x:

- h = RMSNorm(x); r = h W_router [d, router_width]; p = softmax(r);
- S = top-``num_experts_per_tok``(p), w_i = p_i / sum over S
  (``norm_topk_prob``; a file that sets it false is refused);
- y = sum over i in S among the held experts of
  w_i W_down,i(silu(W_gate,i h) * W_up,i h); x + y goes on.

What the experts held elsewhere would add is left out here as in the
program. The reference computes every held expert on every token and
weights each by its routing weight (0 where not chosen). Every layer is
sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``); a file that
says otherwise is refused.

Weight scales: the dense block's (``common.dense_shapes``); router and
W_gate, W_up N(0, 1/hidden), W_down N(0, 1/moe_intermediate_size), the
output head N(0, 16/hidden) as the dense files' tied head.

``step_flops`` counts the expected held pairs under uniform,
independent routing: each token's top-k lands on
``num_experts_per_tok`` of the ``router_width`` experts, each equally
likely, so a token routes k x held / router_width pairs to held experts
on average (0.94-1.06 of it in the served cell on a TPU v5e). It does
not count the experts touched: random weights route a sequence's
tokens alike, so a launch touches about half the held experts that
uniform routing would.
``expert_cost`` therefore takes the counts the engine measures
(``moe_held_pairs``, ``moe_experts_touched``).
"""
import math

import jax
import jax.numpy as jnp

from bench import spec
from bench.arch import common

QK_NORM = True


def _check_program() -> None:
    """The parent of the held-expert layer cannot serve this file's
    configurations: say so at once, not after the weights are made."""
    from repro.configs.registry import MoEConfig
    if "held_count" not in getattr(MoEConfig, "__dataclass_fields__", {}):
        raise spec.SpecError(
            "the serving program has no held-expert MoE layer "
            "(MoEConfig.held_count): it cannot serve Qwen3MoeForCausalLM")


_check_program()


def _f32(a):
    return a.astype(jnp.float32)


def program_config(conf: dict):
    if conf["decoder_sparse_step"] != 1 or conf["mlp_only_layers"]:
        raise ValueError("Qwen3MoeForCausalLM here has every layer sparse")
    if conf.get("qk_norm", QK_NORM) != QK_NORM:
        raise ValueError("Qwen3MoeForCausalLM has QK-norm")
    if not conf["norm_topk_prob"]:
        raise ValueError("the serving program renormalises the top-k "
                         "weights (norm_topk_prob true)")
    from repro.configs.registry import MoEConfig
    cfg = common.dense_program_config(conf, qk_norm=QK_NORM)
    return cfg.replace(family="moe", moe=MoEConfig(
        num_experts=conf["router_width"],
        top_k=conf["num_experts_per_tok"],
        d_ff_expert=conf["moe_intermediate_size"],
        held_offset=conf["expert_offset"],
        held_count=conf["num_experts"]))


def shapes(conf: dict) -> dict:
    leaves = common.dense_shapes(conf, qk_norm=QK_NORM)
    for k in ("w_gate", "w_up", "w_down"):
        del leaves[f"layers/mlp/{k}"]
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    E, f = conf["num_experts"], conf["moe_intermediate_size"]
    leaves.update({
        "layers/moe/router": ((L, d, conf["router_width"]),
                              1 / math.sqrt(d)),
        "layers/moe/w_gate": ((L, E, d, f), 1 / math.sqrt(d)),
        "layers/moe/w_up": ((L, E, d, f), 1 / math.sqrt(d)),
        "layers/moe/w_down": ((L, E, f, d), 1 / math.sqrt(f)),
        "unembed": ((d, conf["vocab_size"]), 4.0 / math.sqrt(d)),
    })
    return leaves


# ---------------------------------------------------------- reference
def _mm_experts(x, w, lower):
    """x [..., k] or [..., E, k] @ w [E, k, n] -> [..., E, n] in
    float32 (or float8 operands, one scale per token and per expert's
    output channel)."""
    if lower:
        x, w = common.f8(x, -1), common.f8(w, -2)
    eq = "...ek,ekn->...en" if x.ndim == w.ndim + 1 else "...k,ekn->...en"
    return jnp.einsum(eq, x, w, precision=common.HIGHEST)


def routing(conf: dict, h, router, *, lower: bool):
    """Each held expert's routing weight [..., held] for normed tokens
    h [..., d]: 0 where the expert is not among the token's top-k."""
    p = jax.nn.softmax(common.mm(h, router, lower), axis=-1)
    top, sel = jax.lax.top_k(p, conf["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = conf["expert_offset"] + jnp.arange(conf["num_experts"])
    return jnp.sum(jnp.where(sel[..., None] == held, top[..., None], 0.0),
                   axis=-2)


def moe(conf: dict, x, lw: dict, *, lower: bool):
    """The held experts' half of a layer, residual included."""
    m = lw["moe"]
    h = common.rms(x, _f32(lw["ln2"]), conf["rms_norm_eps"])
    gate = routing(conf, h, _f32(m["router"]), lower=lower)
    g = _mm_experts(h, _f32(m["w_gate"]), lower)
    u = _mm_experts(h, _f32(m["w_up"]), lower)
    y = _mm_experts(jax.nn.silu(g) * u, _f32(m["w_down"]), lower)
    return x + jnp.einsum("...e,...ed->...d", gate, y,
                          precision=common.HIGHEST)


def _layer(conf, x, lw, pos, *, lower):
    x = common.attention(conf, x, lw, pos, lower=lower, qk_norm=QK_NORM)
    return moe(conf, x, lw, lower=lower)


def hidden(conf: dict, w, tokens, *, lower: bool = False):
    return common.hidden(_layer, conf, w, tokens, lower=lower)


def _head(conf, x, final_norm, unembed, *, lower):
    return common.head(conf, x, final_norm, _f32(unembed), lower=lower)


def logits(conf: dict, w, x, *, lower: bool = False):
    return common.program(_head, conf, lower=lower)(
        x, w["final_norm"], w["unembed"])


# ------------------------------------------------ operations and bytes
def held_pairs(conf: dict, tokens: int) -> float:
    """Expected (token, held expert) pairs of ``tokens`` tokens in one
    layer, under uniform routing."""
    return tokens * conf["num_experts_per_tok"] * conf["num_experts"] \
        / conf["router_width"]


def step_flops(conf: dict, rows) -> float:
    """Model FLOPs of the chip's share of one fused step: attention
    projections, router and the expected held experts' FFNs of every
    valid token, attention over what each attends, and the output head
    for each valid row."""
    rows = list(rows)
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, f = conf["head_dim"], conf["moe_intermediate_size"]
    tokens = sum(n for _, n in rows)
    proj = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    router = d * conf["router_width"]
    experts = 3 * d * f * held_pairs(conf, tokens)
    mm_flops = 2.0 * L * ((proj + router) * tokens + experts)
    att = 4.0 * L * hq * hd * common.attended(rows)
    out = 2.0 * d * conf["vocab_size"] * len(rows)
    return mm_flops + att + out


def expert_cost(conf: dict, pairs: int, touched: int, itemsize: int = 2):
    """(FLOPs, bytes) the ``moe_experts`` kernel needs where ``pairs``
    (token, held expert) pairs ran and ``touched`` held experts were
    touched, each summed over layers as the engine counts them: the
    pairs' FFNs, each touched expert's weights read once, and each
    pair's row read in and written out."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    flops = 2.0 * 3 * d * f * pairs
    nbytes = (touched * 3 * d * f + pairs * 2 * d) * itemsize
    return flops, float(nbytes)
