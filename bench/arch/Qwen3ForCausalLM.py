"""Qwen3 (huggingface.co/Qwen/Qwen3-4B): the dense block of ``common``
with RMSNorm over each head's Q and K, the configuration's QKV bias
(none in Qwen3-4B), and the embedding tied as the output head."""
from bench.arch import common

QK_NORM = True


def program_config(conf: dict):
    return common.dense_program_config(conf, qk_norm=QK_NORM)


def shapes(conf: dict) -> dict:
    return common.dense_shapes(conf, qk_norm=QK_NORM)


def _layer(conf, x, lw, pos, *, lower):
    return common.dense_layer(conf, x, lw, pos, lower=lower,
                              qk_norm=QK_NORM)


def hidden(conf: dict, w, tokens, *, lower: bool = False):
    return common.hidden(_layer, conf, w, tokens, lower=lower)


def logits(conf: dict, w, x, *, lower: bool = False):
    return common.program(common.tied_head, conf, lower=lower)(
        x, w["final_norm"], w["embed"])


def step_flops(conf: dict, rows) -> float:
    return common.dense_step_flops(conf, rows)
