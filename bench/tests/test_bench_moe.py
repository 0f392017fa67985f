"""The Qwen3-MoE share (``bench/arch/Qwen3MoeForCausalLM.py``) in the
harness: its configuration resolves to its architecture file at the
published widths with 16 of 128 experts held; a tiny MoE cell, written
into a temporary root, serves ``correct`` through the whole harness on
the CPU while the float8 control does not; its FLOP and byte counts
match hand counts; a file whose top-k weights are not renormalised is
refused; and a parent without the held-expert layer is told so when
the file loads."""
import json
import math
from pathlib import Path

import pytest

from bench import run, spec
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
NAME = "Qwen3MoeForCausalLM"
CELL = "qwen3-30b-a3b-ep8.voice"
TINY_MOE = dict(architecture=NAME, tie_word_embeddings=False, qk_norm=True,
                decoder_sparse_step=1, mlp_only_layers=[],
                moe_intermediate_size=32, num_experts=4, router_width=16,
                num_experts_published=16, expert_offset=4,
                num_experts_per_tok=4, norm_topk_prob=True)


def _conf():
    return json.loads(
        (ROOT / "bench/configs/qwen3-30b-a3b-ep8.json").read_text())


def test_the_share_resolves_at_published_widths():
    cell = spec.resolve_cell(CELL)
    assert Path(cell.arch.__file__) == ROOT / "bench" / "arch" / f"{NAME}.py"
    conf = cell.config
    assert conf["reduced"] == ["num_experts"]
    cfg = cell.arch.program_config(conf)
    assert (cfg.family, cfg.num_layers, cfg.d_model, cfg.num_heads,
            cfg.num_kv_heads, cfg.resolved_head_dim, cfg.vocab_size) == \
        ("moe", 48, 2048, 32, 4, 128, 151936)
    assert cfg.qk_norm and not cfg.tie_embeddings
    m = cfg.moe
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.held, m.held_offset,
            m.num_shared_experts) == (128, 8, 768, 16, 0, 0)
    leaves = cell.arch.shapes(conf)
    assert leaves["layers/moe/w_gate"][0] == (48, 16, 2048, 768)
    assert leaves["layers/moe/w_up"][0] == (48, 16, 2048, 768)
    assert leaves["layers/moe/w_down"][0] == (48, 16, 768, 2048)
    assert leaves["layers/moe/router"][0] == (48, 2048, 128)
    assert leaves["unembed"][0] == (2048, 151936)
    assert not any(k.startswith("layers/mlp/") for k in leaves)


def test_unrenormalised_top_k_weights_are_refused():
    conf = dict(_conf(), norm_topk_prob=False)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        spec.architecture(conf).program_config(conf)


def test_a_parent_without_held_experts_is_refused_at_once(monkeypatch):
    import dataclasses

    from repro.configs import registry

    @dataclasses.dataclass(frozen=True)
    class OldMoEConfig:
        num_experts: int
        top_k: int

    monkeypatch.setattr(registry, "MoEConfig", OldMoEConfig)
    with pytest.raises(spec.SpecError, match="held-expert"):
        spec.architecture(_conf())


ROWS = [(0, 32), (100, 1), (2047, 1), (500, 14)]


def test_step_flops_and_expert_cost_are_the_hand_counts():
    conf = _conf()
    arch = spec.architecture(conf)
    T = 32 + 1 + 1 + 14
    attended = (32 * 33 // 2) + 101 + 2048 + (14 * 500 + 14 * 15 // 2)
    pairs = T * 8 * 16 / 128                         # uniform routing
    proj = 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 32 * 128 * 2048
    want = 2.0 * 48 * ((proj + 2048 * 128) * T + 3 * 2048 * 768 * pairs) \
        + 4.0 * 48 * 32 * 128 * attended + 2.0 * 2048 * 151936 * 4
    assert arch.step_flops(conf, ROWS) == pytest.approx(want, rel=1e-12)
    # 200 pairs on 30 touched experts, summed over layers
    flops, nbytes = arch.expert_cost(conf, 200, 30)
    assert flops == pytest.approx(2.0 * 3 * 2048 * 768 * 200, rel=1e-12)
    assert nbytes == pytest.approx(
        (30 * 3 * 2048 * 768 + 200 * 2 * 2048) * 2, rel=1e-12)
    # one decode token routes 8 x 16/128 = 1 pair to a held expert on
    # average
    assert math.isclose(arch.held_pairs(conf, 1), 1.0)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    source = (ROOT / "bench" / "arch" / f"{NAME}.py").read_text()
    return tiny.make(tmp_path_factory.mktemp("moe"), config=TINY_MOE,
                     arch={NAME: source})


def test_the_tiny_moe_cell_serves_correct(root):
    out = run.execute(tiny.CELL, 2**31 + 171, 4.0, False, root=root,
                      require_tpu=False)
    assert out["correct"] is True, out["check"]
    assert out["check"]["tokens_compared"]["value"] >= 10
    assert out["check"]["token_mismatch"]["value"] == 0


def test_the_tiny_moe_cells_control_is_not_correct(root):
    out = run.execute(tiny.CELL, 2**31 + 172, 4.0, False, root=root,
                      require_tpu=False, control=True)
    assert out["correct"] is False
    assert out["check"]["gap_max"]["value"] > \
        out["check"]["gap_max"]["limit"]
    assert out["check"]["token_mismatch"]["value"] == 0
