"""A new architecture needs only new files: ``TinyUntiedForCausalLM``,
the dense block with an output head of its own (an ``unembed`` leaf,
``tie_word_embeddings: false``), is written only into a temporary root
beside the tiny cell and served through the whole harness on the CPU.
A sound run reads ``correct``; the float8 control in the program's
place does not."""
import pytest

from bench import run, spec
from bench.tests import tiny

NAME = "TinyUntiedForCausalLM"
SOURCE = '''"""The dense block of ``common`` with its own output head."""
import math

from bench.arch import common


def program_config(conf):
    return common.dense_program_config(conf, qk_norm=False)


def shapes(conf):
    leaves = common.dense_shapes(conf, qk_norm=False)
    d = conf["hidden_size"]
    leaves["unembed"] = ((d, conf["vocab_size"]), 4.0 / math.sqrt(d))
    return leaves


def _layer(conf, x, lw, pos, *, lower):
    return common.dense_layer(conf, x, lw, pos, lower=lower, qk_norm=False)


def hidden(conf, w, tokens, *, lower=False):
    return common.hidden(_layer, conf, w, tokens, lower=lower)


def _head(conf, x, final_norm, unembed, *, lower):
    return common.head(conf, x, final_norm, unembed.astype("float32"),
                       lower=lower)


def logits(conf, w, x, *, lower=False):
    return common.program(_head, conf, lower=lower)(
        x, w["final_norm"], w["unembed"])


def step_flops(conf, rows):
    return common.dense_step_flops(conf, rows)
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("untied"),
                     config={"architecture": NAME,
                             "tie_word_embeddings": False},
                     arch={NAME: SOURCE})


def test_the_untied_architecture_is_found_in_the_root(root):
    cell = spec.resolve_cell(tiny.CELL, root)
    assert cell.arch.__file__ == str(root / "bench" / "arch" / f"{NAME}.py")
    assert sorted(p.name for p in (root / "bench" / "arch").iterdir()) \
        == [f"{NAME}.py"]
    assert cell.arch.program_config(cell.config).tie_embeddings is False
    assert "unembed" in cell.arch.shapes(cell.config)


def test_the_untied_architecture_serves_correct(root):
    out = run.execute(tiny.CELL, 2**31 + 161, 4.0, False, root=root,
                      require_tpu=False)
    assert out["correct"] is True
    assert out["check"]["tokens_compared"]["value"] >= 10
    assert out["check"]["token_mismatch"]["value"] == 0


def test_the_untied_architectures_control_is_not_correct(root):
    out = run.execute(tiny.CELL, 2**31 + 162, 4.0, False, root=root,
                      require_tpu=False, control=True)
    assert out["correct"] is False
    assert out["check"]["gap_max"]["value"] > \
        out["check"]["gap_max"]["limit"]
    assert out["check"]["token_mismatch"]["value"] == 0
