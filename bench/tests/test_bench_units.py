"""Percentiles, FLOP and byte counts, peaks, and how cells resolve."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import peaks, spec, stats
from bench.arch import common
from bench.loadgen.client import TurnLog, Window

ROOT = Path(__file__).resolve().parents[2]


def _run_with(turns, t_open=100.0, t_close=110.0, grace=5.0):
    from bench import run
    cell = spec.Cell("c", "x", "y", 1, {}, {})
    r = run.BenchRun(cell=cell, seed=0, seconds=t_close - t_open,
                     trace_on=False)
    r.window = Window(t_open, t_close)
    r.grace_deadline = t_close + grace
    r.client.turns = turns
    return r


def _turn(send, first, end=None):
    t = TurnLog(user=0, session_id="s", turn=0, ctx_before=0,
                prompt=None, max_new_tokens=1)
    t.t_speech_end = send if end is None else end
    t.t_send = send
    t.t_first = first
    return t


def test_percentiles_count_every_turn_and_failures_at_the_deadline():
    turns = [_turn(100.0 + i, 100.0 + i + 0.1 * (i + 1)) for i in range(8)]
    turns.append(_turn(109.0, None))            # never answered
    turns.append(_turn(109.5, 200.0))           # answered after grace
    turns.append(_turn(99.0, 99.5))             # sent before the window
    turns.append(_turn(110.0, 110.2))           # sent at the close
    r = _run_with(turns)
    ttfp = r.ttfps()
    assert len(ttfp) == 10
    assert sorted(ttfp)[-2:] == pytest.approx([5.5, 6.0])
    assert r.failed() == 2
    assert stats.percentile(ttfp, 50) == pytest.approx(
        (0.5 + 0.6) / 2)
    # linear interpolation: 5.5 + 0.1 * (6.0 - 5.5)
    assert stats.percentile(ttfp, 90) == pytest.approx(5.55)
    assert stats.percentile([], 90) is None


def test_flops_and_bytes_against_hand_counts():
    conf = {"num_hidden_layers": 2, "hidden_size": 8,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 2, "intermediate_size": 16, "vocab_size": 10}
    # q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*16
    assert common.dense_layer_params(conf) == 64 + 32 + 32 + 64 + 384
    rows = [(3, 1), (0, 2)]      # a decode row at position 3; a prefill
    # attended: decode 4; prefill tokens see 1 and 2
    assert common.attended(rows) == 4 + 1 + 2
    f = common.dense_step_flops(conf, rows)
    assert f == 2 * 2 * 576 * 3 + 4 * 2 * 4 * 2 * 7 + 2 * 8 * 10 * 2
    af, ab = common.attention_cost(conf, rows)
    assert af == 4 * 2 * 4 * 2 * 7
    # K,V up to each row's last position (4 + 2), 2 kv heads of 2;
    # q in and out out for 3 tokens, 4 heads of 2; bf16; 2 layers
    assert ab == 2 * ((4 + 2) * 2 * 2 * 2 + 3 * 2 * 4 * 2) * 2
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert common.least_time(50.0, 1.0, peak) == (0.5, "compute")
    assert common.least_time(1.0, 50.0, peak) == (5.0, "memory")


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    assert {c["name"] for c in bench["configs"]} == {
        w["config"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = spec.resolve_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["users"] > 0
        names = {m.name for m in cell.end_to_end + cell.per_layer}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m.name))
        assert "setup_s" in names
        for m in cell.per_layer:
            assert m.moves in {e.name for e in cell.end_to_end}


NEW_ARCH = """
def program_config(conf): return conf["name"]
def shapes(conf): return {}
def hidden(conf, w, tokens, *, lower=False): return None
def logits(conf, w, x, *, lower=False): return None
def step_flops(conf, rows): return 7.0
"""


def test_a_cell_defined_only_in_a_temp_dir_resolves(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "arch").mkdir()
    (tmp_path / "bench/arch/NewForCausalLM.py").write_text(NEW_ARCH)
    conf = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    conf["name"] = "newmodel"
    conf["architecture"] = "NewForCausalLM"
    (tmp_path / "bench/configs/newmodel.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/voice.json").read_text())
    mix["users"] = 3
    (tmp_path / "bench/traffic/newmix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/new.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "newmodel", "source": "s", "reduced": [],
                     "file": "bench/configs/newmodel.json", "why": "w"}],
        "workloads": [{"name": "newmodel.newmix", "config": "newmodel",
                       "traffic": "newmix", "chips": 1, "why": "w"}],
        "end_to_end": [],
        "per_layer": [{"name": "new.metric", "unit": "s",
                       "better": "lower", "source": "host_clock",
                       "layer": "l", "moves": "x"}]}))
    cell = spec.resolve_cell("newmodel.newmix", tmp_path)
    assert cell.traffic["users"] == 3
    assert cell.config["name"] == "newmodel"
    assert cell.arch.program_config(cell.config) == "newmodel"
    assert cell.arch.step_flops(cell.config, [(0, 1)]) == 7.0
    read = spec.readers(cell.per_layer, tmp_path)["new.metric"]
    assert read(None) == 42.0
    with pytest.raises(spec.SpecError):
        spec.resolve_cell("nope", tmp_path)


@pytest.mark.parametrize(
    "config", [c["name"] for c in spec.load_benchmark()["configs"]])
def test_each_config_resolves_to_its_architecture_file(config):
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config)
    conf = json.loads((ROOT / entry["file"]).read_text())
    arch = spec.architecture(conf)
    assert Path(arch.__file__) == \
        ROOT / "bench" / "arch" / f"{conf['architecture']}.py"
    for w in bench["workloads"]:
        if w["config"] == config:
            cell = spec.resolve_cell(w["name"])
            assert Path(cell.arch.__file__) == Path(arch.__file__)


def test_an_unknown_architecture_raises_naming_its_file(tmp_path):
    conf = json.loads((ROOT / "bench/configs/qwen3-4b.json").read_text())
    conf["architecture"] = "NoSuchForCausalLM"
    with pytest.raises(spec.SpecError) as e:
        spec.architecture(conf, tmp_path)
    assert str(tmp_path / "bench" / "arch" / "NoSuchForCausalLM.py") \
        in str(e.value)
    (tmp_path / "bench" / "arch").mkdir(parents=True)
    (tmp_path / "bench/arch/NoSuchForCausalLM.py").write_text(
        "def shapes(conf):\n    return {}\n")
    with pytest.raises(spec.SpecError, match="program_config"):
        spec.architecture(conf, tmp_path)


def test_run_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen2-1.5b.voice", "--seed", "3", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.voice", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
