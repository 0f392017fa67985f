"""A tiny cell for CPU runs of the whole harness: the qwen2-1.5b
configuration and voice mix with every size cut down, written into a
temporary root beside copies of the metric readers and the
architecture files. Its check limit, 0.1, lies between what a sound
tiny run reads (0.0) and what the float8 control in its place reads
(0.36)."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.voice"


def make(root: Path, *, config=None, arch=None, **traffic) -> Path:
    """The tiny cell in ``root``. ``config`` updates its configuration
    file; ``arch`` maps architecture names to the source of their
    files, written in place of copies of the benchmark's own."""
    b = root / "bench"
    (b / "configs").mkdir(parents=True, exist_ok=True)
    (b / "traffic").mkdir(parents=True, exist_ok=True)
    shutil.copytree(ROOT / "bench" / "metrics", b / "metrics",
                    dirs_exist_ok=True)
    if arch is None:
        shutil.copytree(ROOT / "bench" / "arch", b / "arch",
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    else:
        (b / "arch").mkdir(exist_ok=True)
        for name, source in arch.items():
            (b / "arch" / f"{name}.py").write_text(source)
    conf = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    conf.update(name="tiny", num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=192, vocab_size=331)
    conf.update(config or {})
    conf["engine"].update(slots=4, page_size=8, num_pages=64,
                          max_context=256, prefill_chunk=8,
                          round_token_budget=12)
    (b / "configs/tiny.json").write_text(json.dumps(conf))
    t = json.loads((ROOT / "bench/traffic/voice.json").read_text())
    t.update(users=3,
             prompt_tokens={"median": 12, "sigma": 0.6, "lo": 4, "hi": 40},
             reply_audio_s={"median": 2.0, "sigma": 0.5, "lo": 1.0,
                            "hi": 4.0},
             speech_s={"median": 0.3, "sigma": 0.3, "lo": 0.1, "hi": 0.6},
             think_s={"median": 0.3, "sigma": 0.3, "lo": 0.1, "hi": 0.6},
             min_reply_tokens=4, ramp_s=1.0, grace_s=5.0,
             trace_after_s=0.5, trace_s=1.0,
             check={"sessions": 4, "min_tokens": 10, "gap_limit": 0.1})
    t.update(traffic)
    (b / "traffic/tinyvoice.json").write_text(json.dumps(t))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tiny",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU tests"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny",
                          "traffic": "tinyvoice", "chips": 1,
                          "why": "CPU tests"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
