"""Resolve a benchmark cell to its files, by name.

``BENCHMARK.json`` lists the cells. A cell names a configuration, a
traffic mix and a chip count; the files that hold them are found by
name, never by code:

- ``bench/configs/<config>.json``: the model's sizes, as published, its
  ``architecture`` (the Hugging Face class name) and the engine
  settings it is served with;
- ``bench/arch/<architecture>.py``: what is particular to that
  architecture (``bench/arch/common.py`` holds the pieces that such
  files share):

  - ``program_config(conf)``: the serving program's ``ModelConfig``;
  - ``shapes(conf)``: leaf path -> (shape, std) of the weight tree in
    the program's layout, which ``weights.make`` draws from the seed;
  - ``hidden(conf, w, tokens, *, lower=False)``: the final hidden
    states [B, S, d] in float32 of token rows [B, S], and
    ``logits(conf, w, x, *, lower=False)``: the output head over
    hidden rows [..., d]. Together the plain reference, which imports
    nothing of the program; ``lower=True`` is the float8 control;
  - ``step_flops(conf, rows)``: model FLOPs of one fused step whose
    valid rows are ``(q_start, q_len)``, read by ``step.mfu``;

- ``bench/traffic/<traffic>.json``: the parameters of the one general
  session generator (``loadgen/generator.py``);
- ``bench/metrics/<metric>.py``: one reader per metric, with a
  ``read(run)`` function that returns a number or None.

A later change adds a model, an architecture, a mix or a metric by
adding files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent
ARCH_FUNCTIONS = ("program_config", "shapes", "hidden", "logits",
                  "step_flops")


class SpecError(Exception):
    """A cell, file or metric that cannot be resolved."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                        # end_to_end | per_layer
    workloads: Optional[List[str]] = None
    bound: Optional[float] = None
    layer: Optional[str] = None
    moves: Optional[str] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    arch: Optional[ModuleType] = None


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_benchmark(root: Path = REPO_ROOT) -> dict:
    return _load_json(Path(root) / "BENCHMARK.json")


def _metrics(spec: dict, kind: str) -> List[Metric]:
    out = []
    for m in spec.get(kind, []):
        out.append(Metric(name=m["name"], unit=m["unit"],
                          better=m["better"], source=m["source"],
                          kind=kind, workloads=m.get("workloads"),
                          bound=m.get("bound"), layer=m.get("layer"),
                          moves=m.get("moves")))
    return out


def resolve_cell(name: str, root: Path = REPO_ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its config and
    traffic files read, and the metrics it reports."""
    root = Path(root)
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in confs:
        raise SpecError(f"cell {name!r} names unknown config "
                        f"{w['config']!r}")
    conf = _load_json(root / confs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{w['traffic']}.json")
    e2e = [m for m in _metrics(spec, "end_to_end") if m.applies_to(name)]
    per = [m for m in _metrics(spec, "per_layer") if m.applies_to(name)]
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=conf, traffic=traffic, end_to_end=e2e,
                per_layer=per, arch=architecture(conf, root))


def _load(path: Path, prefix: str, name: str) -> ModuleType:
    modname = prefix + "".join(c if c.isalnum() else "_" for c in name)
    mspec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def architecture(conf: dict, root: Path = REPO_ROOT) -> ModuleType:
    """The module ``root/bench/arch/<architecture>.py`` named by the
    configuration's ``architecture`` key."""
    name = conf.get("architecture")
    if not isinstance(name, str) or not name.isidentifier():
        raise SpecError(f"configuration {conf.get('name')!r} names no "
                        f"architecture (got {name!r})")
    path = Path(root) / "bench" / "arch" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no file for architecture {name!r} at {path}")
    mod = _load(path, "bench_arch_", name)
    missing = [f for f in ARCH_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{path} defines no {', '.join(missing)}")
    return mod


def metric_reader(name: str, root: Path = REPO_ROOT) -> Callable:
    """The ``read(run)`` function of ``root/bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    mod = _load(path, "bench_metric_", name)
    if not hasattr(mod, "read"):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def readers(metrics: List[Metric], root: Path = REPO_ROOT) \
        -> Dict[str, Callable]:
    return {m.name: metric_reader(m.name, root) for m in metrics}
