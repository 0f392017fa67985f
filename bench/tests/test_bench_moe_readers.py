"""The MoE reader ``moe.expert_ms_per_launch``: on a synthetic trace
with ``moe_experts`` ops and fixed launches, against a hand
computation; pinned on a slice of the MoE cell recorded on a TPU v5
lite (two launches, 48 ``moe_experts`` calls each); and on a dense
cell's recorded chip trace, with no trace, or with no launch in the
slice, it reads nothing."""
from pathlib import Path

import pytest

from bench import run, spec
from bench.trace import reduce as R

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "qwen3-30b-a3b-ep8.voice"
DEV = "/device:TPU:0"
NAME = "moe.expert_ms_per_launch"


def _events():
    """A 10 ms slice: two launches of a 2-layer scan, each layer one
    ``moe_experts`` call (0.6 and 0.9 ms) among other ops."""
    def ev(line, name, ts, dur):
        return {"plane": DEV, "line": line, "name": name, "ts": ts,
                "dur": dur}
    out = [{"plane": "/host:CPU", "line": "python", "name": "bench.trace",
            "ts": 0.0, "dur": 1e7}]
    for i, t0 in enumerate((1e6, 5e6)):
        out.append(ev(R.MODULES_LINE, "jit_paged_fused_step(1)", t0, 3e6))
        for layer in range(2):
            t = t0 + layer * 1.4e6
            out.append(ev(R.OPS_LINE, f"%fusion.{layer} = bf16[4] "
                                      "fusion(%a)", t, 2e5))
            out.append(ev(R.OPS_LINE, f"%moe_experts.{3 + layer} = f32[16,"
                                      "2048] custom-call(%fusion.1)",
                          t + 3e5, (0.6e6, 0.9e6)[i]))
    return out


def _run(events, launches):
    cell = spec.resolve_cell(CELL, ROOT)
    r = run.BenchRun(cell=cell, seed=0, seconds=10.0, trace_on=True,
                     conf=cell.config, engine_conf=cell.config["engine"])
    r.trace = R.Trace(events)
    r.trace_span = (0.0, 1.0)
    r.launches = launches
    return r


LAUNCHES = [run.Launch(0.1, [(300 + j, 1) for j in range(12)], 16, 1),
            run.Launch(0.5, [(40, 32), (900, 1), (1200, 1)], 16, 32)]


def test_the_reader_reads_the_hand_computed_value():
    r = _run(_events(), LAUNCHES)
    dev_s = 2 * 0.6e-3 + 2 * 0.9e-3
    assert r.trace.op_time("moe_experts") == pytest.approx(dev_s)
    ms = spec.metric_reader(NAME, ROOT)(r)
    assert ms == pytest.approx(1e3 * dev_s / 2)


@pytest.mark.parametrize("trace", ["dense", "none"])
def test_the_reader_reads_nothing_without_the_kernel(trace):
    r = _run(R.load_events(DATA / "qwen2-1.5b.voice.trace.json.gz"),
             LAUNCHES)
    if trace == "none":
        r.trace = None
    assert spec.metric_reader(NAME, ROOT)(r) is None


def test_the_reader_reads_nothing_without_a_traced_launch():
    r = _run(_events(), [run.Launch(2.0, [(40, 1)], 16, 1)])
    assert r.traced_launches() == []
    assert spec.metric_reader(NAME, ROOT)(r) is None


def test_the_reader_on_the_recorded_moe_slice():
    r = _run(R.load_events(DATA / f"{CELL}.trace.json.gz"),
             [run.Launch(0.1, [(812, 1)], 16, 1),
              run.Launch(0.2, [(1377, 1)], 16, 1)])
    assert r.trace.op_time("moe_experts") == pytest.approx(0.002281257)
    assert sum(1 for e in r.trace.ops[DEV]
               if R.op_name(e["name"]).startswith("moe_experts")) == 96
    got = spec.metric_reader(NAME, ROOT)(r)
    assert got == pytest.approx(1.1406285, rel=1e-12)
