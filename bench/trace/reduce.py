"""From a profiler trace to the numbers the metrics read.

``events_from_xplane`` flattens a JAX profiler trace (``*.xplane.pb``)
into plain events: ``{"plane", "line", "name", "ts", "dur"}`` in
nanoseconds on the trace's one clock. It keeps the device planes' "XLA
Ops" and "XLA Modules" lines and the host's ``bench.*`` spans, which the
benchmark writes with ``jax.profiler.TraceAnnotation`` around its calls
into each layer. ``Trace`` reduces such a list; the unit tests feed it a
small recorded one.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
Interval = Tuple[float, float]


def events_from_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:CPU")
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if host and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "ts": float(e.start_ns),
                            "dur": float(e.duration_ns)})
    return out


def describe_xplane(path: str) -> List[dict]:
    """Every plane and line of a trace with its event count and a few
    names: what to look at before trusting the filters above."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "names": sorted({e.name for e in evs})[:12]})
    return out


def save_events(events: Sequence[dict], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(list(events), f)


def load_events(path: Path) -> List[dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) \
        -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_name(text: str) -> str:
    """An op event's own name: a TPU trace names each op by its HLO
    text (``%paged_attention.5 = bf16[...] custom-call(...)``), whose
    operands name other ops."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%")


def _base(name: str) -> str:
    """An op's name without XLA's numeric suffix (``fusion.12``)."""
    return re.sub(r"[.:]\d+$", "", name)


def self_times(events: Sequence[dict]) -> List[Tuple[str, float]]:
    """(op name, duration less the ops nested inside it) per event of
    one line: a loop op encloses the ops of its body."""
    evs = sorted(events, key=lambda e: (e["ts"], -e["dur"]))
    out: List[List] = []
    stack: List[int] = []
    for e in evs:
        end = e["ts"] + e["dur"]
        while stack and out[stack[-1]][2] <= e["ts"]:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= e["dur"]
        out.append([op_name(e["name"]), e["dur"], end])
        stack.append(len(out) - 1)
    return [(n, d) for n, d, _ in out]


class Trace:
    """Reductions over one traced slice. Times are returned in seconds.

    ``window`` is the slice's host span (``bench.trace``); without one,
    the extent of the device ops."""

    def __init__(self, events: Sequence[dict]):
        self.events = list(events)
        self.devices = sorted({e["plane"] for e in self.events
                               if e["plane"].startswith("/device:")})
        self.ops = defaultdict(list)
        self.modules = defaultdict(list)
        self.spans: List[dict] = []
        for e in self.events:
            if e["plane"].startswith("/device:"):
                (self.ops if e["line"] == OPS_LINE
                 else self.modules)[e["plane"]].append(e)
            elif e["name"].startswith(SPAN_PREFIX):
                self.spans.append(e)
        win = [e for e in self.spans if e["name"] == "bench.trace"]
        if win:
            self.window: Interval = (win[0]["ts"],
                                     win[0]["ts"] + win[0]["dur"])
        else:
            allops = [e for d in self.devices for e in self.ops[d]]
            self.window = (min((e["ts"] for e in allops), default=0.0),
                           max((e["ts"] + e["dur"] for e in allops),
                               default=0.0))

    # ----------------------------------------------------------- device
    def busy(self, device: str) -> List[Interval]:
        ivs = union([(e["ts"], e["ts"] + e["dur"])
                     for e in self.ops[device]])
        return intersect(ivs, [self.window])

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds of the window with an op running, averaged over the
        devices traced."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) \
            / len(self.devices) * 1e-9

    def span_intervals(self, name: str) -> List[Interval]:
        return union([(e["ts"], e["ts"] + e["dur"]) for e in self.spans
                      if e["name"] == name])

    def busy_share_in(self, span: str) -> Optional[float]:
        """Share of the union of ``span``'s intervals (inside the window)
        in which the devices were busy, averaged over devices."""
        spans = intersect(self.span_intervals(span), [self.window])
        total = length(spans)
        if total <= 0 or not self.devices:
            return None
        busy = sum(length(intersect(self.busy(d), spans))
                   for d in self.devices) / len(self.devices)
        return busy / total

    def op_time(self, base: str) -> float:
        """Seconds of device ops named ``base`` (any numeric suffix),
        averaged over devices."""
        k = len(self.devices) or 1
        return sum(e["dur"] for d in self.devices for e in self.ops[d]
                   if _base(op_name(e["name"])) == base) * 1e-9 / k

    def modules_in(self, span: str) -> List[dict]:
        """Device program executions that start inside a host span: for
        ``bench.launch``, the fused step (its jitted partial carries no
        name of its own)."""
        ivs = self.span_intervals(span)
        return [e for d in self.devices for e in self.modules[d]
                if any(a <= e["ts"] <= b for a, b in ivs)]

    def module_time_in(self, span: str) -> float:
        k = len(self.devices) or 1
        return sum(e["dur"] for e in self.modules_in(span)) * 1e-9 / k

    # -------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> List[list]:
        """Device ops by self time (nested ops taken out of their
        loop), summed by name."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, dur in self_times(self.ops[d]):
                tot[_base(name)] += dur
        k = len(self.devices) or 1
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in best]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest stretches with no op on a device, each named by
        the innermost host span around its midpoint."""
        gaps = []
        for d in self.devices:
            prev = self.window[0]
            for a, b in self.busy(d) + [(self.window[1],
                                         self.window[1])]:
                if a > prev:
                    gaps.append((prev, a))
                prev = max(prev, b)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for a, b in gaps[:n]:
            out.append([self.host_span_at((a + b) / 2),
                        (b - a) * 1e-9])
        return out

    def host_span_at(self, t: float) -> str:
        inside = [e for e in self.spans
                  if e["name"] != "bench.trace"
                  and e["ts"] <= t <= e["ts"] + e["dur"]]
        if not inside:
            return "host: outside bench spans"
        return max(inside, key=lambda e: e["ts"])["name"]
