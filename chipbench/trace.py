"""Reduce a profiler trace (``.xplane.pb``) to intervals the metrics read.

A trace holds device planes (``/device:TPU:<n>``), whose op lines carry one
event per operation the device ran, and host planes, whose thread lines
carry the ``TraceAnnotation`` spans the program and this harness open.
Everything here works on plain ``(start_ns, end_ns)`` pairs on the
profiler's one clock, so the tests can feed it a recorded trace or made-up
intervals alike.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

ROUND_SPAN = "chipbench.round"
BACKEND_SPANS = ("backend.train_s", "backend.eval_s")
NO_SPAN = "no backend span"
# the device line that has one event per operation, as the TPU profiler
# names it; the other lines (modules, steps, scopes) repeat the same time
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    """Device op events per device, and host spans by name."""

    ops: Dict[str, List[Tuple[str, float, float]]]   # device -> (name, s, e)
    spans: Dict[str, List[Interval]]                  # span name -> [(s, e)]

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def rounds(self) -> List[Interval]:
        return sorted(self.spans.get(ROUND_SPAN, []))

    def window(self) -> Optional[Interval]:
        """From the start of the first traced round to the end of the last."""
        r = self.rounds()
        return (r[0][0], max(e for _, e in r)) if r else None

    def busy(self, device: str) -> List[Interval]:
        return union((s, e) for _, s, e in self.ops[device])


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ROUND_SPAN or ev.name in BACKEND_SPANS:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return Trace(ops={k: v for k, v in ops.items() if v}, spans=dict(spans))


def find(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(found)}")
    return found[0]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``busy`` (a union) within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# ---------------------------------------------------------------------------
# reductions the metrics share
# ---------------------------------------------------------------------------

def backend_spans(trace: Trace) -> List[Interval]:
    return union(iv for name in BACKEND_SPANS
                 for iv in trace.spans.get(name, []))


def idle_gaps(trace: Trace, device: str) -> List[Tuple[str, float]]:
    """The idle gaps of ``device`` inside the window, in time order, each
    cut where a backend span opens or closes and labelled with the span
    open over it (or NO_SPAN): (label, ns)."""
    lo, hi = trace.window()
    pieces = []
    idle = gaps(trace.busy(device), lo, hi)
    for name in BACKEND_SPANS:
        pieces += [(s, e, name) for s, e in
                   intersect(idle, union(trace.spans.get(name, [])))]
    pieces += [(s, e, NO_SPAN) for s, e in
               intersect(idle, gaps(backend_spans(trace), lo, hi))]
    return [(name, e - s) for s, e, name in sorted(pieces)]


def span_per_round(trace: Trace, name: str) -> Optional[float]:
    """Seconds per traced round spent in the host span ``name``."""
    if trace.window() is None:
        return None
    spans = clip(union(trace.spans.get(name, [])), *trace.window())
    if not spans:
        return None
    return length(spans) / 1e9 / len(trace.rounds())


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The device ops that took most time inside the window, in seconds
    summed over the devices and divided by their number."""
    lo, hi = trace.window()
    total: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        for name, s, e in trace.ops[dev]:
            if e > lo and s < hi:
                total[name] += (min(e, hi) - max(s, lo)) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, t / len(trace.devices)) for name, t in ranked]


def top_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the device that idles most, in seconds."""
    dev = max(trace.devices, key=lambda d: sum(
        t for _, t in idle_gaps(trace, d)))
    ranked = sorted(idle_gaps(trace, dev), key=lambda g: -g[1])[:n]
    return [(name, t / 1e9) for name, t in ranked]
