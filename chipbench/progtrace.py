"""The program's own spans in a traced run, with their stats.

``trace.load`` keeps the device ops, the harness's round spans and the two
backend spans. Inside the round the program opens more
(``repro.obs.profile.span``), each with stats at its boundary:

- ``round.transport`` (``n_selected``, ``involved``): selection, failures
  and the PON simulation;
- ``round.minibatch`` (``n_clients``, ``h2d_bytes``): the padded clients'
  minibatch draws and their stacking onto the device;
- ``train.chunk`` (``chunk``, ``rows``): one vmapped client chunk's
  dispatch, inside ``backend.train_s``;
- ``round.aggregate``: the strategy's aggregate and the server update.

It also marks JAX's compile pipeline: ``jax.trace``, ``jax.lower``,
``jax.compile`` and ``jax.cache_read`` (a persistent-cache read), each a
zero-length marker where the interval ends whose ``dur_ns`` stat gives its
length, and ``fun`` the function. Here each becomes the interval it marks.

A traced run also records Python calls (the profiler's default Python
tracer). Where the program opens none of the spans above, the readers fall
back on the calls those spans wrap, named ``<file>:<function>``:
``loop.py:_transport_stage``; ``backends.py:run_round`` up to its
``backend.train_s``; JAX's own ``partial_eval.py:trace_to_jaxpr``,
``pxla.py:_cached_lowering_to_hlo`` and ``compiler.py:compile_or_get_cached``
(a compile or a persistent-cache read).

Readers call ``program(ctx, __file__)``: the ``.xplane.pb`` that the
harness just read, found again under the checkout's trace directory by its
rounds and loaded once per traced run.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tr

PROGRAM_SPANS = ("round.transport", "round.minibatch", "train.chunk",
                 "round.aggregate")
JAX_STAGES = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_read")
# the Python calls the fallbacks read
JAX_CALLS = {"jax.trace": "partial_eval.py:trace_to_jaxpr",
             "jax.lower": "pxla.py:_cached_lowering_to_hlo",
             "jax.compile": "compiler.py:compile_or_get_cached"}
CALLS = {"loop.py:_transport_stage", "backends.py:run_round",
         *JAX_CALLS.values()}
_PY_EVENT = re.compile(r"^\$(\S+):\d+ (\S+)$")
# where run.py writes a traced run's trace, under the checkout's root
TRACE_DIR = Path("results") / "chipbench" / "trace"

Event = Tuple[float, float, dict]          # (start_ns, end_ns, stats)


@dataclasses.dataclass
class Program:
    """Program spans and JAX's compile stages by name; Python calls."""

    spans: Dict[str, List[Event]]
    calls: Dict[str, List[tr.Interval]]
    rounds: List[tr.Interval]

    @property
    def instrumented(self) -> bool:
        """The program opens its own spans (and so marks JAX's stages)."""
        return any(self.spans.get(n) for n in PROGRAM_SPANS)

    def intervals(self, name: str) -> List[tr.Interval]:
        return [(s, e) for s, e, _ in self.spans.get(name, [])]

    def jax(self, stage: str) -> List[tr.Interval]:
        """The intervals of one compile stage: the program's markers, or
        on an uninstrumented program the Python calls of that stage (a
        compile and a cache read are then one stage, ``jax.compile``)."""
        if self.instrumented:
            return self.intervals(stage)
        return list(self.calls.get(JAX_CALLS.get(stage, ""), []))


def load(path: Path) -> Program:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: Dict[str, List[Event]] = defaultdict(list)
    calls: Dict[str, List[tr.Interval]] = defaultdict(list)
    rounds: List[tr.Interval] = []
    wanted = set(PROGRAM_SPANS)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, s = ev.name, ev.start_ns
                if name in wanted:
                    spans[name].append((s, s + ev.duration_ns,
                                        dict(ev.stats)))
                elif name in JAX_STAGES:
                    stats = dict(ev.stats)
                    spans[name].append((s - stats.get("dur_ns", 0), s,
                                        stats))
                elif name == tr.ROUND_SPAN:
                    rounds.append((s, s + ev.duration_ns))
                elif name.startswith("$"):
                    m = _PY_EVENT.match(name)
                    key = m and f"{m.group(1)}:{m.group(2)}"
                    if key in CALLS:
                        calls[key].append((s, s + ev.duration_ns))
    return Program(spans=dict(spans), calls=dict(calls),
                   rounds=sorted(rounds))


def program(ctx, reader_file: str) -> Optional[Program]:
    """The program's spans in the traced run whose trace ``ctx`` holds, or
    None when that trace has no rounds or cannot be found again."""
    if "program" in ctx:
        return ctx["program"]
    rounds = ctx["trace"].rounds()
    if not rounds:
        return None
    root = Path(reader_file).resolve().parents[2]
    found = sorted((root / TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    ctx["program"] = None
    for path in found:
        p = load(path)
        if p.rounds == rounds:
            ctx["program"] = p
            break
    return ctx["program"]


def per_round(trace: tr.Trace, intervals) -> Optional[float]:
    """Seconds per traced round covered by ``intervals`` (None if none)."""
    window = trace.window()
    if window is None:
        return None
    inside = tr.clip(tr.union(intervals), *window)
    if not inside:
        return None
    return tr.length(inside) / 1e9 / len(trace.rounds())


def before_train(trace: tr.Trace, calls: List[tr.Interval]
                 ) -> List[tr.Interval]:
    """Each call cut where the first ``backend.train_s`` inside it opens."""
    starts = sorted(s for s, _ in trace.spans.get("backend.train_s", []))
    out = []
    for s, e in calls:
        first = next((t for t in starts if s <= t <= e), None)
        if first is not None:
            out.append((s, first))
    return out
