"""The gradient regime's host spans and the device's collective ops in a
traced run.

``trace.load`` keeps the two client-stacked backend spans and
``progtrace.load`` the client-stacked round's own; the gradient regime
opens ``backend.train_step_s`` (stat ``rows_weighted``) around its step and
``round.minibatch`` (``rows``, ``tokens``, ``h2d_bytes``) around its token
rows. ``spans`` finds the traced run's ``.xplane.pb`` again by its rounds,
as ``progtrace.program`` does, and reads those spans with their stats.

A device op event's name is its HLO instruction, ``%<name> = <type>
<opcode>(<operands>), ..., calls=%<computation>``. ``op_kind`` reads the
name, the opcode and the called computation (not the operands, which may
name a collective's result): on the v5e the FSDP step's collectives are
``all-gather``, ``all-reduce``, ``collective-permute-start``/``-done``,
fusions named ``async-collective-start``/``-done`` and fusions calling an
``all-reduce-scatter`` computation. A fusion calling an
``async_collective_fusion`` computes (a matmul, say) while a collective it
started runs beside it: compute. A ``while``, ``conditional`` or ``call``
event spans the ops of its body, which the same line lists one by one: a
container.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Optional

from chipbench import progtrace as pt
from chipbench import trace as tr

HOST_SPANS = ("backend.train_step_s", "round.minibatch")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all", "async-collective")
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?(\S+) = (.*)$", re.S)
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%([\w.-]+)")

Event = pt.Event


def op_kind(op: str) -> str:
    """``collective``, ``container`` or ``compute``."""
    m = _INSTR.match(op)
    name, rest = (m.group(1), m.group(2)) if m else (op, "")
    code = _OPCODE.search(rest)
    code = code.group(1) if code else ""
    calls = _CALLS.search(rest)
    own = [w.replace("_", "-") for w in (name, code)]
    called = calls.group(1).replace("_", "-") if calls else ""
    if (any(c in w for c in COLLECTIVES for w in own)
            or any(c in called for c in COLLECTIVES[:-1])):
        return "collective"
    return "container" if code in CONTAINERS else "compute"


def load(path: Path) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {n: [] for n in HOST_SPANS}
    out[tr.ROUND_SPAN] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         dict(ev.stats)))
    return out


def spans(ctx, reader_file: str, name: str) -> Optional[List[Event]]:
    """The host spans ``name`` of the traced run whose trace ``ctx``
    holds, or None when that trace cannot be found again."""
    if "host_spans" not in ctx:
        ctx["host_spans"] = None
        rounds = ctx["trace"].rounds()
        root = Path(reader_file).resolve().parents[2]
        found = sorted((root / pt.TRACE_DIR).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime_ns, reverse=True)
        for path in found if rounds else []:
            got = load(path)
            if sorted((s, e) for s, e, _ in got[tr.ROUND_SPAN]) == rounds:
                ctx["host_spans"] = got
                break
    got = ctx["host_spans"]
    return None if got is None else got.get(name)


def per_round(ctx, intervals) -> Optional[float]:
    return pt.per_round(ctx["trace"], intervals)
