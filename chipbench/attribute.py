"""Say what the host does while the device idles, from one traced run.

    python3 chipbench/attribute.py <trace directory or .xplane.pb>

Prints one JSON object, every time in seconds per traced round:

- ``spans``: each backend and program span (``progtrace``);
- ``h2d_mib``: the minibatch bytes ``round.minibatch`` sends to the device;
- ``load``: the work each round's spans say they did: clients selected
  and involved (``round.transport``), padded clients (``round.minibatch``),
  chunks and rows per chunk (``train.chunk``);
- ``jax``: each compile stage's count and seconds, and the functions;
- ``idle``: the device's idle time (the device that idles most) inside
  ``backend.train_s``, inside ``backend.eval_s`` and outside both, each
  split by the innermost program span or compile stage open over it
  ("driver" where none is);
- ``chunk_idle_s``: the device's idle time inside each ``train.chunk``,
  by the chunk's index in its round;
- ``idle_gaps``: the longest idle gaps, cut where a span opens or closes
  and labelled ``<backend span>/<program span>/.../<innermost>``, as
  ``breakdown.idle_gaps`` labels them by backend span alone.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import progtrace as pt  # noqa: E402
from chipbench import trace as tr  # noqa: E402

DRIVER = "driver"
Piece = Tuple[float, float, str, Tuple[str, ...]]


def _inner(p: pt.Program) -> List[Tuple[float, float, str]]:
    out = [(s, e, n) for n in pt.PROGRAM_SPANS for s, e in p.intervals(n)]
    out += [(s, e, n) for n in pt.JAX_STAGES for s, e in p.jax(n)]
    return sorted(out, key=lambda x: (x[0], -x[1]))     # outer first


def idle_pieces(t: tr.Trace, p: pt.Program, device: str) -> List[Piece]:
    """The device's idle time in the window, cut at every span boundary:
    (start, end, backend span or NO_SPAN, program spans open, outermost
    first)."""
    lo, hi = t.window()
    backend = [(s, e, n) for n in tr.BACKEND_SPANS
               for s, e in t.spans.get(n, [])]
    inner = _inner(p)
    cuts = sorted({x for s, e, _ in backend + inner for x in (s, e)})
    out: List[Piece] = []
    for s, e in tr.gaps(t.busy(device), lo, hi):
        points = ([s] + cuts[bisect.bisect_right(cuts, s):
                             bisect.bisect_left(cuts, e)] + [e])
        for a, b in zip(points, points[1:]):
            mid = (a + b) / 2
            outer = next((n for s0, e0, n in backend if s0 <= mid < e0),
                         tr.NO_SPAN)
            chain: List[str] = []
            for s0, e0, n in inner:
                if s0 <= mid < e0 and (not chain or chain[-1] != n):
                    chain.append(n)
            out.append((a, b, outer, tuple(chain)))
    return out


def label(piece: Piece) -> str:
    return "/".join((piece[2],) + piece[3])


def _mean(events, key: str) -> Optional[float]:
    vals = [st[key] for *_, st in events if key in st]
    return sum(vals) / len(vals) if vals else None


def attribute(t: tr.Trace, p: pt.Program, top: int = 10) -> dict:
    window = t.window()
    if window is None or not t.devices:
        raise SystemExit("attribute: the trace has no rounds or no device "
                         f"op line ({tr.OPS_LINE!r})")
    lo, hi = window
    n = len(t.rounds())
    inside = lambda ivs: tr.clip(tr.union(ivs), lo, hi)
    spans = {name: tr.length(inside(t.spans.get(name, []))) / 1e9 / n
             for name in tr.BACKEND_SPANS}
    spans.update({name: tr.length(inside(p.intervals(name))) / 1e9 / n
                  for name in pt.PROGRAM_SPANS})
    events = {name: [ev for ev in p.spans.get(name, []) if lo <= ev[0] <= hi]
              for name in pt.PROGRAM_SPANS}
    h2d = sum(st.get("h2d_bytes", 0) for *_, st in events["round.minibatch"])
    chunks = events["train.chunk"]
    load = {"n_selected": _mean(events["round.transport"], "n_selected"),
            "involved": _mean(events["round.transport"], "involved"),
            "n_clients": _mean(events["round.minibatch"], "n_clients"),
            "chunks": len(chunks) / n, "rows": _mean(chunks, "rows")}
    jax: Dict[str, dict] = {}
    for stage in pt.JAX_STAGES:
        events = [(s, e, st) for s, e, st in p.spans.get(stage, [])
                  if lo <= e <= hi]
        funs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for s, e, st in events:
            f = funs[st.get("fun", "")]
            f[0] += 1 / n
            f[1] += (e - s) / 1e9 / n
        jax[stage] = {"count": len(events) / n,
                      "s": tr.length(inside(p.jax(stage))) / 1e9 / n,
                      "funs": dict(sorted(funs.items(),
                                          key=lambda kv: -kv[1][1]))}
    jax["union_s"] = tr.length(inside(
        [iv for st in pt.JAX_STAGES for iv in p.jax(st)])) / 1e9 / n

    pieces = {d: idle_pieces(t, p, d) for d in t.devices}
    dev = max(t.devices, key=lambda d: sum(b - a for a, b, *_ in pieces[d]))
    idle: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for a, b, outer, chain in pieces[dev]:
        idle[outer][chain[-1] if chain else DRIVER] += (b - a) / 1e9 / n
    idle_out = {outer: {"total": sum(by.values()),
                        **dict(sorted(by.items(), key=lambda kv: -kv[1]))}
                for outer, by in idle.items()}
    by_chunk: Dict[int, float] = defaultdict(float)
    for a, b, _, chain in pieces[dev]:
        if "train.chunk" in chain:
            mid = (a + b) / 2
            st = next((st for s, e, st in chunks if s <= mid < e), {})
            by_chunk[st.get("chunk", -1)] += (b - a) / 1e9 / n
    longest = sorted(pieces[dev], key=lambda x: x[0] - x[1])[:top]
    return {"rounds": n, "device": dev, "spans": spans,
            "h2d_mib": h2d / 2 ** 20 / n, "load": load, "jax": jax,
            "idle": idle_out, "chunk_idle_s": dict(sorted(by_chunk.items())),
            "idle_gaps": [[label(x), (x[1] - x[0]) / 1e9] for x in longest]}


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = Path(argv[0])
    if path.is_dir():
        path = tr.find(path)
    print(json.dumps(attribute(tr.load(path), pt.load(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
